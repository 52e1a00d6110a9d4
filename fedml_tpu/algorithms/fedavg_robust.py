"""FedAvg-Robust — defense hooks at aggregation time.

Parity with fedml_api/distributed/fedavg_robust/FedAvgRobustAggregator.py:
norm-diff clipping and weak-DP Gaussian noise applied to each client update
before averaging (:133, :179-207; defense math in
fedml_core/robustness/robust_aggregation.py).

Here the defenses are the cohort engine's ``transform_update`` hook, so the
whole defended round (local training + clip + noise + aggregation) remains
one jit — on a mesh the defense runs shard-local before the psum.

Beyond the reference, ``defense`` also accepts the Byzantine-tolerant
aggregation rules of core/byzantine.py (coordinate_median, trimmed_mean,
krum, multi_krum, geometric_median), which replace the aggregate itself.
"""

from __future__ import annotations

import dataclasses
import logging

from fedml_tpu.algorithms.fedavg import FedAvg, FedAvgConfig
from fedml_tpu.core.byzantine import METHODS as BYZ_METHODS
from fedml_tpu.core.byzantine import make_byzantine_aggregate
from fedml_tpu.core.pallas_agg import (make_fused_robust_aggregate,
                                       pallas_interpret)
from fedml_tpu.core.robust import add_gaussian_noise, clip_update
from fedml_tpu.parallel.cohort import make_cohort_step
from fedml_tpu.trainer.local_sgd import make_local_trainer
from fedml_tpu.trainer.workload import make_client_optimizer

log = logging.getLogger(__name__)


@dataclasses.dataclass
class FedAvgRobustConfig(FedAvgConfig):
    defense: str = "weak_dp"     # clip/DP (reference parity) or a
    #                              Byzantine rule (core/byzantine.py)
    norm_bound: float = 5.0
    stddev: float = 0.025        # reference default for weak DP
    defense_backend: str = "xla"  # "xla" | "pallas" (fused kernel,
    #                                core/pallas_agg.py; single-chip only)
    trim_frac: float = 0.1       # trimmed_mean: fraction cut per side
    byz_f: int = 0               # krum: assumed Byzantine count
    krum_m: int = 1              # multi_krum: how many updates to average
    gm_iters: int = 8            # geometric_median: Weiszfeld iterations
    gm_eps: float = 1e-6         # geometric_median: smoothing floor


class FedAvgRobust(FedAvg):
    DEFENSES = ("norm_diff_clipping", "weak_dp", "none") + BYZ_METHODS

    def __init__(self, workload, data, config: FedAvgRobustConfig, mesh=None, sink=None):
        super().__init__(workload, data, config, mesh=mesh, sink=sink)
        cfg = config
        if cfg.defense not in self.DEFENSES:
            raise ValueError(f"unknown defense {cfg.defense!r}; "
                             f"available: {self.DEFENSES}")
        if cfg.defense_backend not in ("xla", "pallas"):
            raise ValueError(
                f"unknown defense_backend {cfg.defense_backend!r}; "
                f"available: ('xla', 'pallas')")

        opt = make_client_optimizer(cfg.client_optimizer, cfg.lr, cfg.wd)
        local_train = make_local_trainer(workload, opt, cfg.epochs)

        if cfg.defense in BYZ_METHODS:
            # Byzantine rules replace the AGGREGATE (they need the whole
            # cohort: per-coordinate sorts / the pairwise distance matmul),
            # so they ride the single-chip vmap engine; the mesh path's
            # aggregation is a fixed psum and would need an all-gather
            if mesh is not None:
                raise ValueError(
                    f"defense {cfg.defense!r} needs the full cohort on one "
                    "chip (sorts / pairwise distances); drop --mesh_clients")
            if cfg.defense_backend == "pallas":
                raise ValueError(
                    "defense_backend='pallas' fuses clip+noise+mean; "
                    f"Byzantine rule {cfg.defense!r} has its own aggregate "
                    "— use the xla backend")
            if cfg.defense in ("krum", "multi_krum"):
                m = cfg.krum_m if cfg.defense == "multi_krum" else 1
                # the bound is on the LIVE cohort: sample_clients caps the
                # cohort at the dataset's client count, so a small dataset
                # shrinks n below the configured cohort size
                n = min(cfg.client_num_per_round, data.client_num)
                max_m = n - cfg.byz_f - 2
                if m > max_m:
                    raise ValueError(
                        f"multi-Krum needs m <= n - f - 2 = "
                        f"{n} - {cfg.byz_f} - 2 = "
                        f"{max_m}, got m={m}: selecting that many updates "
                        "can include Byzantine ones, silently degenerating "
                        "to a plain mean")
                if n < 2 * cfg.byz_f + 3:
                    # Blanchard et al. 2017 Prop. 1: the (alpha, f)-Byzantine
                    # resilience of Krum additionally needs n >= 2f + 3; below
                    # it the selection can be steered by a near-majority of
                    # attackers.  Warn rather than abort — the rule still runs
                    # and small cohorts are common in tests/simulation.
                    log.warning(
                        "krum robustness guarantee needs n >= 2f + 3 "
                        "(n=%d, f=%d): selection may be defeatable by a "
                        "coordinated near-majority of Byzantine silos",
                        n, cfg.byz_f)
            agg = make_byzantine_aggregate(
                cfg.defense, trim_frac=cfg.trim_frac, byz_f=cfg.byz_f,
                krum_m=cfg.krum_m, gm_iters=cfg.gm_iters, gm_eps=cfg.gm_eps)
            self.cohort_step = make_cohort_step(
                local_train, aggregate=agg,
                client_axis=cfg.client_axis)
            return

        if cfg.defense_backend == "pallas" and cfg.defense != "none":
            # fused clip+noise+mean: one VMEM pass, no transformed [N, D]
            # copies in HBM (core/pallas_agg.py).  The clip norm is global
            # across the cohort, so this path is single-chip; mesh-sharded
            # runs use the XLA transform hook.
            if mesh is not None:
                raise ValueError("defense_backend='pallas' does not shard "
                                 "over a mesh; drop --mesh_clients or use "
                                 "the xla backend")
            fused = make_fused_robust_aggregate(
                norm_bound=(cfg.norm_bound if cfg.defense in
                            ("norm_diff_clipping", "weak_dp") else None),
                noise_std=(cfg.stddev if cfg.defense == "weak_dp" else 0.0),
                interpret=pallas_interpret("robust_aggregate"))
            self.cohort_step = make_cohort_step(
                local_train, aggregate=fused,
                client_axis=cfg.client_axis)
            return

        def transform(client_params, global_params, rng):
            p = client_params
            if cfg.defense in ("norm_diff_clipping", "weak_dp"):
                p = clip_update(p, global_params, cfg.norm_bound)
            if cfg.defense == "weak_dp":
                p = add_gaussian_noise(p, rng, cfg.stddev)
            return p

        self.cohort_step = make_cohort_step(
            local_train, mesh=mesh,
            transform_update=None if cfg.defense == "none" else transform,
            client_axis=cfg.client_axis)
