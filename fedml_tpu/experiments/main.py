"""``python -m fedml_tpu`` — the experiments layer.

Replaces the reference's 5,550-LoC ``fedml_experiments/`` tree (one
``main_*.py`` + shell launcher per algorithm×paradigm) with ONE entry point:
every algorithm in the framework runs end-to-end from a shell, with hermetic
synthetic data when no ``--data_dir`` is given, and the same flag surface as
``main_fedavg.py:46-112`` where flags carry over.

Launch story parity:

* reference: ``sh run_fedavg_distributed_pytorch.sh 10 10 lr mnist ...`` →
  ``mpirun -np 11 -hostfile mpi_host_file python3 main_fedavg.py ...``
* here: ``python -m fedml_tpu --algo fedavg --model lr --dataset mnist
  --client_num_per_round 10 ...`` — on-pod "processes" are mesh shards
  (``--mesh_clients N``); multi-host pods add ``--coordinator_address
  host:port --num_processes P --process_id i`` per host
  (jax.distributed.initialize, fedml_tpu/parallel/mesh.py).

Every run writes ``metrics.jsonl`` + ``summary.json`` into ``--run_dir``
(the wandb-equivalent stream the reference CI asserts on,
CI-script-fedavg.sh:43-48) and prints one final JSON summary line.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import time
from typing import Any, Callable, Dict, Optional

import numpy as np

from fedml_tpu.experiments.config import ExperimentConfig, config_from_argv
from fedml_tpu.experiments.models import create_workload, sample_shape_of
from fedml_tpu.utils.metrics import MetricsSink, profiler_trace

logger = logging.getLogger("fedml_tpu")

RUNNERS: Dict[str, Callable] = {}


def runner(name: str):
    def deco(fn):
        RUNNERS[name] = fn
        return fn
    return deco


# --------------------------------------------------------------------------
# shared plumbing
# --------------------------------------------------------------------------

def load_experiment_data(cfg: ExperimentConfig):
    """Registry dispatch with per-dataset kwargs (the load_data switch,
    main_fedavg.py:115-221)."""
    from fedml_tpu.data import load_data
    kw: Dict[str, Any] = {"batch_size": cfg.batch_size}
    if cfg.dataset in ("cifar10", "cifar100", "cinic10"):
        # client_num partitions the real pickle tree, num_clients sizes
        # the hermetic twin (each loader drops the other's knob)
        kw.update(client_num=cfg.client_num_in_total,
                  num_clients=cfg.client_num_in_total,
                  partition_method=cfg.partition_method,
                  partition_alpha=cfg.partition_alpha,
                  seed=cfg.seed)
    else:
        # twin-only knob; real loaders carry their own client counts
        kw.update(num_clients=cfg.client_num_in_total, seed=cfg.seed)
    t0_ns = time.perf_counter_ns()
    data = load_data(cfg.dataset, data_dir=cfg.data_dir, **kw)
    data.load_ns = (t0_ns, time.perf_counter_ns() - t0_ns)
    return data


def _fedavg_cfg_kwargs(cfg: ExperimentConfig) -> Dict[str, Any]:
    freq = cfg.frequency_of_the_test
    if cfg.ci:
        # CI mode restricts eval to round 0 + the final round (the gate
        # `round_idx % freq == 0` always fires at 0, reference parity:
        # FedAVGAggregator.py:126-131 shrinks eval rather than skipping it)
        freq = max(cfg.comm_round, 1)
    return dict(comm_round=cfg.comm_round,
                client_num_per_round=cfg.client_num_per_round,
                epochs=cfg.epochs, batch_size=cfg.batch_size, lr=cfg.lr,
                client_optimizer=cfg.client_optimizer, wd=cfg.wd,
                frequency_of_the_test=freq, seed=cfg.seed,
                rounds_per_dispatch=cfg.rounds_per_dispatch,
                eval_chunk_clients=cfg.eval_chunk_clients)


def _make_workload(cfg: ExperimentConfig, data):
    """The one place runner code constructs the model workload (threading a
    new construction knob is a one-line change here, not 9 edits)."""
    return create_workload(cfg.model, cfg.dataset, data.class_num,
                           sample_shape_of(data),
                           compute_dtype=cfg.compute_dtype,
                           attn_block_size=cfg.attn_block_size,
                           attn_flash=cfg.attn_flash,
                           moe_experts=cfg.moe_experts,
                           model_config=cfg.model_config)


def _make_checkpointer(cfg: ExperimentConfig):
    if not cfg.checkpoint_dir:
        return None
    from fedml_tpu.utils.checkpoint import RoundCheckpointer
    return RoundCheckpointer(cfg.checkpoint_dir,
                             save_every=cfg.checkpoint_every,
                             async_save=cfg.checkpoint_async,
                             keep_last_n=cfg.checkpoint_keep_last_n)


def _make_perf(cfg: ExperimentConfig):
    """Performance flight recorder (obs/perf.py) for the live actor
    modes: a per-round ``perf.jsonl`` ledger at ``--perf_ledger`` (or
    ``run_dir/perf.jsonl`` under ``--perf``).  Only the SERVER node
    records — silo processes return None.  The runner owns ``close()``
    (stops the RSS sampler thread)."""
    # perf_strict/device_obs imply the recorder: a strict sentry (or a
    # device observatory) with no recorder to own it would be the exact
    # "flag parses then silently never enforces" condition the algo gate
    # in main() rejects
    if not (cfg.perf or cfg.perf_ledger or cfg.perf_strict
            or cfg.device_obs):
        return None
    if cfg.silo_backend != "local" and cfg.node_id != 0:
        return None  # a gRPC silo has no round lifecycle to ledger
    import os
    from fedml_tpu.obs import PerfRecorder
    device = None
    if cfg.device_obs:
        # device & compile observatory (obs/device.py): every ledger
        # line gains a device section; the hot jits built below wrap
        # through PerfRecorder.instrument_jit / the device= seams
        from fedml_tpu.obs import DeviceRecorder
        device = DeviceRecorder()
    path = cfg.perf_ledger or os.path.join(
        cfg.metrics_dir or cfg.run_dir or ".", "perf.jsonl")
    return PerfRecorder(path, node=f"node{cfg.node_id}",
                        strict_recompiles=cfg.perf_strict, device=device)


def _make_health(cfg: ExperimentConfig, kind: str,
                 suppress_payload=None):
    """Federation health observatory (obs/health.py) for the live actor
    modes: streaming learning-health stats + a ``health.jsonl`` ledger
    at ``--health_ledger`` (or ``run_dir/health.jsonl`` under
    ``--health``).  Only the SERVER node accumulates.  Drift-alarm
    thresholds ride the same ``--slo`` spec as every other objective
    (health_misalignment_ratio / health_norm_cv_ratio /
    health_starvation_ratio); non-health names in the spec are simply
    not thresholds here."""
    if not (cfg.health or cfg.health_ledger):
        return None
    if cfg.silo_backend != "local" and cfg.node_id != 0:
        return None  # a gRPC silo has no round lifecycle to observe
    import os
    from fedml_tpu.obs import HealthAccumulator
    from fedml_tpu.obs.health import HEALTH_SLOS
    from fedml_tpu.obs.perf import parse_slo_spec
    path = cfg.health_ledger or os.path.join(
        cfg.metrics_dir or cfg.run_dir or ".", "health.jsonl")
    spec = parse_slo_spec(cfg.slo) if cfg.slo else {}
    thresholds = {k: v for k, v in spec.items() if k in HEALTH_SLOS}
    return HealthAccumulator(kind=kind, node=f"node{cfg.node_id}",
                             ledger_path=path, thresholds=thresholds,
                             suppress_payload=suppress_payload)


def _make_journal(cfg: ExperimentConfig, subdir: Optional[str] = None):
    """Durable round journal (utils/journal.py) for the live actor
    modes: crash-safe per-accept records + periodic atomic fold-state
    snapshots under ``--journal_dir`` (or ``run_dir/journal`` under
    ``--journal``).  Only the SERVER node journals; under the edge
    topology each edge gets its own ``edge{e}`` subdirectory."""
    if not (cfg.journal or cfg.journal_dir):
        return None
    if cfg.silo_backend != "local" and cfg.node_id != 0:
        return None  # a gRPC silo has no fold state to journal
    import os
    from fedml_tpu.utils.journal import RoundJournal
    base = cfg.journal_dir or os.path.join(
        cfg.metrics_dir or cfg.run_dir or ".", "journal")
    path = os.path.join(base, subdir) if subdir else base
    if not cfg.checkpoint_dir:
        logger.warning("--journal without --checkpoint_dir: mid-round "
                       "recovery needs the round-boundary checkpoint to "
                       "resume against; the journal will record but a "
                       "restarted server starts from round 0")
    elif cfg.checkpoint_every != 1:
        logger.warning("--journal with --checkpoint_every %d: mid-round "
                       "recovery only engages when the crashed round "
                       "directly follows a checkpointed one; set "
                       "--checkpoint_every 1 for full coverage",
                       cfg.checkpoint_every)
    return RoundJournal(path, snapshot_every=cfg.journal_snapshot_every,
                        node=subdir or f"node{cfg.node_id}")


def _compose_extra_state(named):
    """Fold several named ``(get_fn, set_fn)`` pairs into the one
    ``extra_state`` checkpoint hook: the saved tree is a dict keyed by
    name (fixed shapes per entry, so the whole composite still doubles
    as the orbax restore template).  A restored tree missing a name (a
    checkpoint from before that subsystem existed) warns and restores
    what is there."""
    named = [(n, gs) for n, gs in named if gs is not None]
    if not named:
        return None

    def get():
        return {name: g() for name, (g, _) in named}

    def set_(tree):
        if not hasattr(tree, "get"):
            logger.warning("checkpoint extra-state is not the named-dict "
                           "schema (pre-composition checkpoint?); "
                           "skipping extra-state restore")
            return
        for name, (_, s) in named:
            sub = tree.get(name)
            if sub is None:
                logger.warning("checkpoint extra-state has no %r entry; "
                               "that subsystem starts fresh", name)
                continue
            s(sub)

    return (get, set_)


def _make_server_opt(cfg: ExperimentConfig, template, *, plan=None,
                     sentry=None, device=None):
    """The live server-optimizer seam (fedml_tpu/server_opt, ISSUE 18).
    ``plain`` returns None — the actors then keep the pre-seam
    ``params = finalize(...)`` assignment byte-for-byte, which IS the
    bit-identity parity contract."""
    if cfg.server_opt == "plain":
        return None
    from fedml_tpu.server_opt import ServerOptimizer
    return ServerOptimizer(
        cfg.server_opt, template, lr=cfg.server_lr,
        momentum=cfg.server_momentum,
        beta1=cfg.server_adam_beta1, beta2=cfg.server_adam_beta2,
        eps=cfg.server_adam_eps,
        fedac_mu=cfg.fedac_mu, fedac_gamma=cfg.fedac_gamma,
        fedac_alpha=cfg.fedac_alpha, fedac_beta=cfg.fedac_beta,
        local_steps=cfg.epochs, plan=plan, sentry=sentry, device=device)


def _make_controller(cfg: ExperimentConfig, *, cohort, epochs,
                     wave_size=0, max_cohort=None, epochs_live=False):
    """The health-driven adaptive round controller (--adaptive)."""
    if not cfg.adaptive:
        return None
    from fedml_tpu.server_opt import AdaptiveController
    return AdaptiveController(
        cohort=cohort, epochs=epochs, wave_size=wave_size,
        min_cohort=cfg.adapt_min_cohort, max_cohort=max_cohort,
        patience=cfg.adapt_patience, epochs_live=epochs_live)


def _degrade_setup(cfg: ExperimentConfig, n_silos: int,
                   mode: str = "sync"):
    """The sustained-degradation spine (--min_quorum /
    --adaptive_deadline / --partition_frac → robust/degrade.py,
    ISSUE 19), with fail-loud config gates: every misconfiguration is a
    NAMED error at startup, never a silently-ignored flag.  ``mode``:
    "sync" (cross_silo round barrier), "async" (the watchdog is the
    deadline analog; barrier flags are refused by name)."""
    wanted = (cfg.min_quorum > 0 or cfg.adaptive_deadline
              or cfg.partition_frac > 0)
    if not wanted:
        return None
    if not 0.0 < cfg.min_quorum <= 1.0 and cfg.min_quorum != 0.0:
        raise ValueError(
            f"--min_quorum must be in (0, 1] (a cohort fraction), got "
            f"{cfg.min_quorum}")
    if mode == "async":
        if cfg.min_quorum > 0 or cfg.partition_frac > 0:
            raise ValueError(
                "--min_quorum/--partition_frac adjudicate the sync round "
                "barrier; the async server has no barrier to close — "
                "only --adaptive_deadline (the watchdog analog) applies")
        if not cfg.retask_timeout_s:
            raise ValueError(
                "--adaptive_deadline under --algo async_fl adapts the "
                "re-task watchdog and needs --retask_timeout_s > 0 (the "
                "ceiling and cold-start fallback)")
    elif mode == "sync":
        if cfg.straggler_policy != "drop":
            raise ValueError(
                "--min_quorum/--adaptive_deadline/--partition_frac "
                "adjudicate the close-early deadline, which only the "
                "'drop' straggler policy has; use --straggler_policy "
                "drop (wait never closes early, abort never degrades "
                "gracefully)")
        if (cfg.adaptive_deadline or cfg.partition_frac > 0) \
                and not cfg.round_timeout_s:
            raise ValueError(
                "--adaptive_deadline/--partition_frac need "
                "--round_timeout_s > 0: the static timeout is the "
                "deadline's ceiling and the cold-start fallback, and "
                "without a timer the deadline can never fire")
    if cfg.partition_frac > 0 and not 0.0 < cfg.partition_frac <= 1.0:
        raise ValueError(
            f"--partition_frac must be in (0, 1] (a cohort fraction), "
            f"got {cfg.partition_frac}")
    if cfg.partition_frac > 0 and cfg.min_quorum > 0 \
            and cfg.partition_frac > 1.0 - cfg.min_quorum + 1e-9:
        raise ValueError(
            f"--partition_frac {cfg.partition_frac} exceeds the quorum "
            f"gap 1 - min_quorum = {1.0 - cfg.min_quorum:.3f}: a miss "
            f"that large already blocks the quorum, so the partition "
            f"hold would be unreachable dead code — lower "
            f"--partition_frac or --min_quorum")
    from fedml_tpu.robust.degrade import ReliabilityTracker
    return ReliabilityTracker(
        n_silos,
        min_quorum=cfg.min_quorum,
        adaptive_deadline=cfg.adaptive_deadline,
        deadline_floor_s=cfg.deadline_floor_s,
        deadline_quantile=cfg.deadline_quantile,
        deadline_slack=cfg.deadline_slack,
        partition_frac=cfg.partition_frac,
        partition_max_holds=cfg.partition_max_holds)


def _make_slo(cfg: ExperimentConfig):
    """SLO evaluator over the telemetry registry (obs/perf.py) backing
    the serve frontend's ``/healthz?deep=1``; ``--slo`` overrides the
    default objectives.  Needs live telemetry — with the registry
    disabled every objective would read vacuously healthy, so return
    None (the frontend then answers ``deep: unconfigured``)."""
    from fedml_tpu.obs import telemetry as _tel
    if not _tel.get_registry().enabled:
        if cfg.slo:
            logger.warning("--slo given but telemetry is disabled; the "
                           "deep health check needs --telemetry true")
        return None
    from fedml_tpu.obs.perf import SloEvaluator, parse_slo_spec
    thresholds = parse_slo_spec(cfg.slo) if cfg.slo else None
    return SloEvaluator(thresholds=thresholds)


def _eval_global(workload, params, data) -> Dict[str, float]:
    """Train/test accuracy over all clients (the per-runner summary for
    algorithms that don't track their own history)."""
    import jax
    from fedml_tpu.parallel.cohort import cohort_eval
    from fedml_tpu.trainer.local_sgd import make_evaluator
    ev = cohort_eval(make_evaluator(workload))
    out = {}
    for split, stacked in (("train", data.train), ("test", data.test)):
        if stacked is None:
            continue
        from fedml_tpu.utils.metrics import stats_from_metrics
        m = ev(params, {k: jax.numpy.asarray(v) for k, v in stacked.items()})
        out.update(stats_from_metrics(m, prefix=f"{split}_"))
    return out


def _release_eval_fn(workload, data):
    """Held-out scorer for the release gate: test accuracy, higher is
    better.  None when the dataset has no test split — the eval signal
    then passes vacuously (and says so in the verdict) instead of
    scoring candidates on training data."""
    if data.test is None:
        return None
    import jax
    from fedml_tpu.parallel.cohort import cohort_eval
    from fedml_tpu.trainer.local_sgd import make_evaluator
    from fedml_tpu.utils.metrics import stats_from_metrics
    ev = cohort_eval(make_evaluator(workload))
    test = {k: jax.numpy.asarray(v) for k, v in data.test.items()}

    def score(params):
        return stats_from_metrics(ev(params, test))["acc"]

    return score


def _first_cohort(data, n: int):
    """Deterministic cohort of the first n clients (for cohort-input
    algorithms: FedNAS / FedGKT / FedGAN)."""
    from fedml_tpu.data.stacking import gather_cohort
    ids = np.arange(min(n, data.client_num))
    return gather_cohort(data.train, ids, pad_to=n)


def _image_sample_shape(cfg, data, algo: str):
    shape = sample_shape_of(data)
    if len(shape) != 3:
        raise ValueError(
            f"--algo {algo} needs image-shaped data [H, W, C]; dataset "
            f"{cfg.dataset!r} yields {shape}. Try --dataset femnist or "
            f"cifar10.")
    return shape


# --------------------------------------------------------------------------
# FedAvg family
# --------------------------------------------------------------------------

@runner("fedavg")
def run_fedavg(cfg, data, mesh, sink):
    from fedml_tpu.algorithms.fedavg import FedAvg, FedAvgConfig
    wl = _make_workload(cfg, data)
    if cfg.mesh_sequence > 0:
        # dp x sp: long-context federated training over a [clients,
        # sequence] mesh (parallel/sequence.py) — ring attention + psum'd
        # loss/grads inside each client, weighted psum across the cohort.
        # The dense workload still drives init + eval (params identical).
        from fedml_tpu.models import TransformerLM
        from fedml_tpu.parallel.sequence import (
            make_sp_cohort_step, make_sp_mesh, make_sp_nwp_workload)
        from fedml_tpu.trainer.workload import make_client_optimizer
        if cfg.model != "transformer":
            raise ValueError("--mesh_sequence requires --model transformer "
                             "(the ring-attention-capable model)")
        if cfg.moe_experts:
            raise ValueError(
                "--moe_experts with --mesh_sequence is not supported: the "
                "sequence-parallel loss path does not capture the Switch "
                "load-balance loss (it would silently train with zero "
                "balancing pressure); drop one of the flags")
        if not cfg.attn_block_size:
            logging.getLogger(__name__).warning(
                "--mesh_sequence without --attn_block_size: init/eval run "
                "single-chip attention (auto-blockwise past 1024 tokens "
                "when a block of 64-512 divides T, DENSE O(T^2) scores "
                "otherwise); set --attn_block_size to pin the "
                "memory-efficient path")
        if mesh is not None:
            raise ValueError("--mesh_sequence and --mesh_clients build one "
                             "combined [clients, sequence] mesh; pass "
                             "--mesh_sequence S with client sharding "
                             "implied by the remaining devices")
        import jax
        n_dev = len(jax.devices())
        n_cli = max(1, n_dev // cfg.mesh_sequence)
        algo = FedAvg(wl, data, FedAvgConfig(**_fedavg_cfg_kwargs(cfg)),
                      mesh=None, sink=sink)
        sp_wl = make_sp_nwp_workload(wl.model)
        algo.cohort_step = make_sp_cohort_step(
            sp_wl, make_client_optimizer(cfg.client_optimizer, cfg.lr,
                                         cfg.wd),
            cfg.epochs, mesh=make_sp_mesh(
                n_cli, cfg.mesh_sequence,
                devices=jax.devices()[:n_cli * cfg.mesh_sequence]))
    else:
        algo = FedAvg(wl, data, FedAvgConfig(**_fedavg_cfg_kwargs(cfg)),
                      mesh=mesh, sink=sink)
    algo.run(checkpointer=_make_checkpointer(cfg))
    return algo.history[-1] if algo.history else {}


@runner("fedprox")
def run_fedprox(cfg, data, mesh, sink):
    from fedml_tpu.algorithms.fedprox import FedProx, FedProxConfig
    wl = _make_workload(cfg, data)
    algo = FedProx(wl, data,
                   FedProxConfig(mu=cfg.mu, **_fedavg_cfg_kwargs(cfg)),
                   mesh=mesh, sink=sink)
    algo.run(checkpointer=_make_checkpointer(cfg))
    return algo.history[-1] if algo.history else {}


@runner("fedopt")
def run_fedopt(cfg, data, mesh, sink):
    from fedml_tpu.algorithms.fedopt import FedOpt, FedOptConfig
    wl = _make_workload(cfg, data)
    algo = FedOpt(wl, data, FedOptConfig(
        server_optimizer=cfg.server_optimizer, server_lr=cfg.server_lr,
        server_momentum=cfg.server_momentum, **_fedavg_cfg_kwargs(cfg)),
        mesh=mesh, sink=sink)
    algo.run(checkpointer=_make_checkpointer(cfg))
    return algo.history[-1] if algo.history else {}


@runner("fednova")
def run_fednova(cfg, data, mesh, sink):
    from fedml_tpu.algorithms.fednova import FedNova, FedNovaConfig
    wl = _make_workload(cfg, data)
    algo = FedNova(wl, data, FedNovaConfig(
        mu=cfg.mu if cfg.mu else 0.0, gmf=cfg.gmf,
        **_fedavg_cfg_kwargs(cfg)), mesh=mesh, sink=sink)
    algo.run(checkpointer=_make_checkpointer(cfg))
    return algo.history[-1] if algo.history else {}


@runner("fedavg_robust")
def run_fedavg_robust(cfg, data, mesh, sink):
    from fedml_tpu.algorithms.fedavg_robust import (FedAvgRobust,
                                                    FedAvgRobustConfig)
    wl = _make_workload(cfg, data)
    targeted = None
    if cfg.backdoor:
        # poison the first K clients' shards + track targeted-task accuracy
        # (FedAvgRobustAggregator.test_target_accuracy:270)
        from fedml_tpu.algorithms.backdoor import (make_targeted_test_set,
                                                   poison_federated_data)
        shape = _image_sample_shape(cfg, data, "fedavg_robust --backdoor")
        del shape
        attackers = list(range(min(cfg.attacker_num, data.client_num)))
        eval_src = data.test if data.test is not None else data.train
        honest = np.arange(len(attackers), data.client_num)
        x_eval = np.asarray(eval_src["x"])[honest]
        y_eval = np.asarray(eval_src["y"])[honest]
        m_eval = np.asarray(eval_src["mask"])[honest].reshape(-1) > 0
        x_eval = x_eval.reshape((-1,) + x_eval.shape[3:])[m_eval]
        y_eval = y_eval.reshape(-1)[m_eval]
        targeted = make_targeted_test_set(
            x_eval, y_eval, cfg.target_label, trigger_size=cfg.trigger_size)
        data = poison_federated_data(
            data, attackers, cfg.target_label, cfg.poison_frac,
            cfg.trigger_size, seed=cfg.seed)
    algo = FedAvgRobust(wl, data, FedAvgRobustConfig(
        defense=cfg.defense, norm_bound=cfg.norm_bound, stddev=cfg.stddev,
        defense_backend=cfg.defense_backend, trim_frac=cfg.trim_frac,
        byz_f=cfg.byz_f, krum_m=cfg.krum_m,
        gm_iters=cfg.gm_iters, gm_eps=cfg.gm_eps,
        **_fedavg_cfg_kwargs(cfg)), mesh=mesh, sink=sink)
    params = algo.run(checkpointer=_make_checkpointer(cfg))
    out = dict(algo.history[-1]) if algo.history else {}
    if targeted is not None:
        from fedml_tpu.algorithms.backdoor import targeted_accuracy
        out["backdoor_acc"] = targeted_accuracy(wl, params, targeted)
        sink.log({"backdoor_acc": out["backdoor_acc"]},
                 step=cfg.comm_round - 1)
    return out


@runner("hierarchical")
def run_hierarchical(cfg, data, mesh, sink):
    from fedml_tpu.algorithms.hierarchical import (HierarchicalConfig,
                                                   HierarchicalFedAvg)
    wl = _make_workload(cfg, data)
    algo = HierarchicalFedAvg(wl, data, HierarchicalConfig(
        group_num=cfg.group_num, group_comm_round=cfg.group_comm_round,
        **_fedavg_cfg_kwargs(cfg)), mesh=mesh, sink=sink)
    algo.run(checkpointer=_make_checkpointer(cfg))
    return algo.history[-1] if algo.history else {}


# --------------------------------------------------------------------------
# other paradigms
# --------------------------------------------------------------------------

@runner("centralized")
def run_centralized(cfg, data, mesh, sink):
    import jax
    from fedml_tpu.algorithms.centralized import CentralizedTrainer
    wl = _make_workload(cfg, data)
    trainer = CentralizedTrainer(wl, lr=cfg.lr,
                                 client_optimizer=cfg.client_optimizer,
                                 wd=cfg.wd, epochs_per_call=cfg.epochs)
    train = {k: jax.numpy.asarray(v) for k, v in data.train_global.items()}
    sample = jax.tree.map(lambda v: v[0], train)
    params = wl.init(jax.random.key(cfg.seed), sample)
    rng = jax.random.key(cfg.seed)
    for r in range(cfg.comm_round):
        rng, rr = jax.random.split(rng)
        params = trainer.train_rounds(params, train, 1, rr)
        if r % cfg.frequency_of_the_test == 0 or r == cfg.comm_round - 1:
            stats = {"train_" + k: v
                     for k, v in trainer.metrics(params, train).items()}
            if data.test_global is not None:
                stats.update({"test_" + k: v for k, v in trainer.metrics(
                    params, data.test_global).items()})
            stats["round"] = r
            sink.log(stats, step=r)
    return stats


@runner("decentralized")
def run_decentralized(cfg, data, mesh, sink):
    from fedml_tpu.algorithms.decentralized import (DecentralizedConfig,
                                                    DecentralizedGossip)
    wl = _make_workload(cfg, data)
    algo = DecentralizedGossip(wl, data, DecentralizedConfig(
        comm_round=cfg.comm_round, epochs=cfg.epochs,
        batch_size=cfg.batch_size, lr=cfg.lr,
        client_optimizer=cfg.client_optimizer, wd=cfg.wd,
        neighbor_num=cfg.neighbor_num,
        frequency_of_the_test=cfg.frequency_of_the_test, seed=cfg.seed),
        mesh=mesh)
    algo.run()
    for h in algo.history:
        sink.log(h, step=h.get("round"))
    return algo.history[-1] if algo.history else {}


@runner("decentralized_online")
def run_decentralized_online(cfg, data, mesh, sink):
    """DSGD / PushSum online learning on streaming UCI data (standalone/
    decentralized main_dol.py surface: --mode --iteration_number --beta
    --b_symmetric --time_varying --topology_neighbors_num_*)."""
    import os
    from fedml_tpu.algorithms.decentralized_online import (
        DecentralizedOnlineConfig, run_decentralized_online as run_dol)
    from fedml_tpu.data.uci import load_streaming_uci, synthetic_stream
    n = min(cfg.client_num_in_total, 128)
    total = cfg.iteration_number * n
    if cfg.data_dir and cfg.dataset.upper() in ("SUSY", "RO"):
        path = cfg.data_dir if os.path.isfile(cfg.data_dir) else os.path.join(
            cfg.data_dir, "SUSY.csv" if cfg.dataset.upper() == "SUSY"
            else "datatraining.txt")
        stream = load_streaming_uci(cfg.dataset, path, list(range(n)),
                                    total, cfg.beta, seed=cfg.seed)
    else:
        stream = synthetic_stream(num_clients=n, total=total,
                                  beta=cfg.beta, seed=cfg.seed)
    out = run_dol(stream, DecentralizedOnlineConfig(
        mode=cfg.mode, iteration_number=cfg.iteration_number,
        epochs=cfg.epochs, learning_rate=cfg.lr, weight_decay=cfg.wd,
        b_symmetric=cfg.b_symmetric,
        topology_neighbors_num_undirected=cfg.topology_neighbors_num_undirected,
        topology_neighbors_num_directed=cfg.topology_neighbors_num_directed,
        time_varying=cfg.time_varying, seed=cfg.seed))
    for h in out["history"][:: max(len(out["history"]) // 50, 1)]:
        sink.log(h, step=h["iteration"])
    return {"final_regret": out["final_regret"],
            "accuracy": out["accuracy"]}


@runner("scaffold")
def run_scaffold(cfg, data, mesh, sink):
    """SCAFFOLD control-variate FL (beyond the reference's list —
    algorithms/scaffold.py)."""
    from fedml_tpu.algorithms.scaffold import Scaffold, ScaffoldConfig
    wl = _make_workload(cfg, data)
    algo = Scaffold(wl, data, ScaffoldConfig(**_fedavg_cfg_kwargs(cfg)),
                    mesh=mesh, sink=sink)
    algo.run(checkpointer=_make_checkpointer(cfg))
    return algo.history[-1] if algo.history else {}


@runner("ditto")
def run_ditto(cfg, data, mesh, sink):
    """Ditto personalized FL (beyond the reference's list —
    algorithms/ditto.py): the FedAvg global stream unchanged, plus
    per-client personalized models trained with a λ proximal pull toward
    the globals; history carries personal_{train,test}_acc columns."""
    from fedml_tpu.algorithms.ditto import Ditto, DittoConfig
    wl = _make_workload(cfg, data)
    algo = Ditto(wl, data, DittoConfig(
        ditto_lambda=cfg.ditto_lambda, personal_lr=cfg.personal_lr,
        personal_epochs=cfg.personal_epochs, **_fedavg_cfg_kwargs(cfg)),
        mesh=mesh, sink=sink)
    algo.run(checkpointer=_make_checkpointer(cfg))
    return algo.history[-1] if algo.history else {}


@runner("feddyn")
def run_feddyn(cfg, data, mesh, sink):
    """FedDyn dynamic regularization (beyond the reference's list —
    algorithms/feddyn.py): per-client λ corrections make the federated
    fixed point coincide with the centralized optimum under drift."""
    from fedml_tpu.algorithms.feddyn import FedDyn, FedDynConfig
    wl = _make_workload(cfg, data)
    algo = FedDyn(wl, data, FedDynConfig(
        feddyn_alpha=cfg.feddyn_alpha, **_fedavg_cfg_kwargs(cfg)),
        mesh=mesh, sink=sink)
    algo.run(checkpointer=_make_checkpointer(cfg))
    return algo.history[-1] if algo.history else {}


@runner("fedac")
def run_fedac(cfg, data, mesh, sink):
    """FedAC accelerated federated SGD (beyond the reference —
    algorithms/fedac.py, arXiv:2006.08950): Nesterov-coupled local steps;
    --fedac_mu derives the paper's (gamma, alpha, beta) coupling."""
    from fedml_tpu.algorithms.fedac import FedAC, FedACConfig
    wl = _make_workload(cfg, data)
    algo = FedAC(wl, data, FedACConfig(
        fedac_mu=cfg.fedac_mu, fedac_gamma=cfg.fedac_gamma,
        fedac_alpha=cfg.fedac_alpha, fedac_beta=cfg.fedac_beta,
        **_fedavg_cfg_kwargs(cfg)), mesh=mesh, sink=sink)
    algo.run(checkpointer=_make_checkpointer(cfg))
    return algo.history[-1] if algo.history else {}


@runner("dp_fedavg")
def run_dp_fedavg(cfg, data, mesh, sink):
    """User-level DP FedAvg with a real RDP accountant (beyond the
    reference's unaccounted weak DP, robust_aggregation.py:51-55 —
    algorithms/dp_fedavg.py): clipped uniform mean + central Gaussian
    noise; every eval row reports the (ε, δ) actually spent."""
    from fedml_tpu.algorithms.dp_fedavg import DPFedAvg, DPFedAvgConfig
    wl = _make_workload(cfg, data)
    algo = DPFedAvg(wl, data, DPFedAvgConfig(
        dp_clip=cfg.dp_clip,
        dp_noise_multiplier=cfg.dp_noise_multiplier,
        dp_delta=cfg.dp_delta, dp_accounting=cfg.dp_accounting,
        **_fedavg_cfg_kwargs(cfg)),
        mesh=mesh, sink=sink)
    algo.run(checkpointer=_make_checkpointer(cfg))
    return algo.history[-1] if algo.history else {}


def _pp_workload(cfg, data):
    """--mesh_stages: silo-local GPipe pipeline over the transformer block
    stack (parallel/pipeline.py) — the deployment for silos whose model is
    too deep for one chip.  Same TransformerLM hyperparameters as
    create_workload's dense path; composes with --moe_experts (the Switch
    balance loss rides the schedule's scan carry, pipeline.py)."""
    import jax
    from fedml_tpu.parallel.pipeline import (PipelineLM, make_pp_nwp_workload,
                                             make_stage_mesh)
    if cfg.model != "transformer":
        raise ValueError("--mesh_stages requires --model transformer "
                         "(the stacked-block PipelineLM)")
    shape = sample_shape_of(data)
    if len(shape) != 1:
        raise ValueError(f"--mesh_stages needs a sequence dataset "
                         f"(next-word prediction); got sample shape {shape}")
    n_dev = len(jax.devices())
    if n_dev < cfg.mesh_stages:
        raise ValueError(f"--mesh_stages {cfg.mesh_stages} exceeds the "
                         f"{n_dev} available devices")
    # TransformerLM's dense defaults (experiments/models.py) in stacked
    # form; the block count grows to one-per-stage past the default 2
    plm = PipelineLM(vocab_size=data.class_num, d_model=128, n_heads=4,
                     n_layers=max(2, cfg.mesh_stages), d_ff=512,
                     max_len=2048, moe_experts=cfg.moe_experts)
    mesh = make_stage_mesh(cfg.mesh_stages,
                           devices=jax.devices()[:cfg.mesh_stages])
    n_micro = cfg.pp_microbatches or cfg.mesh_stages
    if cfg.batch_size % n_micro:
        raise ValueError(f"--batch_size {cfg.batch_size} must divide into "
                         f"{n_micro} GPipe microbatches (--pp_microbatches)")
    return make_pp_nwp_workload(plm, mesh, n_micro=n_micro)


def _silo_training_setup(cfg, data, wl, perf=None):
    """Shared silo-side machinery for the sync (cross_silo) and async
    (async_fl) actor modes: the initial global params and the per-silo
    ``train_fn(params, client_idx, round_idx)`` factory.

    The rng chain reproduces FedAvg.run exactly (key(seed) -> init split
    -> one split per round -> per-cohort-slot fold_in) so the message
    choreography lands bit-comparably with the in-jit cohort engine —
    every node derives the chain deterministically from (seed, round).
    The chain advances incrementally (O(R) total, not O(R^2)); a
    backwards query (never happens in a normal run) restarts it."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.trainer.local_sgd import (instrument_train_fn,
                                             make_local_trainer)
    from fedml_tpu.trainer.workload import make_client_optimizer

    jitted = jax.jit(make_local_trainer(
        wl, make_client_optimizer(cfg.client_optimizer, cfg.lr, cfg.wd),
        cfg.epochs))
    if perf is not None:
        # flight recorder: the local trainer jit is a registered hot
        # function — the sentry counts any round that grows its cache,
        # and under --device_obs instrument_jit wraps it so each compile
        # lands in the named compile ledger (wall time + arg signature)
        # and its cost-analysis FLOPs feed the live MFU gauge
        jitted = perf.instrument_jit("train_fn", jitted)
    # instrument_train_fn is the identity when telemetry is disabled;
    # it composes OUTSIDE the device wrapper (both forward _cache_size)
    local = instrument_train_fn(jitted, epochs=cfg.epochs)
    import threading
    _chain = {"next_round": 0,
              "rng": jax.random.split(jax.random.key(cfg.seed))[0]}
    # the chaos CLI mode drives silos on separate THREADS sharing this
    # chain; an unlocked advance would over-step next_round and silently
    # break the (seed, round) determinism contract
    _chain_lock = threading.Lock()

    def _round_rng(round_idx):
        with _chain_lock:
            if round_idx < _chain["next_round"] - 1:
                _chain["next_round"] = 0
                _chain["rng"] = jax.random.split(jax.random.key(cfg.seed))[0]
            if round_idx == _chain["next_round"] - 1:
                return _chain["last"]
            while _chain["next_round"] <= round_idx:
                _chain["rng"], _chain["last"] = \
                    jax.random.split(_chain["rng"])
                _chain["next_round"] += 1
            return _chain["last"]

    def make_train_fn(silo_id, shard_transform=None):
        # shard_transform(shard, client_idx, round_idx) -> shard: the
        # adversary harness's data-poisoning seam (robust/adversary.py
        # backdoor) — the silo genuinely trains on the transformed shard
        def train_fn(params, client_idx, round_idx):
            shard = {k: data.train[k][client_idx]
                     for k in ("x", "y", "mask")}
            if shard_transform is not None:
                shard = shard_transform(shard, client_idx, round_idx)
            shard = {k: jnp.asarray(v) for k, v in shard.items()}
            rng = jax.random.fold_in(_round_rng(round_idx), silo_id - 1)
            new, _ = local(params, shard, rng)
            return new, float(data.train["num_samples"][client_idx])
        return train_fn

    sample = jax.tree.map(lambda v: jnp.asarray(v[0, 0]),
                          {k: data.train[k] for k in ("x", "y", "mask")})
    _, init_rng = jax.random.split(jax.random.key(cfg.seed))
    return wl.init(init_rng, sample), make_train_fn


def _robust_setup(cfg: ExperimentConfig, template, kind: str, sentry=None,
                  device=None):
    """Payload-defense wiring shared by the sync and async actor modes
    (fedml_tpu/robust): the admission pipeline (``--admission`` — 'auto'
    arms it whenever any defense flag is set) and the aggregation
    regime.  Returns ``(admission, defended_aggregate, stream_agg)``:
    ``--agg_mode stack`` yields the jit-once defended aggregate over the
    staged ``[cohort, ...]`` buffer (``defended_aggregate``; None when
    every defense flag is off — the legacy exact weighted mean runs);
    ``--agg_mode stream`` yields a `StreamingAggregator` instead
    (``stream_agg``, ALWAYS set — plain mean streams too; that is the
    O(model)-memory point), and ``defended_aggregate`` stays None.
    ``sentry``: the flight recorder's RecompileSentry — the hot
    aggregation jit registers so a retracing round is counted/failed.
    ``device``: the flight recorder's DeviceRecorder (--device_obs) —
    the hot aggregation jits wrap through its compile-ledger/FLOPs
    instrumentation."""
    if cfg.admission not in ("auto", "on", "off"):
        raise ValueError(f"--admission must be auto|on|off, "
                         f"got {cfg.admission!r}")
    from fedml_tpu.core.stream_agg import STREAM_MODES
    if cfg.agg_mode not in STREAM_MODES:
        raise ValueError(f"--agg_mode must be one of {STREAM_MODES}, "
                         f"got {cfg.agg_mode!r}")
    robust_on = (cfg.robust_agg != "mean" or cfg.norm_clip > 0
                 or cfg.agg_noise_std > 0)
    # 'auto' also arms the screen under payload corruption: a corrupted
    # compressed frame can make the DECODER itself throw, and without
    # admission that exception kills the server event loop mid-run
    # (adversary flags alone do NOT arm it — the undefended-under-attack
    # baseline must stay runnable)
    screen_on = robust_on or cfg.chaos_corrupt > 0
    admission = defended = None
    if cfg.admission == "on" or (cfg.admission == "auto" and screen_on):
        from fedml_tpu.robust import AdmissionPipeline, TrustTracker
        admission = AdmissionPipeline(
            template, kind=kind, max_num_samples=cfg.max_num_samples,
            norm_k=cfg.norm_screen_k, norm_window=cfg.norm_screen_window,
            norm_min_history=cfg.norm_screen_min_history,
            trust=TrustTracker(
                strikes_to_quarantine=cfg.strikes_to_quarantine,
                quarantine_rounds=cfg.quarantine_rounds,
                probation_rounds=cfg.probation_rounds))
    if cfg.agg_mode == "stream":
        from fedml_tpu.core.stream_agg import StreamingAggregator
        stream = StreamingAggregator(
            template, method=cfg.robust_agg, kind=kind,
            norm_clip=cfg.norm_clip, noise_std=cfg.agg_noise_std,
            seed=cfg.seed, reservoir_k=cfg.stream_reservoir,
            trim_frac=cfg.trim_frac, byz_f=cfg.byz_f, krum_m=cfg.krum_m,
            gm_iters=cfg.gm_iters, gm_eps=cfg.gm_eps, sentry=sentry,
            device=device)
        return admission, None, stream
    if robust_on:
        from fedml_tpu.robust import make_defended_aggregate
        defended = make_defended_aggregate(
            cfg.robust_agg, trim_frac=cfg.trim_frac, byz_f=cfg.byz_f,
            krum_m=cfg.krum_m, gm_iters=cfg.gm_iters, gm_eps=cfg.gm_eps,
            norm_clip=cfg.norm_clip, noise_std=cfg.agg_noise_std,
            seed=cfg.seed, sentry=sentry, device=device)
    return admission, defended, None


def _adversary_train_fns(cfg: ExperimentConfig, data, make_train_fn,
                         n_silos: int):
    """Wrap the silo train-fn factory with the ``--adversary`` spec
    (fedml_tpu/robust/adversary.py): listed silos run their seeded attack
    over the real message path; everyone else is untouched."""
    if not cfg.adversary:
        return make_train_fn
    from fedml_tpu.robust import (make_backdoor_shard_transform,
                                  make_malicious_train_fn,
                                  parse_adversary_spec)
    adversaries = parse_adversary_spec(cfg.adversary)
    bad = sorted(s for s in adversaries if s > n_silos)
    if bad:
        raise ValueError(f"--adversary names silos {bad} but the "
                         f"deployment has only {n_silos} silos (ids 1.."
                         f"{n_silos})")

    def wrapped(silo_id):
        atk = adversaries.get(silo_id)
        if atk is None:
            return make_train_fn(silo_id)
        transform = None
        if atk.kind == "backdoor":
            _image_sample_shape(cfg, data,
                                f"--adversary backdoor (silo {silo_id})")
            target = int(atk.param) if atk.param >= 0 else cfg.target_label
            transform = make_backdoor_shard_transform(
                target, trigger_size=cfg.trigger_size,
                poison_frac=cfg.poison_frac, seed=cfg.seed)
        return make_malicious_train_fn(atk, make_train_fn(silo_id,
                                                          transform),
                                       silo_id, seed=cfg.seed)

    return wrapped


@runner("async_fl")
def run_async_fl(cfg, data, mesh, sink):
    """FedBuff-style asynchronous federation (algorithms/async_fl.py):
    no barrier — the server aggregates every --async_goal uploads with
    (1+staleness)^-alpha discounts and immediately re-tasks the consumed
    silos.  --comm_round counts server VERSIONS (aggregations).  Local
    hub deployment (the async protocol is transport-agnostic; the gRPC
    path would reuse the same actors)."""
    from fedml_tpu.algorithms.async_fl import (AsyncFedServerActor,
                                               delta_encoder)
    from fedml_tpu.algorithms.cross_silo import FedAvgClientActor
    from fedml_tpu.comm.local import LocalHub

    if mesh is not None:
        raise ValueError("--mesh_clients does not apply to the async "
                         "actor mode (each silo trains single-chip)")
    if cfg.wire_compression != "none" or cfg.error_feedback:
        raise ValueError(
            "--wire_compression/--error_feedback are not wired into "
            "--algo async_fl yet (the async server consumes raw deltas); "
            "running on would silently send uncompressed uploads")
    if cfg.silo_backend != "local":
        raise ValueError(
            "--algo async_fl currently deploys over the local hub only; "
            f"--silo_backend {cfg.silo_backend!r} would silently be "
            "ignored (the actors are transport-agnostic — the gRPC "
            "wiring mirrors cross_silo's when needed)")
    perf = _make_perf(cfg)
    # async has no serve frontend, but `--slo` must still evaluate: the
    # rolling objectives ride on_version below (gauges + breach counters)
    slo = _make_slo(cfg)
    # async deltas ARE updates: health norms/alignment read them raw
    health = _make_health(cfg, kind="delta")
    wl = _make_workload(cfg, data)
    init, make_train_fn = _silo_training_setup(cfg, data, wl, perf=perf)
    n_silos = min(cfg.client_num_per_round, data.client_num)
    goal = cfg.async_goal or max(1, n_silos // 2)
    make_train_fn = _adversary_train_fns(cfg, data, make_train_fn, n_silos)
    if cfg.edge_aggregators > 0:
        raise ValueError("--edge_aggregators is a cross_silo (sync barrier) "
                         "topology; the async server consumes per-silo "
                         "deltas directly")
    # async uploads are deltas — the admission screen fingerprints them
    # against the params template (same treedef/shapes/dtypes) and
    # screens the raw delta norm
    admission, defended, stream = _robust_setup(
        cfg, init, kind="delta", sentry=perf.sentry if perf else None,
        device=perf.device if perf else None)

    history = []

    def on_version(version, params):
        if slo is not None:
            slo.evaluate()  # rolling: gauges update, breaches count
        if (version % cfg.frequency_of_the_test == 0
                or version == cfg.comm_round):
            stats = _eval_global(wl, params, data)
            stats["version"] = version
            history.append(stats)
            sink.log(stats, step=version)

    # the staleness-aware server-optimizer seam (ISSUE 18): the
    # discounted buffer mean becomes the pseudo-gradient
    server_opt = _make_server_opt(
        cfg, init, sentry=perf.sentry if perf else None,
        device=perf.device if perf else None)

    # version-checkpoint extra state: the trust ledger survives crashes
    # (the sync runner's composition, mirrored)
    trust_extra = None
    if admission is not None:
        trust_extra = (lambda: admission.trust.state_dict(n_silos),
                       admission.trust.load_state_dict)
    srv_opt_extra = None
    if server_opt is not None:
        srv_opt_extra = (server_opt.state_dict, server_opt.load_state_dict)
    # the degrade tracker's async role (ISSUE 19): the observed
    # task→upload latency adapts the re-task watchdog's quiet threshold
    degrade = _degrade_setup(cfg, n_silos, mode="async")
    degrade_extra = None
    if degrade is not None:
        degrade_extra = (degrade.state_dict, degrade.load_state_dict)
    extra_state = _compose_extra_state([("trust", trust_extra),
                                        ("srv_opt", srv_opt_extra),
                                        ("degrade", degrade_extra)])

    # zero-copy pipelined ingest (comm/ingest.py, ISSUE 20): one fold
    # worker consumes the buffer-fold queue in arrival order.  No decode
    # arena here — async uploads are DELTAS screened against the delta
    # template, and the staleness-discounted buffer path keeps the host
    # decode (the arena rides the sync paths); what pipelining buys is
    # decode+screen+fold off the transport thread.
    ingest = None
    if cfg.ingest_pipeline:
        from fedml_tpu.comm.ingest import IngestPipeline
        ingest = IngestPipeline(
            num_shards=1, depth=cfg.ingest_queue_depth,
            fault_feed=((lambda reason, detail:
                         degrade.note_dead_letter(reason))
                        if degrade is not None else None))

    hub = LocalHub(codec_roundtrip=True)  # exercise the wire codec
    server = AsyncFedServerActor(
        hub.transport(0), init, data.client_num, n_silos,
        num_versions=cfg.comm_round, aggregation_goal=goal,
        staleness_exponent=cfg.staleness_exponent,
        server_lr=cfg.async_server_lr, on_version=on_version,
        seed=cfg.seed, checkpointer=_make_checkpointer(cfg),
        retask_timeout_s=cfg.retask_timeout_s or None,
        admission=admission, defended_aggregate=defended,
        stream_agg=stream, perf=perf, health=health,
        extra_state=extra_state, journal=_make_journal(cfg),
        server_opt=server_opt, degrade=degrade, ingest=ingest)
    server.register_handlers()
    silos = [FedAvgClientActor(i, hub.transport(i), make_train_fn(i),
                               encode_upload=delta_encoder)
             for i in range(1, n_silos + 1)]
    for s in silos:
        s.register_handlers()
    try:
        server.start()
        hub.pump(idle_hook=(ingest.drain if ingest is not None else None))
    finally:
        if perf is not None:
            perf.close()  # join the RSS sampler thread
    out = dict(history[-1]) if history else {}
    if server.staleness_seen:
        out["mean_staleness"] = float(np.mean(server.staleness_seen))
    return out


@runner("cross_silo")
def run_cross_silo(cfg, data, mesh, sink):
    """Distributed FedAvg over the host-edge actor/transport layer — the
    reference's ``mpirun -np N+1 main_fedavg.py`` deployment
    (run_fedavg_distributed_pytorch.sh:17-21).

    ``--silo_backend local`` runs server + N silo actors in-process over the
    deterministic hub (the reference's localhost-MPI CI analog);
    ``--silo_backend grpc`` runs THIS process as ``--node_id`` k (0=server,
    1..N=silos) with peers from ``--ip_config`` (the reference's
    grpc_ipconfig.csv format, ip_config_utils.py:4-14) at
    ``--base_port``+rank.  Each silo trains its sampled client's shard with
    a jit'd local-SGD program; only aggregation rides messages.
    """
    import jax
    from fedml_tpu.algorithms.cross_silo import (FedAvgClientActor,
                                                 FedAvgServerActor)

    if mesh is not None:
        raise ValueError("--mesh_clients does not apply to the cross-silo "
                         "actor mode (each silo trains single-chip); drop "
                         "the flag or use --algo fedavg for on-pod sharding")

    perf = _make_perf(cfg)
    # built once per run, evaluated EVERY round below — not only behind
    # the serve frontend, so `--slo` without --serve_port still exports
    # the fedml_slo_* gauges and ticks breach counters instead of
    # silently never evaluating the configured objectives
    slo = _make_slo(cfg)
    # the privacy↔observability trade, stated in the ledger: under flat
    # (--secagg pairwise) masking the root sees only ciphertext, so the
    # payload-derived health stats are SUPPRESSED BY NAME; under grouped
    # masking the root receives plaintext edge MEANS and its block-level
    # stats keep working (the edges' own accumulators are the suppressed
    # ones)
    health = _make_health(
        cfg, kind="params",
        suppress_payload=("secagg_pairwise_masking"
                          if cfg.secagg == "pairwise" else None))
    wl = (_pp_workload(cfg, data) if cfg.mesh_stages > 0
          else _make_workload(cfg, data))
    init, make_train_fn = _silo_training_setup(cfg, data, wl, perf=perf)
    n_silos = min(cfg.client_num_per_round, data.client_num)
    timeout = cfg.round_timeout_s or None
    make_train_fn = _adversary_train_fns(cfg, data, make_train_fn, n_silos)
    shard_spine = None
    if cfg.model_shards > 0:
        # sharded global-model spine (fedml_tpu/shard_spine): the
        # spine's ShardAdmission + ShardedStreamingAggregator replace
        # the whole-model screen and fold wholesale — per-shard wire
        # slices, per-shard fold state, per-shard defended finalize
        from fedml_tpu.robust import TrustTracker
        from fedml_tpu.shard_spine import build_shard_spine
        admission = defended = None
        shard_spine = build_shard_spine(
            init, num_shards=cfg.model_shards,
            norm_clip=cfg.norm_clip, noise_std=cfg.agg_noise_std,
            seed=cfg.seed, fused=cfg.fused_finalize,
            max_num_samples=cfg.max_num_samples,
            norm_k=cfg.norm_screen_k,
            norm_window=cfg.norm_screen_window,
            norm_min_history=cfg.norm_screen_min_history,
            trust=TrustTracker(
                strikes_to_quarantine=cfg.strikes_to_quarantine,
                quarantine_rounds=cfg.quarantine_rounds,
                probation_rounds=cfg.probation_rounds),
            sentry=perf.sentry if perf else None,
            device=perf.device if perf else None)
        stream = shard_spine.agg
    else:
        admission, defended, stream = _robust_setup(
            cfg, init, kind="params",
            sentry=perf.sentry if perf else None,
            device=perf.device if perf else None)

    # live secure aggregation (secure/protocol.py, --secagg): masked
    # uploads over the real transport.  pairwise = the whole cohort is
    # one masking group served by the ROOT's SecAggServer; grouped =
    # masking scoped per edge block (each edge runs the protocol for its
    # silos and ships a plaintext partial mean to an UNMODIFIED root).
    secagg_root = None
    make_edge_secagg = None
    make_silo_secagg = lambda g: None  # noqa: E731
    if cfg.secagg != "off":
        from fedml_tpu.robust import AdmissionPipeline, TrustTracker
        from fedml_tpu.secure.protocol import (SecAggClient, SecAggServer,
                                               masked_template)
        # the weight normalizer every silo and server must agree on:
        # each silo masks n_i/weight_cap <= 1 so the ring budget holds;
        # the normalizer cancels in the recovered sum/weight ratio
        weight_cap = float(np.max(data.train["num_samples"]))
        host_init = jax.tree.map(np.asarray, init)

        def _masked_admission():
            # the PRE-mask-removal screens: structural fingerprint vs
            # the MASKED template + num_samples validation.  Norm
            # screening moves to the post-unmask sum (the protocol's
            # SumNormScreen) — a ciphertext norm is PRG noise.
            return AdmissionPipeline(
                masked_template(host_init), kind="masked",
                max_num_samples=cfg.max_num_samples,
                trust=TrustTracker(
                    strikes_to_quarantine=cfg.strikes_to_quarantine,
                    quarantine_rounds=cfg.quarantine_rounds,
                    probation_rounds=cfg.probation_rounds))

        def _secagg_server(node, noise_std):
            return SecAggServer(
                threshold=cfg.secagg_threshold, clip=cfg.secagg_clip,
                weight_cap=weight_cap, norm_clip=cfg.norm_clip,
                noise_std=noise_std, seed=cfg.seed,
                norm_screen_k=cfg.norm_screen_k,
                norm_screen_window=cfg.norm_screen_window,
                norm_screen_min_history=cfg.norm_screen_min_history,
                node=node)

        make_silo_secagg = lambda g: SecAggClient(g)  # noqa: E731
        if cfg.secagg == "pairwise":
            secagg_root = _secagg_server("server", cfg.agg_noise_std)
            admission = (_masked_admission()
                         if cfg.admission != "off" else None)
            defended = stream = None  # the ring fold replaces both
        else:
            # grouped: edges mask, the root stays plaintext.  The DP
            # noise is injected ONCE, by the root's streaming finalize
            # over the edge means — an edge-side injection would add
            # E+1 draws and make grouped runs systematically noisier
            # than flat ones (the plaintext edge topology's convention,
            # mirrored: edges clip, the root alone adds noise)
            make_edge_secagg = lambda node: _secagg_server(  # noqa: E731
                node, 0.0)

    # multi-level aggregator topology (--edge_aggregators E): E edge
    # actors sit between the silos and the root, each folding its block
    # of silos' uploads at arrival and shipping ONE pre-reduced
    # (mean, weight, count) update per round — the root is this same
    # FedAvgServerActor whose "silos" are the edges
    n_edges = cfg.edge_aggregators
    if n_edges > 0:
        if cfg.silo_backend != "local":
            raise ValueError("--edge_aggregators deploys over the local "
                             "hub only for now (the actors are transport-"
                             "agnostic; gRPC wiring mirrors the flat one)")
        if not 1 <= n_edges <= n_silos:
            raise ValueError(f"--edge_aggregators {n_edges} must be in "
                             f"1..{n_silos} (every edge needs a silo)")
        if cfg.wire_compression != "none" or cfg.error_feedback:
            raise ValueError("--wire_compression/--error_feedback are not "
                             "wired through the edge tier (the root would "
                             "try to decompress an edge's raw mean)")
        if cfg.dead_after_s > 0:
            raise ValueError("--dead_after_s: silo heartbeats terminate at "
                             "their edge; the root failure detector would "
                             "declare every edge dead")
        if admission is not None and admission.max_num_samples > 0:
            # the per-UPLOAD sample cap screens silo claims at the edge
            # tier; the root sees pre-reduced edges whose num_samples is
            # the SUM over their block — scale the root's cap by the
            # largest block so an honest edge is never struck as weight
            # inflation (the edge pipelines below keep the per-silo cap)
            admission.max_num_samples *= -(-n_silos // n_edges)

    # optional lossy upload compression (comm/compress.py): silos send the
    # compressed DELTA to the global model; the server reconstructs.  The
    # down-link broadcast stays exact.
    encode = decode = ef_extra = None
    wire_stats = {"bytes": 0}
    if cfg.wire_compression != "none":
        # host-side numpy throughout — compression is a wire-boundary op
        # and must not bounce the model through the accelerator
        from fedml_tpu.comm.compress import (compress_update,
                                             decompress_update, wire_bytes)

        # error feedback (Seide'14 / Karimireddy'19): the part of the delta
        # the compressor dropped is kept silo-side and added to the NEXT
        # round's delta, so small topk fractions stop systematically losing
        # the same small coordinates.  Residual settlement is DEFERRED
        # until the server's accepted-silos ack arrives with the next sync
        # (ErrorFeedback.resolve via on_accepted): a dropped upload
        # (straggler policy) carries its FULL delta forward instead of
        # losing the sent part.  State is per-silo — fine for persistent
        # silo processes, intentionally beyond the reference's
        # stateless-client contract (flag-gated).
        from fedml_tpu.comm.compress import ErrorFeedback
        _ef = ErrorFeedback()
        if cfg.error_feedback and cfg.silo_backend == "local":
            # EF residuals are silo-side cross-round state; fold them into
            # the server's round checkpoint (fixed-shape template, so it
            # doubles as the orbax restore skeleton).  LOCAL backend only:
            # one process holds every silo's EF there.  A gRPC server
            # never sees silo residuals — checkpointing its own (empty)
            # EF would bloat every checkpoint with model-sized zero trees
            # while restoring nothing; distributed silos keep their own
            # state and are expected to stay alive across server crashes.
            _ef_template = jax.tree.map(
                lambda v: np.zeros_like(np.asarray(v)), init)
            _ef_silos = tuple(range(1, n_silos + 1))
            ef_extra = (lambda: _ef.state_dict(_ef_silos, _ef_template),
                        _ef.load_state_dict)

        # bandwidth observability (the obs report's "bytes saved per
        # round"): compressed-vs-raw bytes of every accepted upload, plus
        # the per-upload compression ratio (handles cached here — null
        # no-ops when telemetry is disabled)
        from fedml_tpu.obs import telemetry as _tel
        _reg = _tel.get_registry()
        _c_comp = _reg.counter("fedml_comm_compressed_bytes_total")
        _c_raw = _reg.counter("fedml_comm_raw_bytes_total")
        _h_ratio = _reg.histogram(
            "fedml_comm_compression_ratio_total",
            buckets=(0.01, 0.025, 0.05, 0.1, 0.2, 0.35, 0.5, 0.75, 1.0))

        def encode(new_params, global_params, _silo=None):
            from fedml_tpu.algorithms.async_fl import delta_encoder
            delta = delta_encoder(new_params, global_params)
            if cfg.error_feedback:
                delta = _ef.apply(_silo, delta)
            payload = compress_update(delta, cfg.wire_compression,
                                      cfg.topk_frac)
            if cfg.error_feedback:
                _ef.record(_silo, delta, decompress_update(payload, delta))
            return payload

        _decode_cache = {"ref": None, "host": None}

        def decode(payload, global_params):
            # one host copy of the globals per round, not one per silo
            # (cache keyed by object identity; holding "ref" prevents id
            # reuse of a collected params tree)
            if _decode_cache["ref"] is not global_params:
                _decode_cache["host"] = jax.tree.map(np.asarray,
                                                     global_params)
                _decode_cache["ref"] = global_params
            host_global = _decode_cache["host"]
            compressed = wire_bytes(payload)
            wire_stats["bytes"] += compressed
            delta = decompress_update(payload, host_global)
            raw = wire_bytes(delta)
            _c_comp.inc(compressed)
            _c_raw.inc(raw)
            if raw:
                _h_ratio.observe(compressed / raw)
            return jax.tree.map(np.add, host_global, delta)

    def make_encode(silo_id):
        if encode is None:
            return None
        return lambda new, g: encode(new, g, _silo=silo_id)

    def make_on_accepted(silo_id):
        if encode is None or not cfg.error_feedback:
            return None
        return lambda accepted: _ef.resolve(silo_id, accepted)

    history = []

    def on_round_done(r, params):
        if slo is not None:
            slo.evaluate()  # rolling: gauges update, breaches count
        if r % cfg.frequency_of_the_test == 0 or r == cfg.comm_round - 1:
            stats = _eval_global(wl, params, data)
            stats["round"] = r
            # where the state lives: fewer devices than shards keeps
            # everything on the default device, and the summary says so
            from fedml_tpu.parallel.mesh import placement_of
            where = placement_of(params)
            stats["global_platform"] = where["platform"]
            stats["global_devices"] = where["devices"]
            if shard_spine is not None:
                stats["shard_state_devices"] = len(
                    {shard_spine.agg.shard_device(s)
                     for s in range(shard_spine.num_shards)})
            if cfg.wire_compression != "none":
                # compressed bytes received since the last eval round
                stats["upload_bytes"] = wire_stats["bytes"]
                wire_stats["bytes"] = 0
            history.append(stats)
            sink.log(stats, step=r)

    detector = None
    if cfg.dead_after_s > 0:
        from fedml_tpu.algorithms.cross_silo import FailureDetector
        detector = FailureDetector(
            suspect_after_s=cfg.suspect_after_s or cfg.dead_after_s / 2,
            dead_after_s=cfg.dead_after_s)

    # serve-while-train (fedml_tpu/serve): the server node publishes each
    # round's global into a hot-swap registry behind an HTTP frontend, so
    # the federation serves its own model live.  A gRPC SILO process never
    # serves — only rank 0 holds the global.
    frontend = publish = release = None
    if cfg.serve_port > 0 and (cfg.silo_backend == "local"
                               or cfg.node_id == 0):
        from fedml_tpu.serve import (MicroBatcher, ModelRegistry,
                                     ServeFrontend, ServeWorkerPool)
        predict = jax.jit(lambda p, x: wl.apply(p, x))
        registry = ModelRegistry(predict)
        buckets = tuple(int(b) for b in cfg.serve_buckets.split(","))
        batcher_kw = dict(
            buckets=buckets,
            max_delay_s=cfg.serve_batch_delay_ms / 1e3,
            queue_depth=cfg.serve_queue_depth,
            default_deadline_s=cfg.serve_deadline_ms / 1e3,
            best_effort_headroom=cfg.serve_best_effort_headroom)
        shadow = None
        if cfg.release_gate:
            # the shadow tap rides every worker's batcher (one shared
            # sampler), so the gate replays real admitted traffic
            from fedml_tpu.serve import ReleaseController, ShadowSampler
            shadow = ShadowSampler(every=cfg.release_shadow_every,
                                   slots=cfg.release_shadow_slots)
            batcher_kw["shadow"] = shadow
        # deep health check: /healthz?deep=1 evaluates the rolling SLOs
        # (round p95, shed rate, worst-worker queue fill, torn frames,
        # quarantines) and answers 503 on breach so an LB can rotate out
        # a violating instance.  The same evaluator backs tiered
        # admission (TierGate): best-effort sheds exactly while deep
        # health would answer 503.
        if cfg.serve_workers > 1:
            frontend = ServeWorkerPool(
                registry, port=cfg.serve_port,
                workers=cfg.serve_workers, slo=slo, health=health,
                **batcher_kw).start()
        else:
            batcher = MicroBatcher(registry, slo=slo, **batcher_kw)
            frontend = ServeFrontend(registry, batcher,
                                     port=cfg.serve_port,
                                     slo=slo, health=health).start()
        if cfg.release_gate:
            import os as _os
            release = ReleaseController(
                registry, shadow=shadow, health=health,
                eval_fn=_release_eval_fn(wl, data),
                divergence_budget=cfg.release_divergence_budget,
                eval_tolerance=cfg.release_eval_tolerance,
                cooldown_s=cfg.release_cooldown_s,
                backoff=cfg.release_backoff,
                max_cooldown_s=cfg.release_max_cooldown_s,
                journal_path=_os.path.join(
                    cfg.metrics_dir or cfg.run_dir or ".",
                    "release.jsonl"))
        _sample_x = np.asarray(data.train["x"][0, 0, 0])
        _warmed = []

        def publish(params, version):
            if release is not None:
                # the gated path: canary → shadow/health/eval verdict →
                # promote or rollback.  The cross-silo hook's version IS
                # the producing round, which keys the health signal.
                release.offer(params, version, round_idx=version)
            else:
                registry.publish(params, version)
            if registry.current() is None:
                return  # first offer rolled back: nothing to warm yet
            if not _warmed:
                _warmed.append(True)
                # compile every bucket off the round path: without this
                # the FIRST request per bucket size pays the jit compile
                # inside its own deadline and is shed 429 from an
                # otherwise idle server.  The pool warms every worker's
                # batcher (all share one jit cache through predict).
                import threading as _th
                _warm_target = (frontend.warmup
                                if cfg.serve_workers > 1
                                else batcher.warmup)
                _th.Thread(target=lambda: _warm_target(_sample_x),
                           daemon=True, name="serve-warmup").start()

    # the server-optimizer seam + adaptive controller (ISSUE 18): the
    # optimizer's O(model) state shards along the spine's plan when one
    # exists, and both ride the round checkpoint by name below
    server_opt = _make_server_opt(
        cfg, init,
        plan=shard_spine.plan if shard_spine is not None else None,
        sentry=perf.sentry if perf else None,
        device=perf.device if perf else None)
    controller = _make_controller(
        cfg, cohort=(n_edges if n_edges > 0 else n_silos),
        epochs=cfg.epochs)
    # the sustained-degradation spine (ISSUE 19): per-silo reliability
    # tracking drives the adaptive deadline, the quorum-aware close, and
    # network-vs-payload fault attribution; under the edge topology the
    # root's cohort IS the edge tier, so the tracker sizes to it
    degrade = _degrade_setup(cfg, n_edges if n_edges > 0 else n_silos)

    # round-checkpoint extra state, composed by name: silo-side EF
    # residuals (PR 3) + the admission trust ledger (ISSUE 12 — a
    # resumed server must keep strikes, quarantine sentences, and
    # probation clocks, or every crash releases jailed attackers early)
    trust_extra = None
    if admission is not None:
        n_trust = n_edges if n_edges > 0 else n_silos
        trust_extra = (lambda: admission.trust.state_dict(n_trust),
                       admission.trust.load_state_dict)
    elif shard_spine is not None and shard_spine.admission is not None:
        # the sharded spine's trust ledger is just as durable as the
        # flat one — strikes, quarantine sentences, probation clocks
        # all survive a crash (ISSUE 12's contract, unchanged)
        _sh_trust = shard_spine.admission.trust
        trust_extra = (lambda: _sh_trust.state_dict(n_silos),
                       _sh_trust.load_state_dict)
    shard_extra = None
    if shard_spine is not None:
        # the shard LAYOUT is checkpointed state: a resume re-derives
        # the plan and VERIFIES the fingerprint instead of silently
        # restoring sharded fold state into a different layout
        shard_extra = (shard_spine.checkpoint_state,
                       shard_spine.restore_checkpoint_state)
    srv_opt_extra = adapt_extra = None
    if server_opt is not None:
        # bit-exact optimizer-state roundtrip; a restore under a
        # different --server_opt (or shard plan) refuses loudly
        # (ServerOptMismatchError — the PR 14 mode-mismatch mirror)
        srv_opt_extra = (server_opt.state_dict, server_opt.load_state_dict)
    if controller is not None:
        adapt_extra = (controller.state_dict, controller.load_state_dict)
    degrade_extra = None
    if degrade is not None:
        # the reliability history rides the round checkpoint: a resumed
        # server re-derives the SAME adaptive deadline and quorum
        # verdict the crashed process would have (ISSUE 19 determinism)
        degrade_extra = (degrade.state_dict, degrade.load_state_dict)
    extra_state = _compose_extra_state([("ef", ef_extra),
                                        ("trust", trust_extra),
                                        ("shard", shard_extra),
                                        ("srv_opt", srv_opt_extra),
                                        ("adapt", adapt_extra),
                                        ("degrade", degrade_extra)])
    journal = _make_journal(cfg)

    # zero-copy pipelined ingest (comm/ingest.py, ISSUE 20): the
    # transport thread only checks guards and enqueues; one fold worker
    # per shard runs decode -> screen -> fold in arrival order.  Queue
    # overflow dead-letters through the degrade tracker's fault feed as
    # NETWORK evidence (the resilient-transport convention) — never a
    # trust strike, never silent.
    ingest = None
    if cfg.ingest_pipeline:
        from fedml_tpu.comm.ingest import IngestArena, IngestPipeline
        ingest = IngestPipeline(
            num_shards=(shard_spine.num_shards
                        if shard_spine is not None else 1),
            depth=cfg.ingest_queue_depth,
            fault_feed=((lambda reason, detail:
                         degrade.note_dead_letter(reason))
                        if degrade is not None else None))
        if cfg.secagg == "off":
            # pre-pinned decode arenas, one per shard, templated on the
            # exact slice layout the wire ships: a frame's float payload
            # lands via ONE device_put into the flat arena, and the
            # fused finite+sumsq screen replaces the per-upload host
            # norm pass.  Masked (secagg) uploads keep the host decode —
            # a ciphertext norm is PRG noise — but the ring fold still
            # runs on the worker.
            if shard_spine is not None:
                # each shard's upload lands where its fold state lives
                arenas = [IngestArena(sl, name=f"ingest_s{s}", perf=perf,
                                      device=shard_spine.agg.shard_device(s))
                          for s, sl in enumerate(
                              shard_spine.broadcast_slices(init))]
            else:
                arenas = [IngestArena(init, perf=perf)]
            ingest.attach_arenas(arenas)

    def make_server(transport):
        # under the edge topology the root's cohort IS the edge tier:
        # straggler policy, admission, trust, and both agg modes apply
        # per edge unchanged
        s = FedAvgServerActor(
            transport, init, data.client_num,
            n_edges if n_edges > 0 else n_silos, cfg.comm_round,
            on_round_done=on_round_done,
            straggler_policy=cfg.straggler_policy,
            round_timeout_s=timeout, min_silo_frac=cfg.min_silo_frac,
            decode_upload=decode, failure_detector=detector,
            checkpointer=_make_checkpointer(cfg),
            publish=publish, extra_state=extra_state,
            admission=admission, aggregate_fn=defended,
            stream_agg=stream, perf=perf, health=health,
            secagg=secagg_root, journal=journal,
            shard_wire=shard_spine,
            server_opt=server_opt, controller=controller,
            degrade=degrade, ingest=ingest)
        s.register_handlers()
        return s

    chaos_on = any((cfg.chaos_drop, cfg.chaos_delay, cfg.chaos_dup,
                    cfg.chaos_reorder, cfg.chaos_corrupt))
    if chaos_on and cfg.silo_backend != "local":
        raise ValueError("--chaos_* injection wraps the local hub only; "
                         "for real wires compose ChaosTransport in code")
    try:
        if cfg.silo_backend == "local":
            import threading
            from fedml_tpu.comm.local import LocalHub
            hub = LocalHub(codec_roundtrip=True)  # exercise the wire codec
            wrap = lambda t: t  # noqa: E731
            if chaos_on:
                from fedml_tpu.algorithms.cross_silo import MsgType
                from fedml_tpu.comm.chaos import (ChaosPlan, ChaosTransport,
                                                  LinkChaos)
                if cfg.chaos_drop > 0 and (cfg.straggler_policy == "wait"
                                           or not timeout):
                    raise ValueError(
                        "--chaos_drop with the strict 'wait' barrier (or no "
                        "--round_timeout_s) would wedge the federation on "
                        "the first lost upload; use --straggler_policy drop "
                        "--round_timeout_s T")
                plan = ChaosPlan(
                    seed=cfg.chaos_seed,
                    default=LinkChaos(drop_prob=cfg.chaos_drop,
                                      delay_prob=cfg.chaos_delay,
                                      max_delay_s=cfg.chaos_max_delay_s,
                                      dup_prob=cfg.chaos_dup,
                                      reorder_prob=cfg.chaos_reorder,
                                      corrupt_prob=cfg.chaos_corrupt),
                    # FINISH: shutdown liveness.  ROUND_TIMEOUT: the
                    # straggler timer's SELF-message rides the server's own
                    # chaotic transport on link (0,0) — dropping it disarms
                    # the only re-arm path and wedges the round
                    immune_types=(MsgType.S2C_FINISH, MsgType.ROUND_TIMEOUT))
                wrap = lambda t: ChaosTransport(t, plan)  # noqa: E731
            server = make_server(wrap(hub.transport(0)))
            # hub address plan: root 0; edges 1..E (the root's "silos");
            # flat silos at E+g, where g is the 1-based GLOBAL cohort
            # slot that seeds the silo's rng stream and client assignment
            # — a silo trains identically under any topology
            edges, edge_of = [], {}
            if n_edges > 0:
                from fedml_tpu.algorithms.hierarchical import (
                    EdgeAggregatorActor)
                from fedml_tpu.core.stream_agg import StreamingAggregator
                blocks = np.array_split(np.arange(1, n_silos + 1), n_edges)
                for e, block in enumerate(blocks, start=1):
                    edge_admission = None
                    if make_edge_secagg is not None:
                        # grouped masking: the edge screens CIPHERTEXT
                        # (masked-template fingerprint + num_samples,
                        # pre-mask-removal) with its own trust ledger
                        if cfg.admission != "off":
                            edge_admission = _masked_admission()
                    elif admission is not None:
                        # each edge screens ITS silos with its own
                        # pipeline/trust ledger (PR 4 composes per-upload
                        # at the edge; the root's screen then sees the
                        # edge means)
                        from fedml_tpu.robust import (AdmissionPipeline,
                                                      TrustTracker)
                        edge_admission = AdmissionPipeline(
                            init, kind="params",
                            max_num_samples=cfg.max_num_samples,
                            norm_k=cfg.norm_screen_k,
                            norm_window=cfg.norm_screen_window,
                            norm_min_history=cfg.norm_screen_min_history,
                            trust=TrustTracker(
                                strikes_to_quarantine=(
                                    cfg.strikes_to_quarantine),
                                quarantine_rounds=cfg.quarantine_rounds,
                                probation_rounds=cfg.probation_rounds))
                    edge_health = None
                    if health is not None:
                        # per-edge statistics-only accumulator: the edge
                        # ships its compact rollup inside its per-round
                        # frame; the root's accumulator owns the
                        # gauges, alarms, and the ledger.  Under grouped
                        # masking the edge sees only ciphertext, so its
                        # payload stats are suppressed BY NAME.
                        from fedml_tpu.obs import HealthAccumulator
                        edge_health = HealthAccumulator(
                            kind="params", node=f"edge{e}", alarms=False,
                            suppress_payload=(
                                "secagg_grouped_masking"
                                if make_edge_secagg is not None else None))
                    # edge folds are plain clipped means — the robust
                    # rule and the DP noise run ONCE, at the root, over
                    # the edge means.  Under grouped masking the edge
                    # instead runs the secure protocol for its block
                    # (ring fold + unmask) and ships the plaintext
                    # PARTIAL MEAN in the same one-frame-per-round format.
                    edges.append(EdgeAggregatorActor(
                        e, wrap(hub.transport(e)),
                        {n_edges + int(g): int(g) for g in block},
                        cohort_total=n_silos,
                        client_num_in_total=data.client_num,
                        stream_agg=(None if make_edge_secagg is not None
                                    else StreamingAggregator(
                                        init, method="mean", kind="params",
                                        norm_clip=cfg.norm_clip,
                                        seed=cfg.seed)),
                        admission=edge_admission,
                        health=edge_health,
                        secagg=(make_edge_secagg(f"edge{e}")
                                if make_edge_secagg is not None else None),
                        journal=_make_journal(cfg, subdir=f"edge{e}"),
                        # the edge must flush its partial fold BEFORE
                        # the root's round timer fires, or an on-time
                        # block is discarded with its one straggler —
                        # half the root timeout leaves the flush margin.
                        # A MASKED edge runs up to three timed stages
                        # (agreement / upload / unmask), so its per-stage
                        # margin is a quarter: two stage timeouts still
                        # land inside the root's window
                        timeout_s=((timeout / 4
                                    if make_edge_secagg is not None
                                    else timeout / 2)
                                   if timeout else None)))
                    for g in block:
                        edge_of[int(g)] = e
            silos = [FedAvgClientActor(
                         n_edges + g, wrap(hub.transport(n_edges + g)),
                         make_train_fn(g),
                         encode_upload=make_encode(g),
                         on_accepted=make_on_accepted(g),
                         heartbeat_interval_s=(cfg.heartbeat_s or None)
                         if chaos_on else None,
                         server_id=edge_of.get(g, 0),
                         # masking identity = the TRANSPORT id (the group
                         # lists in sync frames are transport ids)
                         secagg=make_silo_secagg(n_edges + g))
                     for g in range(1, n_silos + 1)]
            if not chaos_on:
                for a in edges + silos:
                    a.register_handlers()
                for e_actor in edges:
                    # mid-round recovery for a journaled edge: a restart
                    # that left an edge's block mid-flight restores the
                    # durable fold and re-syncs only the missing silos
                    # (no-op without a journal or an open round)
                    e_actor.resume()
                server.start()
                # idle_hook: when every inbox is empty the pump drains
                # queued ingest folds; a truthy processed count means the
                # drain may have enqueued broadcasts, so pumping resumes
                hub.pump(idle_hook=(ingest.drain if ingest is not None
                                    else None))
                return history[-1] if history else {}
            # chaos delivers delayed/reordered frames on wall-clock timers,
            # which the synchronous pump cannot wait for — drive each actor
            # on its own thread like a real deployment
            threads = [threading.Thread(target=a.run, daemon=True,
                                        name=f"node-{a.node_id}")
                       for a in edges + silos]
            for th in threads:
                th.start()
            for e_actor in edges:
                e_actor.resume()
            server.start()
            server.transport.run()  # blocks until the final round's FINISH
            for th in threads:
                th.join(timeout=10)
            return history[-1] if history else {}
        if cfg.silo_backend == "grpc":
            from fedml_tpu.comm.grpc_transport import (GrpcTransport,
                                                       load_ip_table)
            table = (load_ip_table(cfg.ip_config) if cfg.ip_config
                     else {i: "127.0.0.1" for i in range(n_silos + 1)})
            transport = GrpcTransport(cfg.node_id, table,
                                      base_port=cfg.base_port,
                                      max_message_mb=cfg.grpc_max_message_mb,
                                      idle_timeout_s=cfg.silo_idle_timeout_s,
                                      workers=cfg.grpc_workers)
            if cfg.silo_retries > 0:
                # production posture: retried, backed-off, dead-lettered
                # sends with channel re-dial between attempts
                # (comm/resilient.py)
                from fedml_tpu.comm.resilient import (ResilientTransport,
                                                      RetryPolicy)
                transport = ResilientTransport(
                    transport, RetryPolicy(max_attempts=cfg.silo_retries),
                    seed=cfg.seed,
                    # the server's dead letters are NETWORK evidence for
                    # the degrade tracker's partition discrimination —
                    # routed by reason, never a trust strike
                    fault_feed=(
                        (lambda reason, msg:
                         degrade.note_dead_letter(reason))
                        if degrade is not None and cfg.node_id == 0
                        else None))
            if cfg.node_id == 0:
                server = make_server(transport)
                server.start()
                transport.run()   # blocks until the final round's FINISH
                return history[-1] if history else {}
            silo = FedAvgClientActor(
                cfg.node_id, transport, make_train_fn(cfg.node_id),
                encode_upload=make_encode(cfg.node_id),
                on_accepted=make_on_accepted(cfg.node_id),
                heartbeat_interval_s=cfg.heartbeat_s or None)
            # run() (not bare transport.run()) so the heartbeat thread
            # starts
            silo.run()
            return {}
        raise ValueError(f"unknown silo_backend {cfg.silo_backend!r}; "
                         f"available: ('local', 'grpc')")
    finally:
        if perf is not None:
            perf.close()  # join the RSS sampler thread
        if frontend is not None:
            # drain-on-shutdown: queued requests still answer, then the
            # listener closes — training's end never drops live traffic
            frontend.stop(drain=True)


@runner("cross_device")
def run_cross_device(cfg, data, mesh, sink):
    """Mega-cohort cross-device federation (algorithms/cross_device.py):
    the seeded sampler picks 1k-100k clients, static device-sized waves
    each train as ONE compiled program (vmap single-chip, shard_map over
    the --mesh_clients ``clients`` axis), and every wave's stacked
    updates fold device-side into the PR 7 streaming spine at wave
    completion — O(model) server memory at any cohort size, with the
    per-wave admission screens and the perf/health/device observatories
    riding the loop."""
    from fedml_tpu.algorithms.cross_device import (CrossDevice,
                                                   CrossDeviceConfig)
    perf = _make_perf(cfg)
    if perf is not None and data.load_ns is not None:
        # --perf is the one switch of the round path's spans: the
        # recorder holds the tracer and writes run_dir/trace.json when
        # it is closed.  The data were loaded before it existed
        perf.tracer.record_span("setup.data", data.load_ns[1] / 1e9,
                                t0_ns=data.load_ns[0])
    slo = _make_slo(cfg)
    # wave summaries are params-like trees: health norms/alignment read
    # them against the round's global exactly like cross-silo uploads
    health = _make_health(cfg, kind="params")
    wl = _make_workload(cfg, data)
    server_opt = controller = None
    if cfg.server_opt != "plain" or cfg.adaptive:
        import jax
        # the optimizer template must BE the run's initial global
        # (fedac's coupled x sequence starts at it): reproduce run()'s
        # exact rng chain — same seed, same split, same init
        _, _init_rng = jax.random.split(jax.random.key(cfg.seed))
        _tmpl = wl.init(_init_rng, jax.tree.map(
            lambda v: v[0, 0],
            {k: data.train[k] for k in ("x", "y", "mask")}))
        server_opt = _make_server_opt(
            cfg, _tmpl, sentry=perf.sentry if perf else None,
            device=perf.device if perf else None)
        # cross_device's cohort lever is LIVE: the sampler draws from
        # the full population, so the ceiling is the population itself
        controller = _make_controller(
            cfg, cohort=cfg.client_num_per_round, epochs=cfg.epochs,
            wave_size=cfg.wave_size, max_cohort=data.client_num)
    # zero-copy pipelined ingest (ISSUE 20): the wave loop's pipelining
    # — the main thread keeps launching waves while the fold worker
    # runs admission/fold/health for completed ones.  submit_wait means
    # overflow cannot happen (backpressure paces wave launches), so no
    # fault feed is wired.
    ingest = None
    if cfg.ingest_pipeline:
        from fedml_tpu.comm.ingest import IngestPipeline
        ingest = IngestPipeline(num_shards=1,
                                depth=cfg.ingest_queue_depth)
    algo = CrossDevice(
        wl, data, CrossDeviceConfig(
            wave_size=cfg.wave_size, local_alg=cfg.local_alg,
            sampler=cfg.sampler, mu=cfg.mu, norm_clip=cfg.norm_clip,
            agg_noise_std=cfg.agg_noise_std, admission=cfg.admission,
            norm_screen_k=cfg.norm_screen_k,
            norm_screen_window=cfg.norm_screen_window,
            norm_screen_min_history=cfg.norm_screen_min_history,
            wave_adversary=cfg.wave_adversary,
            **_fedavg_cfg_kwargs(cfg)),
        mesh=mesh, sink=sink, perf=perf, health=health, slo=slo,
        server_opt=server_opt, controller=controller, ingest=ingest)
    try:
        algo.run(checkpointer=_make_checkpointer(cfg))
    finally:
        if perf is not None:
            perf.close()  # join the RSS sampler thread
    return algo.history[-1] if algo.history else {}


@runner("turboaggregate")
def run_turboaggregate(cfg, data, mesh, sink):
    import jax
    from fedml_tpu.algorithms.turboaggregate import (TurboAggregate,
                                                     TurboAggregateConfig)
    wl = _make_workload(cfg, data)
    clients_per_group = max(2, cfg.client_num_per_round // cfg.group_num)
    algo = TurboAggregate(wl, data, TurboAggregateConfig(
        comm_round=cfg.comm_round, group_num=cfg.group_num,
        clients_per_group=clients_per_group,
        drop_tolerance=cfg.drop_tolerance, epochs=cfg.epochs, lr=cfg.lr,
        client_optimizer=cfg.client_optimizer, seed=cfg.seed,
        secagg_backend=cfg.secagg_backend))
    sample = jax.tree.map(lambda v: jax.numpy.asarray(v[0, 0]),
                          {k: data.train[k] for k in ("x", "y", "mask")})
    params = wl.init(jax.random.key(cfg.seed), sample)
    params = algo.run(params)
    stats = _eval_global(wl, params, data)
    sink.log(stats, step=cfg.comm_round - 1)
    return stats


@runner("fednas")
def run_fednas(cfg, data, mesh, sink):
    from fedml_tpu.algorithms.fednas import FedNAS, FedNASConfig
    from fedml_tpu.models import DARTSSearchNetwork
    _image_sample_shape(cfg, data, "fednas")
    net = DARTSSearchNetwork(
        C=cfg.fednas_channels, layers=cfg.fednas_layers,
        steps=cfg.fednas_steps, multiplier=cfg.fednas_steps,
        num_classes=data.class_num)
    algo = FedNAS(net, FedNASConfig(rounds=cfg.comm_round,
                                    epochs=cfg.epochs, seed=cfg.seed))
    cohort = _first_cohort(data, cfg.client_num_per_round)
    # local validation split = the local train data (the reference splits
    # each client's local set; with hermetic twins the halves are iid anyway)
    out = algo.run(cohort, cohort)
    for h in out["history"]:
        sink.log({"round": h["round"], "search_loss": h["search_loss"],
                  "genotype": str(h["genotype"])}, step=h["round"])
    return {"search_loss": out["history"][-1]["search_loss"],
            "genotype": str(out["history"][-1]["genotype"])}


@runner("fedgkt")
def run_fedgkt(cfg, data, mesh, sink):
    from fedml_tpu.algorithms.fedgkt import FedGKT, FedGKTConfig
    from fedml_tpu.models import GKTClientResNet, GKTServerResNet
    _image_sample_shape(cfg, data, "fedgkt")
    client = GKTClientResNet(num_classes=data.class_num)
    server = GKTServerResNet(num_classes=data.class_num)
    algo = FedGKT(client, server, FedGKTConfig(
        rounds=cfg.comm_round, epochs_client=cfg.epochs,
        temperature=cfg.temperature, seed=cfg.seed))
    cohort = _first_cohort(data, cfg.client_num_per_round)
    out = algo.run(cohort)
    for h in out["history"]:
        sink.log(h, step=h["round"])
    ev = algo.evaluate(out["client_params"], out["server_params"], cohort)
    sink.log(ev, step=cfg.comm_round - 1)
    return ev


@runner("fedgan")
def run_fedgan(cfg, data, mesh, sink):
    import jax.numpy as jnp
    from fedml_tpu.algorithms.fedgan import FedGan, FedGanConfig
    from fedml_tpu.models import Discriminator, Generator
    shape = _image_sample_shape(cfg, data, "fedgan")
    H, W, ch = shape
    # G emits 4 * 2^len(widths) px; centre-crop the data to the largest
    # generator-compatible size <= min(H, W)
    n_ups, size = 1, 8
    while size * 2 <= min(H, W):
        n_ups, size = n_ups + 1, size * 2
    widths = tuple(64 // (2 ** i) for i in range(n_ups))
    G = Generator(out_channels=ch, widths=widths)
    D = Discriminator()
    cohort = _first_cohort(data, cfg.client_num_per_round)
    oy, ox = (H - size) // 2, (W - size) // 2
    cohort = {"x": jnp.asarray(
        cohort["x"][:, :, :, oy:oy + size, ox:ox + size, :]),
        "num_samples": jnp.asarray(cohort["num_samples"])}
    algo = FedGan(G, D, FedGanConfig(rounds=cfg.comm_round,
                                     local_epochs=cfg.epochs, seed=cfg.seed))
    out = algo.run(cohort)
    for h in out["history"]:
        sink.log(h, step=h["round"])
    return out["history"][-1]


@runner("asdgan")
def run_asdgan(cfg, data, mesh, sink):
    import jax
    import jax.numpy as jnp
    from fedml_tpu.algorithms.fedgan import AsDGan, AsDGanConfig
    from fedml_tpu.models import CondGenerator, PatchDiscriminator
    shape = _image_sample_shape(cfg, data, "asdgan")
    ch = shape[2]
    cohort = _first_cohort(data, cfg.client_num_per_round)
    # hermetic paired task: conditioning a = noisy image, private b = clean
    # (a denoising translation — AsDGan's server-G never sees b directly)
    b = jnp.asarray(cohort["x"])
    noise = jax.random.normal(jax.random.key(cfg.seed), b.shape) * 0.3
    algo = AsDGan(CondGenerator(out_channels=ch), PatchDiscriminator(),
                  AsDGanConfig(epochs=cfg.comm_round, seed=cfg.seed,
                               lambda_l1=cfg.lambda_l1,
                               lambda_perceptual=cfg.lambda_perceptual))
    out = algo.run({"a": b + noise, "b": b,
                    "num_samples": jnp.asarray(cohort["num_samples"])})
    for h in out["history"]:
        sink.log(h, step=h.get("epoch", 0))
    return out["history"][-1]


@runner("fedseg")
def run_fedseg(cfg, data, mesh, sink):
    import jax.numpy as jnp
    from fedml_tpu.algorithms.fedavg import FedAvg, FedAvgConfig
    from fedml_tpu.algorithms.fedseg import SegmentationWorkload
    from fedml_tpu.data.stacking import FederatedData
    from fedml_tpu.models import UNet
    shape = _image_sample_shape(cfg, data, "fedseg")
    # hermetic dense-label task: per-pixel class = brightness threshold of
    # the image itself (2 classes) — learnable, and exercises the full
    # ignore-index CE + confusion-matrix mIoU path
    def to_seg(stacked):
        if stacked is None:
            return None
        y = (np.asarray(stacked["x"]).mean(axis=-1) > 0).astype(np.int32)
        return {**stacked, "y": y}
    seg_data = FederatedData(
        client_num=data.client_num, class_num=2,
        train=to_seg(data.train), test=to_seg(data.test))
    wl = SegmentationWorkload(UNet(num_classes=2, widths=(8, 16)),
                              num_classes=2)
    algo = FedAvg(wl, seg_data, FedAvgConfig(**_fedavg_cfg_kwargs(cfg)),
                  mesh=mesh, sink=sink)
    algo.run(checkpointer=_make_checkpointer(cfg))
    return algo.history[-1] if algo.history else {}


@runner("split_nn")
def run_split_nn(cfg, data, mesh, sink):
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    from fedml_tpu.algorithms.split_nn import (SplitModel, SplitNNConfig,
                                               SplitNNSimulator)
    sample_shape = sample_shape_of(data)

    class Body(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            x = x.reshape((x.shape[0], -1))
            return nn.relu(nn.Dense(64)(x))

    class Head(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            return nn.Dense(data.class_num)(x)

    split = SplitModel(Body(), Head())
    sim = SplitNNSimulator(split, SplitNNConfig(
        epochs_per_client=cfg.epochs, rounds=cfg.comm_round,
        client_lr=cfg.lr, server_lr=cfg.lr))
    n = min(cfg.client_num_per_round, data.client_num)
    client_data = [
        {k: jnp.asarray(data.train[k][c]) for k in ("x", "y", "mask")}
        for c in range(n)]
    out = sim.run(client_data, jax.random.key(cfg.seed))
    for h in out["history"]:
        sink.log(h, step=h.get("sweep", 0))
    return out["history"][-1] if out["history"] else {}


@runner("vfl")
def run_vfl(cfg, data, mesh, sink):
    import jax
    from fedml_tpu.algorithms.vertical_fl import VerticalFL, VFLConfig
    from fedml_tpu.data.tabular import synthetic_vfl_parties
    from fedml_tpu.models import VFLPartyNet
    # vertical FL partitions FEATURES, not clients: two-party synthetic
    # standing in for lending_club / NUS-WIDE (tabular.py loaders take a
    # real csv via --data_dir in library use)
    train, test = synthetic_vfl_parties(
        n_samples=max(cfg.batch_size * 4, 256), seed=cfg.seed)
    feature_dims = [x.shape[1] for x in train[:-1]]
    models = [VFLPartyNet(hidden_dim=16) for _ in feature_dims]
    vfl = VerticalFL(models, VFLConfig(
        rounds=cfg.comm_round, batch_size=cfg.batch_size, lr=cfg.lr,
        frequency_of_the_test=cfg.frequency_of_the_test))
    out = vfl.fit(train, test, jax.random.key(cfg.seed))
    for h in out["history"]:
        sink.log(h, step=h.get("round"))
    return out["history"][-1] if out["history"] else {}


# --------------------------------------------------------------------------
# entry point
# --------------------------------------------------------------------------

def setup_platform(cfg: ExperimentConfig) -> None:
    """Apply ``--platform`` / ``--host_device_count`` BEFORE any backend
    initializes (without the flags, JAX's own env vars decide)."""
    import os
    if cfg.host_device_count > 0:
        flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        flags.append("--xla_force_host_platform_device_count="
                     f"{cfg.host_device_count}")
        os.environ["XLA_FLAGS"] = " ".join(flags)
    if cfg.platform:
        import jax
        jax.config.update("jax_platforms", cfg.platform)


def open_backend() -> None:
    """Initialize the backend NOW: a process that cannot get a chip fails
    here, at start-up and in seconds (libtpu's "already in use by process
    ..."), with the supported layouts in the message."""
    import jax
    try:
        jax.devices()
    except RuntimeError as e:
        raise RuntimeError(
            f"{e}\nfedml_tpu: a chip belongs to one process at a time — "
            f"run one process per chip (TPU_VISIBLE_CHIPS=k), or keep the "
            f"processes that only aggregate (the gRPC server, --node_id 0) "
            f"on the CPU with --platform cpu") from e


def compile_cache_dir() -> Optional[str]:
    """``<checkout>/.jax_cache`` (git-ignored), derived from this
    package's location and nothing that changes between runs — a cache in
    a directory that moves never hits.  None when
    ``JAX_COMPILATION_CACHE_DIR`` is set: JAX reads that itself, and the
    code sets no other."""
    import os
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg), ".jax_cache")


def enable_compile_cache() -> None:
    """Persistent XLA compilation cache — THE one place every entry point
    (`main`, chip_smoke.py) configures it.  Call AFTER platform
    selection (it initializes the backend).  A CPU run is left alone:
    compiles are cheap there and tests churn shapes.  On an accelerator
    the directory is the environment's or `compile_cache_dir`, and every
    program is kept whatever its compile time: chip_smoke.py's cold run
    spent 116 of its 245 compile seconds in programs that took under 5 s
    each (93 s under 1 s), which the former 5 s floor — or JAX's 1 s
    default — would pay again on every start (PERF.md, PR 21)."""
    import jax
    if jax.default_backend() == "cpu":
        return
    cache = compile_cache_dir()
    if cache:
        jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def main(argv=None) -> Dict[str, Any]:
    cfg = config_from_argv(argv) if not isinstance(argv, ExperimentConfig) \
        else argv
    logging.basicConfig(
        level=logging.INFO,
        format=f"[proc {cfg.process_id}] %(asctime)s %(name)s: %(message)s")
    # --cross_device is shorthand for --algo cross_device (the compiled
    # wave engine); pairing it with any OTHER algorithm would silently
    # pick one of the two — fail instead
    if cfg.cross_device and cfg.algo not in ("fedavg", "cross_device"):
        raise ValueError(
            f"--cross_device IS an algorithm selection (the compiled "
            f"wave engine, --algo cross_device); it cannot combine with "
            f"--algo {cfg.algo}")
    if cfg.cross_device or cfg.algo == "cross_device":
        cfg = dataclasses.replace(cfg, algo="cross_device",
                                  cross_device=True)
    setup_platform(cfg)

    from fedml_tpu.parallel.mesh import init_distributed, make_mesh
    init_distributed(cfg.coordinator_address, cfg.num_processes,
                     cfg.process_id)
    open_backend()
    enable_compile_cache()
    mesh = None
    if cfg.mesh_groups > 0:
        if cfg.algo != "hierarchical":
            raise ValueError(
                "--mesh_groups builds the two-level [groups, clients] mesh, "
                "which only the hierarchical algorithm consumes; other "
                f"algorithms (got --algo {cfg.algo}) would silently "
                "duplicate work across the groups axis. Use --mesh_clients.")
        import jax
        from fedml_tpu.parallel.mesh import make_two_level_mesh
        n_dev = len(jax.devices())
        n_cli = cfg.mesh_clients or n_dev // cfg.mesh_groups
        if n_cli < 1:
            raise ValueError(
                f"--mesh_groups {cfg.mesh_groups} exceeds the "
                f"{n_dev} available devices")
        mesh = make_two_level_mesh(
            group_axis=cfg.mesh_groups, client_axis=n_cli,
            devices=jax.devices()[:cfg.mesh_groups * n_cli])
    elif cfg.mesh_clients > 0:
        import jax
        mesh = make_mesh(client_axis=cfg.mesh_clients,
                         devices=jax.devices()[:cfg.mesh_clients])

    if cfg.algo not in RUNNERS:
        raise KeyError(f"unknown --algo {cfg.algo!r}; have {sorted(RUNNERS)}")
    # mixed precision is wired through _make_workload; runners that build
    # their own models (NAS/GKT/GAN/seg/split/vfl/online) would silently
    # train f32 — fail loudly instead of faking a bf16 benchmark
    _DTYPE_RUNNERS = {"fedavg", "fedprox", "fedopt", "fednova",
                      "fedavg_robust", "hierarchical", "centralized",
                      "decentralized", "turboaggregate", "ditto",
                      "feddyn", "dp_fedavg", "fedac", "cross_device"}
    if cfg.compute_dtype and cfg.algo not in _DTYPE_RUNNERS:
        raise ValueError(
            f"--compute_dtype is not wired into --algo {cfg.algo}; "
            f"supported: {sorted(_DTYPE_RUNNERS)}")
    if cfg.mesh_stages > 0 and cfg.algo != "cross_silo":
        raise ValueError(
            "--mesh_stages is silo-local pipeline parallelism: each silo "
            "runs its own [stages] mesh, so it only applies to --algo "
            "cross_silo (the vmapped cohort engine cannot nest a shard_map "
            f"pipeline per client); got --algo {cfg.algo}")
    if cfg.pp_microbatches and not cfg.mesh_stages:
        raise ValueError("--pp_microbatches tunes the GPipe schedule and "
                         "needs --mesh_stages; alone it would be silently "
                         "ignored")
    if cfg.mesh_stages > 0 and (cfg.attn_block_size or cfg.attn_flash):
        raise ValueError(
            "--attn_block_size/--attn_flash are TransformerLM attention "
            "backends; the pipelined PipelineLM (--mesh_stages) runs dense "
            "block attention and would silently drop them")
    # same fail-loudly convention: a silently-ignored EF flag would label
    # uncompressed numbers as EF results
    if cfg.wire_compression != "none" and cfg.algo != "cross_silo":
        raise ValueError("--wire_compression only applies to "
                         "--algo cross_silo (the host-edge wire)")
    if any((cfg.chaos_drop, cfg.chaos_delay, cfg.chaos_dup,
            cfg.chaos_reorder, cfg.chaos_corrupt)) \
            and cfg.algo != "cross_silo":
        raise ValueError(
            f"--chaos_* injection is wired into --algo cross_silo only; "
            f"--algo {cfg.algo} would silently run a CLEAN network and "
            f"label the results as chaos results")
    # the live-path payload defense + adversary harness (fedml_tpu/robust)
    # rides the distributed actor modes only; on the cohort-simulation
    # algorithms the flags would silently do nothing and label plain runs
    # as defended/attacked ones.  cross_device composes the SUBSET that
    # makes sense inside compiled waves (--norm_clip/--agg_noise_std on
    # the streamed mean + the built-in per-wave screens) — its own gates
    # below refuse the rest with reasons.
    if cfg.algo not in ("cross_silo", "async_fl", "cross_device") and (
            cfg.robust_agg != "mean" or cfg.norm_clip or cfg.agg_noise_std
            or cfg.adversary or cfg.admission == "on"):
        raise ValueError(
            f"--robust_agg/--norm_clip/--agg_noise_std/--adversary/"
            f"--admission on are the live distributed defense "
            f"(fedml_tpu/robust) and apply to --algo cross_silo/async_fl "
            f"only; got --algo {cfg.algo}.  For the single-chip cohort "
            f"simulation use --algo fedavg_robust --defense ... instead.")
    # cross-device wave engine: every unsupported combo fails AT CONFIG
    # TIME with its reason — a silently-ignored flag would mislabel the
    # run (the secagg gate convention)
    if cfg.algo == "cross_device":
        if cfg.secagg != "off":
            raise ValueError(
                "--cross_device trains sampled clients INSIDE compiled "
                "wave programs — there are no per-client uploads on a "
                "wire to mask, so --secagg would label an unmasked "
                "simulation as private; secure aggregation lives on the "
                "actor path (--algo cross_silo --secagg ...)")
        if cfg.edge_aggregators > 0:
            raise ValueError(
                "--edge_aggregators is a transport-actor topology; the "
                "cross-device engine's hierarchy is the wave tree itself "
                "(waves pre-reduce on device), so the flag would "
                "silently run a flat engine labeled as an edge tree")
        if cfg.silo_backend != "local":
            raise ValueError(
                f"--cross_device is the compiled single-process engine; "
                f"--silo_backend {cfg.silo_backend!r} (transport actors) "
                f"would be silently ignored — scale out with "
                f"--mesh_clients (+ --coordinator_address on pods) "
                f"instead")
        if cfg.robust_agg != "mean":
            raise ValueError(
                f"--robust_agg {cfg.robust_agg}: order-statistic rules "
                f"need the per-client population, but cross-device waves "
                f"pre-reduce to a weighted partial mean on device.  The "
                f"defenses that compose are the per-wave structure/"
                f"finite/norm screens + --norm_clip/--agg_noise_std on "
                f"the streamed mean; for per-upload robust rules use "
                f"--algo cross_silo --agg_mode stream "
                f"--stream_reservoir K")
        if cfg.adversary:
            raise ValueError(
                "--adversary wraps per-silo train fns over the real "
                "message path (robust/adversary.py); the compiled wave "
                "has no per-silo message seam — run attack scenarios on "
                "--algo cross_silo, or poison wave SUMMARIES here with "
                "--wave_adversary round:wave:kind[:param]")
        if cfg.rounds_per_dispatch > 1:
            raise ValueError(
                "--rounds_per_dispatch is the fedavg HBM-resident "
                "multi-round scan; the cross-device wave loop folds per "
                "wave on the host each round and would silently ignore "
                "it")
    if cfg.error_feedback and cfg.wire_compression == "none":
        raise ValueError("--error_feedback requires --wire_compression "
                         "topk or int8")
    # zero-copy pipelined ingest (comm/ingest.py, ISSUE 20): the
    # bit-parity contract is proven per combination — every combination
    # WITHOUT a parity pin refuses at config time with its reason
    # instead of silently falling back to the inline path
    if cfg.ingest_queue_depth < 1:
        raise ValueError(f"--ingest_queue_depth must be >= 1, got "
                         f"{cfg.ingest_queue_depth}")
    if cfg.ingest_pipeline:
        if cfg.algo not in ("cross_silo", "async_fl", "cross_device"):
            raise ValueError(
                f"--ingest_pipeline pipelines the SERVER receive path "
                f"(cross_silo / async_fl) and the cross_device wave "
                f"loop; --algo {cfg.algo} has no ingest hot path and "
                f"would silently run inline")
        if cfg.wire_compression != "none":
            raise ValueError(
                "--ingest_pipeline x --wire_compression is unproven: "
                "the decompress + error-feedback settlement runs on the "
                "transport thread today, and no bit-parity pin covers "
                "decode-on-worker — drop one flag")
        if cfg.silo_backend != "local" and cfg.algo != "cross_device":
            raise ValueError(
                f"--ingest_pipeline x --silo_backend "
                f"{cfg.silo_backend!r} is unproven: the parity and "
                f"journal-recovery pins drive the local hub; the grpc "
                f"receive path needs its own soak before the pipeline "
                f"rides it")
        if cfg.edge_aggregators > 0:
            raise ValueError(
                "--ingest_pipeline x --edge_aggregators is unproven: "
                "edges fold on their own actors and no pin covers a "
                "pipelined edge tier — drop one flag")
        if any((cfg.chaos_drop, cfg.chaos_delay, cfg.chaos_dup,
                cfg.chaos_reorder, cfg.chaos_corrupt)):
            raise ValueError(
                "--ingest_pipeline x --chaos_* is unproven: chaos "
                "switches the hub to the threaded drive and no parity "
                "pin covers wall-clock chaos timers racing the fold "
                "workers — drop one flag")
        if cfg.algo == "cross_silo" and cfg.agg_mode != "stream" \
                and cfg.secagg == "off":
            raise ValueError(
                "--ingest_pipeline pipelines the STREAMING fold "
                "(decode -> screen -> fold at arrival); --agg_mode "
                "stack banks uploads instead of folding them, so "
                "there is nothing to hide behind the network — use "
                "--agg_mode stream")
    # secure aggregation (secure/protocol.py): every incompatible combo
    # fails AT CONFIG TIME — a silently-ignored privacy flag would label
    # plaintext traffic as masked, the worst possible mislabel
    if cfg.secagg not in ("off", "pairwise", "grouped"):
        raise ValueError(f"--secagg must be off|pairwise|grouped, "
                         f"got {cfg.secagg!r}")
    if cfg.secagg != "off":
        if cfg.algo != "cross_silo":
            raise ValueError(
                f"--secagg is the sync-barrier secure-aggregation protocol "
                f"and applies to --algo cross_silo only; --algo {cfg.algo} "
                f"(including async_fl, whose per-upload staleness discounts "
                f"need plaintext individual deltas) would silently train "
                f"unmasked and label the run as private")
        if cfg.wire_compression != "none" or cfg.error_feedback:
            raise ValueError(
                "--secagg and --wire_compression/--error_feedback are "
                "mutually exclusive: a compressed/EF payload cannot ride "
                "the uint32 masking ring (masks must cancel word-for-word)")
        if cfg.robust_agg != "mean":
            raise ValueError(
                f"--secagg hides individual uploads by construction, so "
                f"order-statistic rules (--robust_agg {cfg.robust_agg}) "
                f"have no population to rank; the defenses that compose "
                f"are the pre-mask structure/num_samples screens and the "
                f"post-unmask sum screen + --norm_clip/--agg_noise_std "
                f"on the sum")
        if cfg.agg_mode != "stream":
            raise ValueError(
                "--secagg folds masked uploads in the uint32 ring at "
                "arrival — there is no stack path; pass --agg_mode stream")
        if cfg.silo_backend != "local":
            raise ValueError("--secagg deploys over the local hub only "
                             "for now (the actors are transport-agnostic; "
                             "gRPC wiring mirrors the flat one)")
        if cfg.secagg == "grouped" and cfg.edge_aggregators < 1:
            raise ValueError(
                "--secagg grouped scopes masking per edge block and needs "
                "--edge_aggregators E >= 1; for a single cohort-wide "
                "masking group use --secagg pairwise")
        if cfg.secagg == "pairwise" and cfg.edge_aggregators > 0:
            raise ValueError(
                "--secagg pairwise masks across the WHOLE cohort, which an "
                "edge cannot partially unmask (cross-block pair masks only "
                "cancel in the root's full sum); use --secagg grouped with "
                "--edge_aggregators")
        if cfg.secagg == "grouped" \
                and cfg.client_num_per_round < 2 * cfg.edge_aggregators:
            raise ValueError(
                f"--secagg grouped needs every edge block to hold >= 2 "
                f"silos (a 1-silo 'masked sum' IS that silo's update): "
                f"{cfg.client_num_per_round} silos over "
                f"{cfg.edge_aggregators} edges leaves a short block")
        if cfg.secagg == "pairwise" and cfg.client_num_per_round < 2:
            raise ValueError("--secagg pairwise needs >= 2 silos per round")
        if cfg.secagg_threshold == 1:
            raise ValueError(
                "--secagg_threshold 1 voids the privacy guarantee: one "
                "share reconstructs every seed; the minimum is 2 (0 = "
                "majority default)")
        # the threshold is a PER-GROUP share count: a t larger than the
        # masking group could never reconstruct, and silently clamping
        # it would rewrite the dropout-tolerance contract the flag
        # documents — fail here, where the group sizes are knowable
        group_min = (cfg.client_num_per_round if cfg.secagg == "pairwise"
                     else cfg.client_num_per_round // cfg.edge_aggregators)
        if cfg.secagg_threshold > group_min:
            raise ValueError(
                f"--secagg_threshold {cfg.secagg_threshold} exceeds the "
                f"smallest masking group ({group_min} silos"
                f"{' per edge block' if cfg.secagg == 'grouped' else ''}): "
                f"reconstruction could never gather that many shares")
    # sharded global-model spine (fedml_tpu/shard_spine): every
    # incompatible combo fails AT CONFIG TIME with its reason — a
    # silently-ignored sharding flag would label a whole-model run as
    # sharded (the secagg gate convention)
    if cfg.model_shards < 0:
        raise ValueError(f"--model_shards must be >= 0, got "
                         f"{cfg.model_shards}")
    if cfg.fused_finalize not in ("auto", "on", "off"):
        raise ValueError(f"--fused_finalize must be auto|on|off, got "
                         f"{cfg.fused_finalize!r}")
    if cfg.fused_finalize != "auto" and cfg.model_shards < 1:
        raise ValueError(
            "--fused_finalize selects the SHARD finalize backend and "
            "needs --model_shards >= 1; alone it would be silently "
            "ignored")
    if cfg.model_shards > 0:
        if cfg.algo != "cross_silo":
            raise ValueError(
                f"--model_shards is the sharded cross-silo spine and "
                f"applies to --algo cross_silo only; --algo {cfg.algo} "
                f"would silently run whole-model and label the run as "
                f"sharded")
        if cfg.agg_mode != "stream":
            raise ValueError(
                "--model_shards shards the STREAMING fold state — pass "
                "--agg_mode stream (the stack path's [cohort, ...] "
                "buffer is whole-model by construction)")
        if cfg.robust_agg != "mean":
            raise ValueError(
                f"--model_shards with --robust_agg {cfg.robust_agg}: "
                f"order-statistic rules need the per-upload population, "
                f"which the sharded fold deliberately never "
                f"materializes; the defenses that compose are the "
                f"per-shard screens + --norm_clip/--agg_noise_std on "
                f"the streamed mean (for robust rules use the "
                f"replicated --agg_mode stream --stream_reservoir K)")
        if cfg.secagg != "off":
            raise ValueError(
                "--model_shards and --secagg are mutually exclusive: a "
                "pairwise-masked uint32 ring word cannot be re-sliced "
                "per shard without breaking mask cancellation")
        if cfg.edge_aggregators > 0:
            raise ValueError(
                "--model_shards and --edge_aggregators are mutually "
                "exclusive for now: an edge folds and ships whole-model "
                "means, which would defeat the per-shard wire (shard "
                "the flat topology, or keep edges replicated)")
        if cfg.wire_compression != "none" or cfg.error_feedback:
            raise ValueError(
                "--model_shards and --wire_compression/--error_feedback "
                "are mutually exclusive: the delta codec reconstructs "
                "against the whole global, not a shard slice")
        if cfg.admission == "off":
            raise ValueError(
                "--model_shards requires the admission screens: the "
                "per-shard structural fingerprint IS the wire protocol "
                "(slices route by screened structure), so --admission "
                "off would leave the sharded fold unprotected against "
                "mis-assembled uploads")
        if cfg.silo_backend != "local":
            raise ValueError(
                "--model_shards deploys over the local hub only for "
                "now (the actors are transport-agnostic; gRPC wiring "
                "mirrors the flat one)")
    # crash consistency (utils/journal.py): the journal snapshots the
    # STREAMING fold state — on a stack-mode (or non-live) run the flag
    # would parse and then silently journal nothing, which is the exact
    # "we thought we were crash-safe" blindness this subsystem ends
    if cfg.journal or cfg.journal_dir:
        if cfg.algo not in ("cross_silo", "async_fl"):
            raise ValueError(
                f"--journal is mid-round crash consistency for the live "
                f"actor modes and applies to --algo cross_silo/async_fl "
                f"only; --algo {cfg.algo} would silently journal nothing "
                f"and label the run as crash-consistent.")
        if cfg.agg_mode != "stream" and cfg.secagg == "off":
            raise ValueError(
                "--journal rides the streaming-fold receive path: pass "
                "--agg_mode stream (the stack path has no incremental "
                "fold state to snapshot).  Secagg rounds journal "
                "abort-only.")
    if cfg.journal_snapshot_every < 1:
        raise ValueError(f"--journal_snapshot_every must be >= 1, got "
                         f"{cfg.journal_snapshot_every}")
    if cfg.serve_port > 0 and cfg.algo != "cross_silo":
        raise ValueError(
            "--serve_port starts the serve-while-train frontend, which is "
            f"wired into --algo cross_silo only; --algo {cfg.algo} would "
            "silently train without serving.")
    if cfg.serve_workers < 1:
        raise ValueError(f"--serve_workers must be >= 1, got "
                         f"{cfg.serve_workers}")
    if cfg.serve_workers > 1 and cfg.serve_port <= 0:
        raise ValueError(
            "--serve_workers scales the HTTP frontend and needs "
            "--serve_port; without one there is no frontend to scale "
            "and the flag would silently do nothing.")
    if not 0.0 < cfg.serve_best_effort_headroom <= 1.0:
        raise ValueError(
            f"--serve_best_effort_headroom must be in (0, 1], got "
            f"{cfg.serve_best_effort_headroom}")
    if cfg.metrics_port > 0 and cfg.prom_port > 0 \
            and cfg.metrics_port != cfg.prom_port:
        raise ValueError(
            f"--metrics_port is an alias for --prom_port; got both, "
            f"disagreeing ({cfg.metrics_port} vs {cfg.prom_port}) — "
            f"pass one, or the same port for both.")
    # release gate (serve/release.py): gates the serve-while-train
    # publish hook, so without a frontend the flag would silently train
    # ungated while the run is labeled canary-protected
    if cfg.release_gate and cfg.serve_port <= 0:
        raise ValueError(
            "--release_gate gates the serve-while-train publish hook "
            "(canary → shadow/health/eval verdict) and needs "
            "--serve_port; without a frontend there is no serving swap "
            "to gate and the flag would silently do nothing.")
    if cfg.release_gate and (cfg.release_shadow_every < 1
                             or cfg.release_shadow_slots < 1):
        raise ValueError(
            f"--release_shadow_every and --release_shadow_slots must be "
            f">= 1, got {cfg.release_shadow_every} and "
            f"{cfg.release_shadow_slots}")
    if cfg.wave_adversary and cfg.algo != "cross_device":
        raise ValueError(
            f"--wave_adversary poisons compiled wave SUMMARIES and "
            f"applies to --algo cross_device only; --algo {cfg.algo} "
            f"would silently train clean while the run is labeled "
            f"poisoned.  Per-silo attacks on the actor path use "
            f"--adversary.")
    # the flight recorder and the SLO evaluator hook the live actors'
    # round lifecycle; on the cohort-simulation algorithms the flags
    # would parse and then never record/evaluate anything — an empty
    # ledger and un-evaluated objectives masquerading as a healthy run
    if cfg.algo not in ("cross_silo", "async_fl", "cross_device") and (
            cfg.perf or cfg.perf_ledger or cfg.perf_strict or cfg.slo
            or cfg.device_obs or cfg.health or cfg.health_ledger):
        raise ValueError(
            f"--perf/--perf_ledger/--perf_strict/--device_obs/--slo/"
            f"--health/--health_ledger instrument the live round "
            f"lifecycle and apply to --algo cross_silo/async_fl/"
            f"cross_device only; --algo {cfg.algo} would silently write "
            f"no ledger and never evaluate the objectives.")
    # server-optimizer spine (fedml_tpu/server_opt, ISSUE 18): every
    # incompatible combo fails AT CONFIG TIME with its reason — the
    # named ServerOptConfigError, so a mislabeled run never trains
    from fedml_tpu.server_opt import SERVER_OPT_NAMES, ServerOptConfigError
    if cfg.server_opt not in SERVER_OPT_NAMES:
        raise ServerOptConfigError(
            f"unknown --server_opt {cfg.server_opt!r}; available: "
            f"{list(SERVER_OPT_NAMES)}")
    if cfg.server_opt != "plain":
        if cfg.algo not in ("cross_silo", "async_fl", "cross_device"):
            raise ServerOptConfigError(
                f"--server_opt {cfg.server_opt} rides the live finalize "
                f"seam and applies to --algo cross_silo/async_fl/"
                f"cross_device only; --algo {cfg.algo} would silently "
                f"run its own server step and label the run "
                f"{cfg.server_opt}.  The standalone forks stay at "
                f"--algo fedopt/fedac.")
        if cfg.robust_agg != "mean":
            raise ServerOptConfigError(
                f"--server_opt {cfg.server_opt} with --robust_agg "
                f"{cfg.robust_agg}: an order-statistic finalize is a "
                f"selection, not a cohort mean — there is no "
                f"pseudo-gradient Δ = global − finalize whose "
                f"expectation the server optimizer's moments assume; "
                f"use --robust_agg mean (with --norm_clip/"
                f"--agg_noise_std for defense)")
        if cfg.secagg != "off":
            raise ServerOptConfigError(
                f"--server_opt {cfg.server_opt} and --secagg are "
                f"mutually exclusive: the masked-sum protocol yields "
                f"the plain mean by construction; there is no seam to "
                f"re-step it without unmasking intermediate state")
        if cfg.local_alg == "fednova" and cfg.algo == "cross_device":
            raise ServerOptConfigError(
                "--server_opt with --local_alg fednova: fednova's "
                "tau_eff step IS a server update; stacking a second "
                "optimizer on top would silently change its normalized "
                "averaging semantics")
    if cfg.adaptive:
        if not (cfg.health or cfg.health_ledger):
            raise ServerOptConfigError(
                "--adaptive steers pacing from the health observatory's "
                "drift alarms and requires --health (or "
                "--health_ledger); without it every decision would be "
                "a vacuous hold and the run would be labeled adaptive")
        if cfg.algo not in ("cross_silo", "cross_device"):
            raise ServerOptConfigError(
                f"--adaptive steers the per-round cohort sampler and "
                f"applies to --algo cross_silo/cross_device only; "
                f"--algo {cfg.algo} has no round cohort to pace")
    if cfg.adapt_min_cohort < 1:
        raise ServerOptConfigError(
            f"--adapt_min_cohort must be >= 1, got "
            f"{cfg.adapt_min_cohort}")
    if cfg.adapt_patience < 1:
        raise ServerOptConfigError(
            f"--adapt_patience must be >= 1, got {cfg.adapt_patience}")
    # decentralized_online consumes a streaming dataset (UCI SUSY/RO or a
    # synthetic stream) that the registry doesn't serve — its runner builds
    # it; loading here would KeyError on --dataset SUSY
    data = (None if cfg.algo == "decentralized_online"
            else load_experiment_data(cfg))
    logger.info("algo=%s model=%s dataset=%s clients=%s (%s data)",
                cfg.algo, cfg.model, cfg.dataset,
                "stream" if data is None else data.client_num,
                "real" if cfg.data_dir else "synthetic-twin")

    # multi-host: only process 0 writes run artifacts / prints the summary
    # (the reference's rank-0-only wandb, main_fedavg.py:288-296); other
    # processes keep an in-memory sink so runner code is rank-agnostic
    import os

    import jax
    is_main = jax.process_index() == 0
    run_dir = cfg.metrics_dir or cfg.run_dir

    # observability opt-ins, enabled BEFORE the runner constructs any
    # transport/actor (instrumented constructors cache metric handles);
    # exports happen in the finally so a crashed run still leaves its
    # telemetry snapshot and whatever spans were recorded
    from fedml_tpu.obs import telemetry as _telemetry, trace as _trace
    registry = prom_server = tracer = None
    scrape_port = cfg.metrics_port or cfg.prom_port  # gate above pins
    # any disagreement, so first-nonzero is an alias pick, not a choice
    if cfg.telemetry or scrape_port > 0:
        registry = _telemetry.enable()
        if scrape_port > 0:
            prom_server = _telemetry.start_http_server(scrape_port,
                                                       registry)
            if prom_server is not None:  # bind failure warned + returned None
                logger.info("telemetry: serving /metrics on :%d",
                            scrape_port)
    if cfg.trace_dir:
        tracer = _trace.enable(node=f"node{cfg.node_id}")

    try:
        with MetricsSink(run_dir if is_main else None,
                         stdout=cfg.log_stdout and is_main,
                         name=cfg.algo) as sink:
            sink.log({"config": dataclasses.asdict(cfg)})
            with profiler_trace(cfg.profile_dir if is_main else None):
                summary = RUNNERS[cfg.algo](cfg, data, mesh, sink)
            sink.log({"final": summary})
    finally:
        # each teardown step independently: a failing export must not
        # skip the remaining saves, leak the /metrics port, leave the
        # process-global tracer/registry enabled for the next main()
        # call, or mask the run's own exception
        if tracer is not None:
            try:
                tracer.export(os.path.join(
                    cfg.trace_dir,
                    f"trace-node{cfg.node_id}-{os.getpid()}.json"))
            except OSError:
                logger.exception("trace export failed")
            _trace.disable()
        if registry is not None:
            if run_dir is not None and is_main:
                try:
                    registry.save(os.path.join(run_dir, "telemetry.json"))
                    with open(os.path.join(run_dir, "telemetry.prom"),
                              "w") as f:
                        f.write(registry.render_prometheus())
                except OSError:
                    logger.exception("telemetry export failed")
            if prom_server is not None:
                prom_server.shutdown()
                prom_server.server_close()  # release the port now
            _telemetry.disable()
    if is_main:
        line = json.dumps({"algo": cfg.algo, "dataset": cfg.dataset,
                           "model": cfg.model,
                           **{k: v for k, v in summary.items()
                              if isinstance(v, (int, float, str))}})
        print(line)
        # sweep-orchestration completion signal (parity:
        # post_complete_message_to_sweep_process writes to the named
        # pipe ./tmp/fedml, fedavg/utils.py:19-27); works with a FIFO
        # or a plain file.  Gated on a non-empty summary so a gRPC silo
        # process (returns {}) can't prematurely unblock the orchestrator
        # or truncate the server's real summary.
        if cfg.completion_signal and summary:
            with open(cfg.completion_signal, "w") as f:
                f.write(line + "\n")
    return summary


if __name__ == "__main__":
    main()
