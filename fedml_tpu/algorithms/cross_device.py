"""Mega-cohort cross-device federation: compiled client waves folded
live into the streaming spine (ROADMAP item 1).

The reference FedML's headline benchmark is cross-device FL — thousands
of sampled lightweight clients per round — but the live path here was
still cross-silo (~8 real actors).  This engine makes one round train
1k-100k *sampled* clients by fusing the pieces that already existed and
had never been wired together:

* the deterministic sampler picks the round's cohort
  (`core/sampling.sample_clients`, reference-bit-exact numpy by default;
  ``--sampler jax`` opts into the on-device variant — the choice is
  recorded in every metrics.jsonl row so curves are never silently
  cross-compared);
* `device_cohort.plan_waves` pads the cohort into static device-sized
  WAVES; each wave trains as ONE compiled program
  (`device_cohort.make_wave_fn`: the client axis vmapped, or run in
  sequence for a conv model (`parallel/cohort.choose_client_axis`), on
  one chip; shard_map over `parallel/mesh.py`'s ``clients`` axis on a
  mesh — FedJAX's vmapped client simulation, arXiv 2108.02117, grafted
  onto the live loop);
* a wave's rows are STAGED ONE WAVE AHEAD: as soon as a wave's program
  is dispatched, one worker thread gathers and hands over the next
  wave's rows (the same round's, or — its ids are a function of the
  round index — the next round's first), so the host gather runs beside
  the chip instead of before it.  The loop takes staged rows only if
  their ids are the ids it now asks for; anything else (a widened
  cohort, a debt-carrying client, a resumed run) gathers inline;
* each wave folds DEVICE-SIDE into the PR 7 `StreamingAggregator` at
  wave completion — never a ``[cohort, ...]`` host stack.  Where nothing
  reads one client's result (plain or fedprox local training in
  sequence, no per-upload clip or noise, no poison seam) the wave
  program carries the slot-order weighted sum itself and the fold takes
  that one summary (`fold_sum`): no tree a client is made, so a GB-size
  tree trains in waves.  Otherwise the stacked results fold
  (`fold_wave`: a sequential slot-order scan, bit-identical to
  per-upload folds and to a single-wave round) and server memory is
  O(model) + one O(wave) device buffer at ANY cohort size;
* per-wave admission screens (structure / finite / norm,
  `device_cohort.WaveAdmission`) on statistics the wave program computes
  beside its summary, so the host walks no tree; the PR 8 health sketch
  and PR 9 compile ledger ride every wave, and perf.jsonl gains a
  ``wave`` phase — drift and re-jits at 100k scale are named, not
  guessed;
* ``--local_alg {sgd,fedprox,scaffold,fednova}`` selects the per-client
  trainer INSIDE the compiled wave ("Can 5th Generation Local Training
  Methods Support Client Sampling?", arXiv 2212.14370): fedprox rides
  the prox-term local trainer; scaffold keeps its control variates as
  host-stacked per-client state (the `algorithms/fedavg.py` convention)
  gathered/scattered per wave; fednova folds normalized pseudo-updates
  and closes the round with the tau_eff server step accumulated across
  waves.

Aggregation is stream-only BY CONSTRUCTION (the whole point is never
holding the cohort); ``--agg_mode`` remains an actor-mode knob.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.algorithms.fedavg import (FedAvg, FedAvgConfig,
                                         gather_client_rows,
                                         scatter_client_rows,
                                         zeros_client_state)
from fedml_tpu.core.global_crc import TreeCrc
from fedml_tpu.core.sampling import sample_clients, sample_clients_jax
from fedml_tpu.core.stream_agg import StreamingAggregator
from fedml_tpu.data.stacking import gather_cohort
from fedml_tpu.device_cohort import (WaveAdmission, admission_stats,
                                     make_scaffold_wave_fn,
                                     make_summed_wave_fn, make_wave_fn,
                                     mean_of_sum, plan_waves)
from fedml_tpu.obs import telemetry, trace
from fedml_tpu.parallel.cohort import (choose_client_axis,
                                       device_memory_bytes, train_cohort,
                                       train_cohort_sum,
                                       wave_outgrows_device)
from fedml_tpu.parallel.mesh import placement_of
from fedml_tpu.trainer.local_sgd import make_local_trainer
from fedml_tpu.trainer.workload import make_client_optimizer

logger = logging.getLogger(__name__)

LOCAL_ALGS = ("sgd", "fedprox", "scaffold", "fednova")
SAMPLERS = ("numpy", "jax")


@dataclasses.dataclass
class CrossDeviceConfig(FedAvgConfig):
    wave_size: int = 0            # 0 = auto (min(cohort, 256), rounded
    #                               up to a mesh-axis multiple)
    local_alg: str = "sgd"        # per-client trainer inside the wave
    sampler: str = "numpy"        # numpy (reference-bit-exact) | jax
    mu: float = 0.1               # fedprox proximal strength
    norm_clip: float = 0.0        # streaming defended mean: clip each
    #                               client update against the round global
    agg_noise_std: float = 0.0    # weak-DP noise at finalize
    admission: str = "auto"       # auto/on: per-wave norm screen armed;
    #                               off: structure/finite only
    norm_screen_k: float = 6.0
    norm_screen_window: int = 64
    norm_screen_min_history: int = 8
    wave_adversary: str = ""      # seeded poisoned WAVE SUMMARIES,
    #                               injected pre-admission (ISSUE 16):
    #                               "round:wave:kind[:param],..." —
    #                               robust/adversary.WAVE_ATTACK_KINDS


class CrossDevice(FedAvg):
    """FedAvg's chassis (init / seeded sampling chain / chunked eval /
    checkpoint-resume) with the round replaced by the wave loop.  The
    optional ``mesh`` shards WAVE TRAINING over its ``clients`` axis;
    eval stays the chunked single-chip sweep (`eval_chunk_clients`
    bounds its memory), so cohort size never needs to divide the mesh —
    only ``wave_size`` does.  ``stage_ahead=False`` (Python only, for
    the parity tests) gathers every wave inline."""

    def __init__(self, workload, data, config: CrossDeviceConfig,
                 mesh=None, sink=None, perf=None, health=None, slo=None,
                 publish=None, server_opt=None, controller=None,
                 degrade=None, ingest=None, stage_ahead: bool = True):
        cfg = config
        if cfg.local_alg not in LOCAL_ALGS:
            raise ValueError(f"--local_alg must be one of {LOCAL_ALGS}, "
                             f"got {cfg.local_alg!r}")
        if cfg.sampler not in SAMPLERS:
            raise ValueError(f"--sampler must be one of {SAMPLERS}, "
                             f"got {cfg.sampler!r}")
        n_dev = mesh.shape["clients"] if mesh is not None else 1
        if cfg.wave_size == 0:
            auto = min(max(cfg.client_num_per_round, 1), 256)
            # a COPY, not an in-place write: a caller reusing one config
            # for two engines (single-chip + mesh) must get each mesh's
            # own auto-derivation, not the first engine's resolved size
            cfg = config = dataclasses.replace(
                cfg, wave_size=-(-auto // n_dev) * n_dev)
        if cfg.wave_size < 1:
            raise ValueError(f"--wave_size must be >= 1, got {cfg.wave_size}")
        if mesh is not None and cfg.wave_size % n_dev:
            raise ValueError(
                f"--wave_size {cfg.wave_size} must be a multiple of the "
                f"mesh clients axis ({n_dev}): waves are static-shape "
                f"shard_map programs")
        if cfg.local_alg in ("scaffold", "fednova"):
            if mesh is not None:
                raise ValueError(
                    f"--local_alg {cfg.local_alg} rides the single-chip "
                    f"vmap wave engine for now (its per-client state / "
                    f"normalized server step need the stateful mesh wrap "
                    f"of parallel/cohort.make_sharded_stateful_round); "
                    f"drop --mesh_clients")
            if cfg.client_axis not in (None, "vmap"):
                raise ValueError(f"client_axis is not wired into the "
                                 f"{cfg.local_alg} wave; leave it None")
        if cfg.local_alg == "scaffold":
            if cfg.client_optimizer != "sgd":
                raise ValueError(
                    "scaffold's local update is plain SGD with "
                    "control-variate correction; --client_optimizer sgd "
                    "only (Karimireddy'20)")
            if getattr(workload, "stateful", False):
                raise ValueError(
                    "scaffold does not support stateful (BatchNorm) "
                    "workloads: control variates over running statistics "
                    "are undefined — use a GroupNorm model")
        # eval/init/checkpoint chassis stays single-chip: the mesh below
        # is the WAVE mesh only (cohort size need not divide it)
        super().__init__(workload, data, config, mesh=None, sink=sink)
        self.wave_mesh = mesh
        self.perf = perf
        self.health = health
        self.slo = slo
        # the train-to-serve seam (ISSUE 16): called with each round's
        # finalized global as ``publish(params, version)`` — version =
        # round_idx + 1 so a pre-published baseline can hold version 0
        self.publish = publish
        # the server-optimizer seam (ISSUE 18): the round's finalize
        # (post local_alg transform — fednova's tau_eff step defines the
        # round's effective mean) becomes the pseudo-gradient the
        # optimizer steps on.  None keeps the pre-seam round exactly.
        if server_opt is not None and cfg.local_alg == "fednova":
            raise ValueError(
                "--server_opt with --local_alg fednova is refused: "
                "fednova's tau_eff step IS a server update; stacking a "
                "second optimizer on top silently changes its normalized "
                "averaging semantics")
        self.server_opt = server_opt
        if controller is not None and health is None:
            raise ValueError(
                "controller (--adaptive) requires the health observatory "
                "(--health): its decisions are a pure function of the "
                "per-round drift-alarm line")
        self.controller = controller
        # degrade: a fedml_tpu.robust.degrade.ReliabilityTracker (ISSUE
        # 19).  The wave engine is synchronous — nothing times out — so
        # only the participation-debt lever is live here: indebted
        # clients (keyed client_id+1 in the tracker) claim cohort seats
        # at the head of the next sample, and per-wave completion times
        # feed the latency history.  None keeps sampling bit-identical.
        self.degrade = degrade
        # ingest: a comm.ingest.IngestPipeline (ISSUE 20).  The wave
        # engine has no wire frames to stage — what pipelining buys here
        # is overlap: the main thread keeps LAUNCHING waves (this
        # regime's "network") while the single fold worker runs
        # admission → fold → health → local-alg accumulation for the
        # waves already completed, in arrival order.  All fold-side
        # state (stream, admission, health, tau/scaffold accumulators)
        # is worker-only between round_start and the pre-finalize
        # drain(); the main thread only reads it after the drain, so the
        # round stays bit-identical to the inline path.  scaffold's
        # per-round gathers are safe: a round's cohort is sampled
        # without replacement, so wave i's scatter and wave i+1's gather
        # touch disjoint client rows.
        self.ingest = ingest
        # seeded wave-summary poisoning, injected PRE-admission — the
        # mega-cohort path's first-class attacker (no per-silo message
        # seam exists inside a compiled wave)
        if cfg.wave_adversary:
            from fedml_tpu.robust.adversary import parse_wave_adversary_spec
            self._wave_attacks = parse_wave_adversary_spec(
                cfg.wave_adversary)
        else:
            self._wave_attacks = {}
        # lazily bound on first round (they need the params template)
        self.stream: Optional[StreamingAggregator] = None
        self.admission: Optional[WaveAdmission] = None
        # scaffold per-client state (host-stacked, fedavg.py convention)
        self.c_global = None
        self.c_locals = None

        reg = telemetry.get_registry()
        self._c_rounds = reg.counter("fedml_cohort_rounds_total")
        self._c_waves = reg.counter("fedml_cohort_waves_total")
        self._c_clients = reg.counter("fedml_cohort_clients_total")
        self._h_wave = reg.histogram("fedml_cohort_wave_seconds")
        self._h_fold = reg.histogram("fedml_cohort_fold_seconds")
        # the round path's timing sites (`_span`): live when something
        # reads them — the recorder's ledger and tracer, the telemetry
        # histograms, the degrade tracker's completion latencies
        self._tracer = perf.tracer if perf is not None else None
        self._timed = (perf is not None or degrade is not None
                       or reg.enabled)
        self._round_ctx = None  # the round span's context (fold worker)
        # staging one wave ahead: the worker (started with the first wave
        # staged, joined by `run`) and the one wave it holds or is making
        self._stage_ahead = stage_ahead
        self._stage_pool: Optional[ThreadPoolExecutor] = None
        self._staged = None     # a Future of (ids, pad_to, rows)
        # how the wave program runs its client axis; the sgd / fedprox
        # wave sets it when it is traced, the scaffold and fednova waves
        # keep their own vmap
        self._wave_axis = "vmap"
        # every client's count of steps that hold a row, for
        # `wave.dispatch`'s step counts: read once from the population's
        # mask, and only where a span will carry them
        self._real_steps = (
            np.asarray(data.train["mask"]).any(axis=-1).sum(axis=-1)
            if self._tracer is not None else None)

        # the round's global on the host, kept by the round that made it
        # where a reader needs one: (the device tree it mirrors, its copy)
        self._mirror = None
        # the global's CRC on the device; the worker that has it computed
        # (and takes that copy), and the one job it holds or is at
        self._tree_crc = TreeCrc()
        self._crc_pool: Optional[ThreadPoolExecutor] = None
        self._crc_job = None
        # whether the wave leaves the device as its sum (`_ensure_bound`
        # decides, from the tree): the program that does, where the
        # configuration allows one
        self._summed = False
        self._summed_any_round = False
        self._summed_fn = None
        self._wave_fn = self._build_wave_fn(workload, cfg, mesh)
        self._stats_fn = jax.jit(admission_stats)
        self._mean_fn = jax.jit(mean_of_sum)
        if perf is not None:
            # the wave program is THE hot jit of this engine: recompile
            # sentry + (under --device_obs) compile ledger / MFU gauge
            self._wave_fn = perf.instrument_jit("wave_train", self._wave_fn)
            if self._summed_fn is not None:
                self._summed_fn = perf.instrument_jit("wave_train_summed",
                                                      self._summed_fn)

    # -- wave program construction ------------------------------------------
    def _build_wave_fn(self, workload, cfg, mesh):
        if cfg.local_alg in ("sgd", "fedprox"):
            opt = make_client_optimizer(cfg.client_optimizer, cfg.lr,
                                        cfg.wd)
            local = make_local_trainer(
                workload, opt, cfg.epochs,
                prox_mu=cfg.mu if cfg.local_alg == "fedprox" else 0.0)

            def counted(metrics, wave_data):
                # what the workload's loss counted (an expert layer's
                # tokens), a client: the wave summary weights every aux
                # by the client's rows, so each is divided by them first
                # and the weighted sum is the plain sum over live clients
                w = jnp.maximum(wave_data["num_samples"].astype(
                    jnp.float32), 1.0)
                return {k: v / w.reshape((-1,) + (1,) * (v.ndim - 1))
                        for k, v in metrics.get("counters", {}).items()}

            def make_stacked(params, wave_data, rng, offset):
                # resolved here, under the wave program's trace (shapes
                # are static there), and kept for `wave.dispatch`'s
                # slot counts
                self._wave_axis = self._choose_axis(params)
                stacked, metrics = train_cohort(
                    local, params, wave_data, rng, index_offset=offset,
                    client_axis=self._wave_axis)
                return stacked, counted(metrics, wave_data)

            def train_summed(params, wave_data, rng, offset):
                wave_sum, total, metrics = train_cohort_sum(
                    local, params, wave_data, rng, index_offset=offset)
                return wave_sum, total, counted(metrics, wave_data)

            if (mesh is None and not cfg.norm_clip
                    and not cfg.agg_noise_std and not cfg.wave_adversary):
                # nothing reads one client's result: where the clients
                # also train in sequence (`_ensure_bound`), the wave
                # leaves the device as its sum
                self._summed_fn = make_summed_wave_fn(train_summed)
            return make_wave_fn(make_stacked, mesh=mesh)

        if cfg.local_alg == "fednova":
            # plain normalized averaging (momentum/prox/gmf off: the gmf
            # server buffer is cross-round state outside this engine's
            # O(model) contract; algorithms/fednova.py carries the full
            # variant).  tau_src = a_i (the mu=0 branch).
            from fedml_tpu.algorithms.fednova import (
                FedNovaConfig, make_fednova_local_trainer)
            ncfg = FedNovaConfig(lr=cfg.lr, epochs=cfg.epochs, wd=cfg.wd,
                                 batch_size=cfg.batch_size, seed=cfg.seed)
            nova_local = make_fednova_local_trainer(workload, ncfg)

            def make_stacked(params, wave_data, rng, offset):
                _, aux = train_cohort(nova_local, params, wave_data, rng,
                                      index_offset=offset,
                                      client_axis="vmap")
                a = jnp.maximum(aux["a_i"], 1e-12)
                # pseudo-params y_i = x − cum_grad_i/a_i: their weighted
                # stream mean is x − Σ p_i d_i, so the one mean spine
                # serves Nova too; the tau_eff server step closes the
                # round host-side from the aux weighted sums
                pseudo = jax.tree.map(
                    lambda p, cg: p[None] - cg
                    / a.reshape((-1,) + (1,) * (cg.ndim - 1)),
                    params, aux["cum_grad"])
                return pseudo, {"tau": aux["a_i"]}

            return make_wave_fn(make_stacked, mesh=mesh)

        # scaffold
        from fedml_tpu.algorithms.scaffold import make_scaffold_local
        local = make_scaffold_local(workload, cfg.lr, cfg.epochs)
        return make_scaffold_wave_fn(local, cfg.lr)

    def _choose_axis(self, params) -> str:
        """The sgd / fedprox wave's client axis: the one named, else
        `choose_client_axis` of the tree, the wave and this device."""
        cfg = self.cfg
        return cfg.client_axis or choose_client_axis(
            params, cfg.wave_size, device_memory_bytes())

    # -- sampling -------------------------------------------------------------
    def _sample_round(self, round_idx: int) -> np.ndarray:
        """Cohort ids for one round.  ``numpy`` is the reference's
        bit-exact seeded chain (curves line up with published
        baselines); ``jax`` is the on-device permutation sampler.  THE
        TWO DIVERGE — same (round, N, m) yields different cohorts
        (pinned in tests/test_cross_device.py) — which is why the
        choice lands in every metrics row.  Both resample
        deterministically — numpy in the ROUND INDEX alone (reference
        parity: ``--seed`` varies init, never the cohort schedule),
        jax in (seed, round) — so a resumed run re-samples the exact
        cohorts the crashed run would have."""
        cfg = self.cfg
        per = cfg.client_num_per_round
        if self.controller is not None:
            # the adaptive cohort lever is LIVE here: the sampler draws
            # from the full population and the wave planner pads any
            # cohort into static-width waves, so widening never retraces
            # a compiled program (the per-round count itself is ledgered)
            per = max(1, min(self.controller.cohort,
                             self.data.client_num))
        if cfg.sampler == "jax":
            key = jax.random.fold_in(
                jax.random.fold_in(jax.random.key(cfg.seed), 0x5A4D50),
                round_idx)
            ids = np.asarray(sample_clients_jax(
                key, self.data.client_num, per))
        else:
            ids = sample_clients(round_idx, self.data.client_num, per)
        if self.degrade is not None:
            # priority re-tasking (ISSUE 19): clients carrying
            # participation debt claim the cohort head, the seeded
            # sample fills the rest — zero debt leaves the draw
            # untouched (bit-identical to the pre-19 schedule)
            pri = [c - 1 for c in self.degrade.priority_clients(per)]
            if pri:
                from fedml_tpu.robust.degrade import merge_priority
                ids = np.asarray(
                    merge_priority([int(c) for c in ids], pri, per),
                    dtype=np.int64)
        return ids

    # -- lazy round machinery -------------------------------------------------
    def _ensure_bound(self, params) -> None:
        if self.stream is None:
            cfg = self.cfg
            if self._summed_fn is not None:
                # the sum stands for the clients' results only where they
                # train one after another (its order is the fold's) and
                # every leaf accumulates in its own dtype
                self._wave_axis = self._choose_axis(params)
                self._summed = (self._wave_axis == "scan" and all(
                    jnp.issubdtype(x.dtype, jnp.floating)
                    for x in jax.tree.leaves(params)))
                # a round of several waves adds wave sums, which orders
                # its additions by wave and not by slot: kept to trees
                # whose stacked wave could not be held anyway
                self._summed_any_round = self._summed and \
                    wave_outgrows_device(params, cfg.wave_size,
                                         device_memory_bytes())
            self.stream = StreamingAggregator(
                params, method="mean", kind="params",
                norm_clip=cfg.norm_clip, noise_std=cfg.agg_noise_std,
                seed=cfg.seed,
                sentry=self.perf.sentry if self.perf else None,
                device=self.perf.device if self.perf else None)
            self.admission = WaveAdmission(
                jax.tree.map(np.asarray, params),
                norm_k=cfg.norm_screen_k,
                norm_window=cfg.norm_screen_window,
                norm_min_history=cfg.norm_screen_min_history,
                norm_screen=cfg.admission != "off")
        if self.cfg.local_alg == "scaffold" and self.c_global is None:
            self.c_global = jax.tree.map(jnp.zeros_like, params)
            self.c_locals = zeros_client_state(
                jax.tree.map(np.asarray, params), self.data.client_num)

    def _span(self, name: str, phase: Optional[str] = None, hist=None,
              wait: Optional[str] = None, **kw):
        """THE timing site of the round path: one clock interval per
        boundary, handed to the span ``name`` (and the profiler
        annotation under it), to the ledger phase ``phase`` and to the
        histogram ``hist`` (`obs.trace.TimedSpan`).  The shared null
        context when nothing reads it (one branch, nothing kept: the pin
        in tests/test_critical_path.py)."""
        if not self._timed:
            return trace.NULL_CONTEXT
        return trace.TimedSpan(self._tracer, name, self.perf, phase, hist,
                               wait, **kw)

    # -- the wave loop --------------------------------------------------------
    def _pin_placement(self, params):
        """Mesh runs: commit the round's params to ONE replicated
        sharding.  Round 0's host-fed params and round N's finalize
        outputs otherwise arrive with different committed shardings and
        key SEPARATE wave-jit cache entries — a per-round retrace the
        strict sentry rightly fails (caught live on the CLI mesh path)."""
        if self.wave_mesh is None:
            return params
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.device_put(params,
                              NamedSharding(self.wave_mesh, P()))

    def _fold_one(self, round_idx, wi, wave, stacked, w, mean,
                  wave_weight, aux_sums, new_c, c_delta, host_params,
                  acc, stats=None, total=None) -> None:
        """Post-wave work for ONE completed wave: admission screen →
        stream fold → health sketch → local-alg accumulation.  Runs
        inline, or (``--ingest_pipeline``) on the single fold worker in
        wave-completion order — same code, same order, bit-identical.
        Every argument is bound at submit time (no late-binding loop
        closures); ``acc`` carries the round's cross-wave accumulators,
        touched only here until the pre-finalize drain."""
        if wave_weight <= 0:
            # a wave of only weightless clients (all-pad / all-empty
            # shards): folds as weight 0 — skipped entirely, never a
            # 0/0 in the normalizer (pinned in tests)
            return
        # explicit parent: on the fold worker's thread no span is active
        with self._span("fold_wave", parent=self._round_ctx):
            self._screen_and_fold(
                round_idx, wi, wave, stacked, w, mean, wave_weight,
                aux_sums, new_c, c_delta, host_params, acc, stats, total)

    def _screen_and_fold(self, round_idx, wi, wave, stacked, w, mean,
                         wave_weight, aux_sums, new_c, c_delta,
                         host_params, acc, stats, total):
        """`_fold_one`'s body, under its span.  ``stacked`` is the wave's
        results a client, or, where the wave left the device as its sum
        (``self._summed``), that sum, ``mean`` then None and ``total``
        its weight on the device.  The screen reads ``stats``, a few
        numbers the wave program made beside its summary; the wave's
        mean comes to the host only for the poison seam and the health
        sketch."""
        cfg = self.cfg
        summed = mean is None
        attack = self._wave_attacks.get((round_idx, wi))
        mean_host = None
        if attack is not None or self.health is not None:
            with self._span("admission.copy", "admission", wait="device"):
                if mean is None:
                    mean = self._mean_fn(stacked, total,
                                         self.stream.reference)
                mean_host = jax.device_get(mean)
        with self._span("admission.screen", "admission"):
            if attack is None:
                verdict = self.admission.screen(
                    stacked if mean is None else mean,
                    stats=jax.device_get(stats))
            else:
                # poison the WAVE SUMMARY pre-admission: the screen, the
                # health sketch, and the fold all see the attacked mean —
                # exactly what a compromised wave aggregation would ship
                from fedml_tpu.robust.adversary import poison_wave_summary
                mean_host = poison_wave_summary(attack, mean_host,
                                                host_params,
                                                seed=cfg.seed)
                logger.warning("round %d wave %d POISONED (%s:%g)",
                               round_idx, wi, attack.kind, attack.param)
                verdict = self.admission.screen(mean_host, host_params)
        if not verdict.ok:
            logger.warning("round %d wave %d REJECTED (%s): %d "
                           "clients' work discarded", round_idx, wi,
                           verdict.reason, wave.n_live)
            if self.health is not None:
                self.health.observe_rejected(wi + 1, verdict.reason)
            return
        with self._span("fold.dispatch", "fold", self._h_fold):
            if attack is not None:
                # fold the POISONED mean through the SAME stacked wave
                # program as every clean wave — each member ships the
                # attacked mean (the weighted mean of identical rows IS
                # the row), so the spine receives what admission and
                # health were shown AND its hot fold never traces a new
                # path in an attack round (the strict recompile sentry
                # holds even under attack)
                poisoned = jax.tree.map(
                    lambda m, s: jnp.broadcast_to(
                        jnp.asarray(m, dtype=s.dtype), s.shape),
                    mean_host, stacked)
                self.stream.fold_wave(poisoned, w)
            elif summed:
                # the wave's own slot-order sum is the summary the fold
                # takes (`mean`, where made above, was made from it)
                self.stream.fold_sum(stacked, w, total)
            else:
                self.stream.fold_wave(stacked, w)
        acc["folded"] += 1
        acc["live"] += wave.n_live
        self._c_clients.inc(wave.n_live)
        if self.health is not None:
            with self._span("health", "health"):
                self.health.observe_admitted(wi + 1, mean_host,
                                             wave_weight,
                                             norm=verdict.norm)
        if cfg.local_alg == "fednova":
            acc["tau"] += float(aux_sums["tau"])
        elif cfg.local_alg == "scaffold":
            # admitted waves only: a rejected wave's work — params
            # AND variates — is discarded for the round
            self.c_locals = scatter_client_rows(
                self.c_locals, wave.ids, jax.tree.map(np.asarray,
                                                      new_c))
            acc["c_delta"] = (
                c_delta if acc["c_delta"] is None else
                jax.tree.map(jnp.add, acc["c_delta"], c_delta))

    def _dispatch_counts(self, wave, prefetched: bool) -> dict:
        """What rides `wave.dispatch` where a tracer keeps spans, counted
        on the host with no device read: the wave's static ``slots`` and the
        client-``steps`` the wave program was handed (slots x steps a
        slot x epochs).  ``slots_sequential`` and ``steps_skipped`` say
        what the sequential client axis made of them: every slot trained
        in turn, and every step whose batch holds no row (all of a padded
        slot's) branched around by the local trainer.  Both are 0 under
        ``vmap``, where a `cond` lowers to a select over both branches
        (`make_local_trainer`).  ``slots_prefetched`` is the wave's slots
        when its rows were taken from the stager (gathered one wave
        ahead) and 0 when they were gathered inline; ``slots_staged`` is
        the whole it is a share of, ``slots`` again under a name that
        came with it: the benchmark's share reader picks its spans by
        the whole's name, and a program before the stager carries
        ``slots`` without the part."""
        W, epochs = self.cfg.wave_size, self.cfg.epochs
        steps = W * self.data.train["mask"].shape[1] * epochs
        counts = {"slots": W, "slots_sequential": 0,
                  "slots_staged": W,
                  "slots_prefetched": W if prefetched else 0,
                  "steps": steps, "steps_skipped": 0}
        if self._wave_axis == "scan":
            counts["slots_sequential"] = W
            counts["steps_skipped"] = steps - epochs * int(
                self._real_steps[wave.ids].sum())
        return counts

    @staticmethod
    def _model_counts(aux_sums) -> dict:
        """What the model's layers counted over the wave's steps (summed
        over layers, steps and clients), for `wave.dispatch`.  Always,
        and 0 for a model without the mechanism: ``attn_calls``, the
        attention cores the wave program was handed
        (`models.transformer.LatentAttention`,
        `models.indexed_attention.IndexedAttention`,
        `models.window_attention.WindowAttention`: layers x client-steps
        that ran), ``attn_calls_fused``, those of them the fused kernels
        took (`causal_blocked_attention` says which),
        ``attn_pairs_causal``, the causal (query, key) pairs of the cores
        whose keys an indexer selects, and ``attn_pairs_selected``, those
        of them it selected, ``attn_tiles_causal``, the key tiles on or
        below the diagonal of the cores under a window (sequences x query
        heads, at the blocks of the path that took them: the precondition
        there is a window of at least one key, so every row sees its
        own), and ``attn_tiles_visited``, those of them the core computed
        (`models.transformer.window_tiles`).  For an expert model
        (`models.moe.HeldExpertMoE`) also the ``tokens`` routed, their
        ``expert_assignments`` (tokens x experts a token),
        ``expert_assignments_held`` (those whose expert this chip holds),
        and the sums over layer-steps of the fullest held expert's tokens
        and of the mean held expert's (``expert_load_max`` /
        ``expert_load_mean``).  Float32 sums: exact up to 2**24 and to
        seven digits beyond."""
        names = {"attn": ("attn_calls", "attn_calls_fused"),
                 "select": ("attn_pairs_causal", "attn_pairs_selected"),
                 "window": ("attn_tiles_causal", "attn_tiles_visited"),
                 "moe": ("tokens", "expert_assignments",
                         "expert_assignments_held", "expert_load_max",
                         "expert_load_mean")}
        read = jax.device_get({k: aux_sums[k] for k in names
                               if k in aux_sums})
        counts = dict.fromkeys(names["attn"] + names["select"]
                               + names["window"], 0.0)
        for k, values in read.items():
            counts.update(zip(names[k], (float(v) for v in values)))
        return counts

    # -- staging one wave ahead ----------------------------------------------
    def _stage_next(self, waves, wi, round_idx) -> None:
        """Called once wave ``wi``'s program is dispatched: have the
        worker gather and hand over the wave that follows it, the same
        round's or the first of round ``round_idx + 1`` (none past the
        last round).  At most one wave is ever staged: the loop takes
        (or drops) it before it asks for the next."""
        if not self._stage_ahead:
            return
        if wi + 1 < len(waves):
            ids = waves[wi + 1].ids
        elif round_idx + 1 < self.cfg.comm_round:
            ids = None          # the worker samples the next round
        else:
            return
        if self._stage_pool is None:
            self._stage_pool = ThreadPoolExecutor(
                1, thread_name_prefix="fedml-stage")
        self._staged = self._stage_pool.submit(
            self._stage_wave, ids, round_idx + 1, self._round_ctx)

    def _stage_wave(self, ids, next_round, round_ctx):
        """On the worker.  ``ids`` None: the first wave of ``next_round``
        as this moment's sampler would draw it (state the running round
        has yet to write, a controller's verdict or a client's debt,
        makes the real draw differ, and the loop then gathers inline).
        Only the population's rows are read here, which no round writes;
        the rows gathered are this wave's alone until the loop takes or
        drops them."""
        W = self.cfg.wave_size
        # explicit parent, as `fold_wave`'s: the round this runs beside
        with self._span("stage.prefetch", parent=round_ctx):
            if ids is None:
                ids = plan_waves(self._sample_round(next_round), W)[0].ids
            # stage.gather and stage.put open inside, under this span
            return ids, W, gather_cohort(self.data.train, ids, pad_to=W)

    def _take_staged(self, ids, pad_to):
        """The staged wave's rows if they are what is asked for now (the
        same ids, the same ``pad_to``), else None: a miss, whose rows are
        dropped.  Waits for the worker where it is still at it, and
        re-raises here what it raised."""
        staged, self._staged = self._staged, None
        if staged is None:
            return None
        staged_ids, staged_pad, rows = staged.result()
        if staged_pad == pad_to and np.array_equal(staged_ids, ids):
            return rows
        return None

    # -- the global's CRC, one round behind -----------------------------------
    def _start_crc(self, params):
        """Hand the round's new global to the worker, which has its CRC
        computed on the device (`_crc_of`).  Returns the job, a future of
        the CRC.  At most one job is in flight: the one before it is
        joined first and re-raises here."""
        if self._crc_job is not None:
            self._join_crc()
        if self._crc_pool is None:
            self._crc_pool = ThreadPoolExecutor(
                1, thread_name_prefix="fedml-crc")
        self._crc_job = self._crc_pool.submit(self._crc_of, params,
                                              self._round_ctx)
        return self._crc_job

    def _join_crc(self) -> int:
        """Wait for the CRC job in flight under `round.crc_join`: the
        main thread's wait on the worker (``waited`` 0 where the job had
        ended already).  Re-raises what the job raised."""
        job = self._crc_job
        with self._span("round.crc_join", wait="worker", joins=1,
                        waited=int(not job.done())):
            return job.result()

    def _crc_of(self, params, round_ctx) -> int:
        """On the worker: the global's CRC program (`core.global_crc`:
        `utils.journal.tree_crc`'s value, and nothing of the global
        leaves the device), queued at once, so behind the round's server
        step and ahead of the next round's first wave, and its word read
        once the device has it.  The first round's job builds the
        program, beside the main thread.  Where a reader needs the global
        on the host (the health sketch, the poison seam), its copy is
        taken here too, one batched transfer, and kept as the next
        round's host copy (`_run_round`); the pair holds the device tree
        too.  Reads ``params``, which no round writes."""
        # explicit parent, as `stage.prefetch`'s: the round it closes
        with self._span("round.crc", parent=round_ctx,
                        on_device=1) as crc_sp:
            crc = self._tree_crc.dispatch(params)
            if self._tracer is not None:
                crc_sp.set(bytes=sum(leaf.nbytes
                                     for leaf in jax.tree.leaves(params)))
            if self.health is not None or self._wave_attacks:
                self._mirror = (params, jax.device_get(params))
            return int(crc)

    def _stop_staging(self) -> None:
        """Drop what is staged and join the worker."""
        self._staged = None
        if self._stage_pool is not None:
            self._stage_pool.shutdown(wait=True)
            self._stage_pool = None
        if self._crc_pool is not None:
            self._crc_pool.shutdown(wait=True)    # the last line lands
            self._crc_pool = None
        self._crc_job = None

    def _run_round(self, params, ids, round_rng, round_idx):
        """One round over the cohort ``ids``: every wave trained, screened
        and folded, then finalize and the server step.  A wave's rows
        come from the stager when it holds exactly that wave (`ids` as
        the default sampler draws them: every wave but a run's first),
        and are gathered inline otherwise, so a caller may pass any
        cohort; the values computed are the same either way."""
        cfg = self.cfg
        W = cfg.wave_size
        waves = plan_waves(ids, W)
        # what `_fold_one` hangs its span under: the round span `run`
        # opened around this call (none when a caller drives rounds itself)
        self._round_ctx = (self._tracer.current_context()
                           if self._tracer is not None else None)
        # the host's copy of this global, if the round that made it kept
        # one (looked up before the pin, which may hand back another tree)
        needs_host = self.health is not None or bool(self._wave_attacks)
        if needs_host and self._crc_job is not None:
            self._join_crc()            # the worker may still be at it
        mirror, self._mirror = self._mirror, None
        host_params = (mirror[1] if mirror is not None
                       and mirror[0] is params else None)
        with self._span("round.pin"):
            params = self._pin_placement(params)
            self._ensure_bound(params)
            self.admission.round_start()
            self.stream.reset(params)
        with self._span("round.host_copy", wait="device"):
            # read by the health sketch and the poison seam alone: the
            # kept copy where there is one, else one batched transfer
            # (every leaf's started before any is awaited), else nothing
            if not needs_host:
                host_params = None
            elif host_params is None:
                host_params = jax.device_get(params)
        if self.health is not None:
            self.health.round_start(round_idx, host_params,
                                    expected=range(1, len(waves) + 1))
        # cross-wave accumulators: one mutable dict so the fold worker
        # (--ingest_pipeline) and the inline path share the same code;
        # the main thread reads it only after the pre-finalize drain
        # the wave as its sum: a one-wave round (the sum is the slot-order
        # fold, bit for bit), or any round of a tree too large to stack
        summed = self._summed and (len(waves) == 1
                                   or self._summed_any_round)
        acc = {"tau": 0.0,             # fednova: Σ n_i·tau_i across waves
               "c_delta": None,        # scaffold: Σ live·(c_i+ − c_i)
               "folded": 0, "live": 0}
        wave_devices = 0  # devices the last wave's updates land on

        for wi, wave in enumerate(waves):
            if wave.n_live == 0:
                continue  # empty-cohort edge: nothing sampled
            with self._span("wave", "wave", self._h_wave) as wave_sp:
                wave_data = self._take_staged(wave.ids, W)
                prefetched = wave_data is not None
                if not prefetched:
                    # a miss: stage.gather and stage.put open inside
                    # gather_cohort, under this span
                    wave_data = gather_cohort(self.data.train, wave.ids,
                                              pad_to=W)
                if cfg.local_alg == "scaffold":
                    with self._span("stage.gather"):
                        c_cohort = gather_client_rows(self.c_locals,
                                                      wave.ids, W)
                with self._span("wave.dispatch") as dispatch_sp:
                    offset = jnp.int32(wave.offset)
                    if cfg.local_alg == "scaffold":
                        (stacked, w, mean, total, new_c, c_delta,
                         _m) = self._wave_fn(params, wave_data, round_rng,
                                             offset, self.c_global,
                                             c_cohort)
                        aux_sums = {}
                        stats = self._stats_fn(mean, params)
                    elif summed:
                        # `stacked` is the wave's sum from here on
                        stacked, w, total, aux_sums, stats = \
                            self._summed_fn(params, wave_data, round_rng,
                                            offset)
                        mean = new_c = c_delta = None
                    else:
                        stacked, w, mean, total, aux_sums = self._wave_fn(
                            params, wave_data, round_rng, offset)
                        new_c = c_delta = None
                        stats = self._stats_fn(mean, params)
                    # the chip is busy from here: stage the wave after
                    self._stage_next(waves, wi, round_idx)
                    if self._real_steps is not None:
                        dispatch_sp.set(
                            **self._dispatch_counts(wave, prefetched))
                with self._span("wave.wait", wait="device"):
                    # blocks: the wave ran to completion
                    wave_weight = float(total)
                    if wi == len(waves) - 1:
                        wave_devices = placement_of(stacked)["devices"]
                    if self._real_steps is not None:
                        dispatch_sp.set(**self._model_counts(aux_sums))
            self._c_waves.inc()
            if self.degrade is not None:
                # every live client completed with the wave: feed the
                # latency history and repay any participation debt
                for cid in wave.ids:
                    self.degrade.observe_completion(int(cid) + 1,
                                                    wave_sp.seconds)
                    self.degrade.note_accept(int(cid) + 1)
            if self.perf is not None:
                # a completed wave is this regime's "upload arrival" on
                # the round's critical-path timeline
                self.perf.note_arrival()
            if self.ingest is not None:
                # hand the post-wave work to the fold worker and go
                # launch the next wave.  submit_wait (not submit): a
                # wave the server itself produced can never be load-shed
                # — the bounded queue applies BACKPRESSURE here, pacing
                # wave launches to what the folder absorbs.  One shard
                # queue = arrival-order folds = bit-parity with inline.
                self.ingest.submit_wait(0, functools.partial(
                    self._fold_one, round_idx, wi, wave, stacked, w,
                    mean, wave_weight, aux_sums, new_c, c_delta,
                    host_params, acc, stats, total))
            else:
                self._fold_one(round_idx, wi, wave, stacked, w, mean,
                               wave_weight, aux_sums, new_c, c_delta,
                               host_params, acc, stats, total)

        if self.ingest is not None:
            # rendezvous: every queued fold lands before finalize reads
            # the stream (the wait is the round's true fold overhang)
            with self._span("fold.drain", "barrier_wait"):
                self.ingest.drain()
        folded, live_clients = acc["folded"], acc["live"]
        tau_acc, c_delta_acc = acc["tau"], acc["c_delta"]

        if self.stream.count == 0:
            logger.warning("round %d: every wave empty or rejected — "
                           "global unchanged", round_idx)
            new_params = params
        else:
            # its span, finalize.dispatch (phase fold), opens inside
            new_params = self.stream.finalize(round_idx)
            with self._span("server_step"):
                if cfg.local_alg == "fednova":
                    # x+ = x − tau_eff·Σ p_i d_i, with mean = x − Σ p_i d_i
                    tau_eff = tau_acc / self.stream.weight_total
                    new_params = jax.tree.map(
                        lambda p, m: (p.astype(jnp.float32) - tau_eff
                                      * (p.astype(jnp.float32)
                                         - m.astype(jnp.float32))
                                      ).astype(p.dtype),
                        params, new_params)
                elif (cfg.local_alg == "scaffold"
                      and c_delta_acc is not None):
                    # c+ = c + (|S|/N)·mean(c_i+ − c_i) = c + Σdelta/N
                    n_total = float(self.data.client_num)
                    self.c_global = jax.tree.map(
                        lambda cg, dv: cg + dv / n_total,
                        self.c_global, c_delta_acc)
                if self.server_opt is not None:
                    # the server-optimizer seam: Δ = params − finalize,
                    # one jitted step (plain returns the finalize
                    # untouched)
                    new_params = self.server_opt.apply(params, new_params,
                                                       round_idx)
        self._c_rounds.inc()
        if self.health is not None:
            self.health.round_end(
                round_idx, new_global=jax.device_get(new_params),
                cohort=len(ids), waves=len(waves), folded_waves=folded)
        return new_params, {"waves": len(waves), "folded_waves": folded,
                            "clients": live_clients,
                            "wave_devices": wave_devices}

    # -- run loop -------------------------------------------------------------
    def run(self, params=None, rng: Optional[jax.Array] = None,
            checkpointer=None):
        cfg = self.cfg
        with self._span("setup.init"):
            rng = rng if rng is not None else jax.random.key(cfg.seed)
            if params is None:
                # the FedAvg.run rng chain, mirrored exactly: parity runs
                # on the same seed start from the same init and round rngs
                rng, init_rng = jax.random.split(rng)
                params = self.workload.init(init_rng, jax.tree.map(
                    lambda v: v[0, 0], {k: self.data.train[k]
                                        for k in ("x", "y", "mask")}))
            params, rng, start_round = self._maybe_resume(checkpointer,
                                                          params, rng)
            # normalize to device arrays once: a numpy round-0 global and
            # later jax outputs must key ONE wave jit entry (the PR 5
            # double-compile class)
            params = jax.tree.map(jnp.asarray, params)
        try:
            for round_idx in range(start_round, cfg.comm_round):
                # the round's root span, one trace id a round; always a
                # live site (never the null context): the metrics row's
                # round_s is read from it
                with trace.TimedSpan(self._tracer, "round", self.perf,
                                     parent=None,
                                     round=round_idx) as round_sp:
                    params, rng = self._round(params, rng, round_idx,
                                              round_sp, checkpointer)
            if self._crc_job is not None:
                self._join_crc()            # the last round's; re-raises
        finally:
            # the last round stages nothing; a round that raised may have
            self._stop_staging()
            # nothing model-sized outlives the loop: the engine sits in
            # reference cycles (its wave closures) and is collected late
            self._mirror = None
            if self.stream is not None:
                self.stream.release()
        if checkpointer is not None:
            checkpointer.flush()
        if self.ingest is not None:
            # every round drained before its finalize; nothing queued
            self.ingest.stop()
        return params

    def _round(self, params, rng, round_idx, round_sp, checkpointer):
        """One pass of `run`'s loop, under the round's root span."""
        cfg = self.cfg
        if self.perf is not None:
            self.perf.round_start(round_idx)
        with self._span("round.sample"):
            ids = self._sample_round(round_idx)
        rng, round_rng = jax.random.split(rng)
        params, info = self._run_round(params, ids, round_rng, round_idx)
        with self._span("round.sync", wait="device"):
            jax.block_until_ready(params)
        if self.publish is not None:
            with self._span("round.publish"):
                self.publish(params, round_idx + 1)
        decision = None
        if self.controller is not None:
            # the pacing verdict for the NEXT round, from this
            # round's health line (decided before the checkpoint so
            # a resume continues the same trajectory)
            kw = ({"debt": self.degrade.max_debt()}
                  if self.degrade is not None else {})
            decision = self.controller.decide(
                round_idx,
                self.health.last_line if self.health is not None
                else None, **kw)
        round_s = round_sp.elapsed()
        if self.perf is not None:
            extra = dict(info)
            # the round's post-finalize global CRC: the ingest
            # bench's bit-parity gate compares this sequence between
            # the inline and pipelined twins (utils.journal.tree_crc's
            # value — the same checksum the crash journal trusts),
            # computed on the device for the worker; the ledger line
            # below is written when it is there
            extra["global_crc"] = self._start_crc(params)
            if self.server_opt is not None:
                extra["server_opt"] = self.server_opt.name
            if decision is not None:
                extra["adapt"] = decision.as_ledger()
            with self._span("round.ledger"):
                self.perf.round_end(round_idx, cohort=len(ids),
                                    wave_size=cfg.wave_size, **extra)
        if self.slo is not None:
            with self._span("round.slo"):
                self.slo.evaluate()
        if (round_idx % cfg.frequency_of_the_test == 0
                or round_idx == cfg.comm_round - 1):
            with self._span("eval"):
                stats = self.evaluate_global(params)
            where = placement_of(params)
            stats.update(round=round_idx, round_s=round_s,
                         cohort=len(ids), waves=info["waves"],
                         folded_waves=info["folded_waves"],
                         wave_size=cfg.wave_size,
                         # provenance: which sampler/trainer made
                         # this curve — never silently cross-compare
                         sampler=cfg.sampler,
                         local_alg=cfg.local_alg,
                         # where the state landed
                         wave_devices=info["wave_devices"],
                         global_platform=where["platform"],
                         global_devices=where["devices"])
            logger.info("round %d: %s", round_idx, stats)
            self.history.append(stats)
            if self.sink is not None:
                self.sink.log(stats, step=round_idx)
        if checkpointer is not None:
            with self._span("checkpoint"):
                checkpointer.maybe_save(
                    round_idx, self._ckpt_state(params, rng, round_idx),
                    last_round=round_idx == cfg.comm_round - 1)
        return params, rng

    # -- checkpoint extra state (scaffold control variates, server
    # optimizer, adaptive controller) -----------------------------------------
    def _extra_state(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {}
        if self.cfg.local_alg == "scaffold" and self.c_global is not None:
            out["scaffold"] = {"c_global": self.c_global,
                               "c_locals": self.c_locals}
        if self.server_opt is not None:
            out["srv_opt"] = self.server_opt.state_dict()
        if self.controller is not None:
            out["adapt"] = self.controller.state_dict()
        if self.degrade is not None:
            out["degrade"] = self.degrade.state_dict()
        return out

    def _extra_state_template(self, params) -> Dict[str, Any]:
        out: Dict[str, Any] = {}
        if self.cfg.local_alg == "scaffold":
            out["scaffold"] = {
                "c_global": jax.tree.map(jnp.zeros_like, params),
                "c_locals": zeros_client_state(
                    jax.tree.map(np.asarray, params),
                    self.data.client_num)}
        if self.server_opt is not None:
            out["srv_opt"] = self.server_opt.state_template()
        if self.controller is not None:
            out["adapt"] = self.controller.state_dict()
        if self.degrade is not None:
            out["degrade"] = self.degrade.state_dict()
        return out

    def _load_extra_state(self, extra) -> None:
        if self.cfg.local_alg == "scaffold" and "scaffold" in extra:
            self.c_global = extra["scaffold"]["c_global"]
            self.c_locals = jax.tree.map(np.asarray,
                                         extra["scaffold"]["c_locals"])
        if self.server_opt is not None and "srv_opt" in extra:
            self.server_opt.load_state_dict(extra["srv_opt"])
        if self.controller is not None and "adapt" in extra:
            self.controller.load_state_dict(extra["adapt"])
        if self.degrade is not None and "degrade" in extra:
            self.degrade.load_state_dict(extra["degrade"])
