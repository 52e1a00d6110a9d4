"""Cross-silo FedAvg: the reference's distributed message choreography on the
host-edge transport layer.

Reference equivalent: the 5-file MPI pattern of
``fedml_api/distributed/fedavg/`` — FedAvgServerManager.py:18-95 (init
broadcast, receive barrier, aggregate, sync), FedAvgClientManager.py:18-75
(train on init/sync, upload), message_define.py:1-30 (int message types).

On-pod this entire choreography collapses into one jit program
(`fedml_tpu.parallel.cohort`); these actors exist for *true* cross-silo
federation — separate hosts/trust domains over gRPC/DCN — where each silo
trains with its own local jit program and only the global aggregation rides
messages.  Weights travel as binary array frames, not JSON float lists
(the reference's transform_tensor_to_list codec, fedavg/utils.py:7-16).

The "process k plays sampled client i" trick (FedAVGTrainer.update_dataset,
FedAVGTrainer.py:25-29) is preserved: the server sends each silo a
``client_idx`` each round and the silo re-points its local shard.
"""

from __future__ import annotations

import logging
import math
import threading
import time
from typing import Callable, Dict, Optional, Set

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.comm.actors import (ClientManager, SelfMessageTimer,
                                   ServerManager)
from fedml_tpu.comm.message import Message
from fedml_tpu.comm.transport import Transport
from fedml_tpu.core.pytree import HostMirror, tree_weighted_mean
from fedml_tpu.core.sampling import sample_clients
from fedml_tpu.obs import telemetry

log = logging.getLogger(__name__)


class MsgType:
    """Message-type constants (parity: message_define.py:1-30)."""
    S2C_INIT = 1          # MSG_TYPE_S2C_INIT_CONFIG
    S2C_SYNC = 2          # MSG_TYPE_S2C_SYNC_MODEL_TO_CLIENT
    C2S_MODEL = 3         # MSG_TYPE_C2S_SEND_MODEL_TO_SERVER
    S2C_FINISH = 4        # shutdown signal (reference uses MPI Abort instead)
    ROUND_TIMEOUT = 5     # server self-message from the straggler timer
    C2S_HEARTBEAT = 6     # silo liveness beat (drives the FailureDetector)


class FailureDetector:
    """Heartbeat-driven silo health registry: ALIVE → SUSPECT → DEAD.

    The reference has no notion of silo health at all — a dead client is
    indistinguishable from a slow one and the barrier waits forever
    (FedAvgServerManager.py:51).  This detector is the standard
    timeout-hierarchy design: every message from a silo (heartbeat OR
    model upload) is a *beat*; a silo unheard for ``suspect_after_s`` is
    SUSPECT (still counted in the round barrier, but flagged), and one
    unheard for ``dead_after_s`` is DEAD.  Dead silos are excluded from
    the next round's expected quorum, so the drop policy stops re-paying
    the full round timeout for a silo that is known to be gone.

    DEAD is sticky until the silo is heard from again: the first beat
    from a declared-dead silo reports a *rejoin*, which the server
    answers with the current global model + round index so the silo can
    re-enter the federation at the next round's broadcast.

    ``clock`` is injectable for deterministic tests.
    """

    ALIVE = "alive"
    SUSPECT = "suspect"
    DEAD = "dead"

    def __init__(self, suspect_after_s: float = 2.0,
                 dead_after_s: float = 5.0,
                 clock: Callable[[], float] = time.monotonic):
        if dead_after_s < suspect_after_s:
            raise ValueError(
                f"dead_after_s ({dead_after_s}) must be >= suspect_after_s "
                f"({suspect_after_s})")
        self.suspect_after_s = suspect_after_s
        self.dead_after_s = dead_after_s
        self._clock = clock
        self._last_heard: Dict[int, float] = {}
        self._declared_dead: Set[int] = set()
        # health gauges refresh on every full states() sweep (each round's
        # broadcast and every straggler-timeout log both sweep)
        reg = telemetry.get_registry()
        self._gauges = {
            self.ALIVE: reg.gauge("fedml_failure_detector_alive_total"),
            self.SUSPECT: reg.gauge("fedml_failure_detector_suspect_total"),
            self.DEAD: reg.gauge("fedml_failure_detector_dead_total")}

    def register(self, silo: int) -> None:
        """Start the clock for a silo without marking a real beat (called
        at federation start so nobody is born dead)."""
        self._last_heard.setdefault(silo, self._clock())

    def beat(self, silo: int) -> bool:
        """Record a liveness beat.  Returns True when this beat REJOINS a
        silo previously declared dead."""
        rejoined = silo in self._declared_dead
        self._declared_dead.discard(silo)
        self._last_heard[silo] = self._clock()
        return rejoined

    def state(self, silo: int) -> str:
        if silo in self._declared_dead:
            return self.DEAD
        last = self._last_heard.get(silo)
        if last is None:
            return self.ALIVE  # never registered: benefit of the doubt
        quiet = self._clock() - last
        if quiet >= self.dead_after_s:
            self._declared_dead.add(silo)  # sticky until the next beat
            return self.DEAD
        if quiet >= self.suspect_after_s:
            return self.SUSPECT
        return self.ALIVE

    def states(self) -> Dict[int, str]:
        out = {silo: self.state(silo) for silo in sorted(self._last_heard)}
        for health, gauge in self._gauges.items():
            gauge.set(sum(1 for s in out.values() if s == health))
        return out

    def dead_silos(self) -> Set[int]:
        return {silo for silo, health in self.states().items()
                if health == self.DEAD}


# a silo-local trainer: (global_params, client_idx, round_idx) ->
# (new_params, num_samples).  Internally this is expected to be a jit'd
# local-SGD program (fedml_tpu.trainer.local_sgd) over the silo's shard.
SiloTrainFn = Callable[[object, int, int], tuple]


class FedAvgServerActor(ServerManager):
    """Rank-0 aggregator actor (reference FedAvgServerManager.py:18-95)."""

    def __init__(self, transport: Transport, init_params,
                 client_num_in_total: int, client_num_per_round: int,
                 num_rounds: int,
                 on_round_done: Optional[Callable[[int, object], None]] = None,
                 straggler_policy: str = "wait",
                 round_timeout_s: Optional[float] = None,
                 min_silo_frac: float = 0.5,
                 decode_upload: Optional[Callable] = None,
                 failure_detector: Optional[FailureDetector] = None,
                 checkpointer=None,
                 publish: Optional[Callable] = None,
                 extra_state: Optional[tuple] = None,
                 admission=None,
                 aggregate_fn: Optional[Callable] = None,
                 stream_agg=None,
                 encode_once: bool = True,
                 incremental_staging: bool = True,
                 perf=None,
                 health=None,
                 secagg=None,
                 journal=None,
                 faultline=None,
                 shard_wire=None,
                 server_opt=None,
                 controller=None,
                 degrade=None,
                 ingest=None):
        """Failure handling (SURVEY.md §5.3 — the reference has none: its
        barrier waits forever and its only exit is ``MPI.Abort``,
        server_manager.py:64):

        * ``straggler_policy="wait"`` — reference-parity strict barrier;
          with a timeout set it logs the missing silos and keeps waiting.
        * ``"drop"`` — after ``round_timeout_s``, aggregate the silos that
          DID report, provided at least ``min_silo_frac`` of the live
          cohort arrived (else keep waiting); stragglers' late uploads are
          discarded by the round tag.
        * ``"abort"`` — after the timeout, send FINISH to every silo and
          stop (the clean version of the reference's MPI abort).

        ``failure_detector``: when set, silo health (driven by heartbeats
        and uploads) feeds the round barrier — silos declared DEAD are
        excluded from the expected quorum at broadcast time (logged in
        ``dropped_silos``), so the drop policy closes rounds as soon as
        the live cohort reports instead of re-paying the full timeout
        every round.  A dead silo that is heard from again *rejoins*: it
        immediately receives the current global + round index and is
        re-included from the next broadcast.

        ``checkpointer``: a `fedml_tpu.utils.checkpoint.RoundCheckpointer`;
        when set, every completed round's (params, round_idx, accepted
        silos) is saved per its ``save_every`` gating, and ``start()``
        resumes from the latest checkpoint if one exists — a crashed and
        restarted server continues the federation instead of restarting
        it from round 0.

        ``publish``: serve-while-train hook — ``publish(host_params,
        round_idx)`` fires after every aggregation (and once on resume),
        so a `serve.registry.ModelRegistry` can hot-swap the federation's
        own global model live while rounds keep running.

        ``extra_state``: a ``(get_fn, set_fn)`` pair folding extra
        cross-round state into every round checkpoint: ``get_fn()``
        returns a FIXED-SHAPE host pytree saved beside params, and
        ``set_fn(tree)`` restores it on resume.  The cross-silo runner
        uses it to persist silo-side `ErrorFeedback` residuals, which
        are cross-round state the (params, round, rng) tuple silently
        dropped — a resumed --error_feedback run used to diverge from an
        uninterrupted one (tests/test_recovery.py pins bit-identity).

        ``admission``: a `fedml_tpu.robust.AdmissionPipeline`; when set,
        every upload is screened (fingerprint / finite / sample-count /
        norm-outlier) before it may aggregate.  A REJECTED upload still
        satisfies the round barrier (the silo reported; its payload is
        inadmissible) but carries weight 0, and its strike feeds the
        pipeline's `TrustTracker` — silos QUARANTINED there are excluded
        from the broadcast and the quorum exactly like
        FailureDetector-dead ones, and re-enter on probation when the
        quarantine expires.

        ``aggregate_fn``: a `fedml_tpu.robust.make_defended_aggregate`
        product ``fn(global_params, stacked, weights, round_idx)``.
        When set, the round's admitted uploads are stacked into the
        STATIC ``[cohort, ...]`` shape (missing/rejected slots hold the
        current global with weight 0) and the whole clip + Byzantine
        rule + noise + mean step runs as that one jit — no recompiles
        after round 1.  When None, the legacy exact
        ``tree_weighted_mean`` over the received list is used.

        ``encode_once``: broadcast via the transport's ``send_many`` —
        the model bytes serialize ONCE per round no matter how many
        silos are tasked (only the small per-silo header varies).  False
        restores the seed per-silo encode loop.

        ``perf``: a `fedml_tpu.obs.perf.PerfRecorder`; when set, every
        round writes one ledger line — phase wall-times
        (broadcast_serialize / staging / admission / straggler_wait /
        defended_aggregate / checkpoint / publish), wire-byte deltas,
        the round's peak host RSS, and the recompile-sentry verdict.
        The actor only drives the round lifecycle; the recorder's owner
        (the runner) registers hot jits and closes it.

        ``health``: a `fedml_tpu.obs.health.HealthAccumulator`; when
        set, every admitted upload folds its learning-health statistics
        at arrival on the SAME admission-accept seam the aggregation
        fold rides (update-norm Welford moments reusing the
        `AdmissionVerdict` norm, cosine alignment against the round's
        running mean direction, per-silo fairness counters), and the
        round close writes one ``health.jsonl`` line with the
        round-over-round global delta norm and the drift-alarm
        verdicts.  Under the edge topology the root also banks each
        edge frame's `Message.ARG_HEALTH` rollup.  The health path is
        ledgered as its own ``health`` perf phase.

        ``incremental_staging``: with an ``aggregate_fn`` set, each
        admitted upload is copied into its slot of a ``[cohort, ...]``
        host staging buffer AT ARRIVAL TIME — staging overlaps the
        straggler wait, so closing the round does only the H2D transfer
        + the defended jit instead of a serial O(cohort) ``np.stack``
        per leaf at the barrier.  The buffer is RELEASED at round close
        (reallocated next round), so stack-mode RSS returns to baseline
        between rounds instead of pinning the cohort watermark for the
        life of the federation.  False restores the seed
        stack-at-the-barrier path (bit-identical results either way;
        tests/test_wire.py pins the equivalence).

        ``secagg``: a `fedml_tpu.secure.protocol.SecAggServer` — the
        round becomes the live secure-aggregation protocol: the sync
        broadcast ships the masking parameters (``Message.ARG_SECAGG``),
        silos advertise DH public keys + Shamir share envelopes, the
        server relays one roster frame per silo, uploads arrive MASKED
        in the uint32 ring (screened by the ``kind="masked"`` admission
        pipeline PRE-mask-removal, then ring-folded at arrival — the
        O(model) streaming spine), and the barrier close runs an UNMASK
        phase: survivors reveal the shares that reconstruct uploaders'
        self-masks and dead silos' pairwise secrets, the sum dequantizes,
        and the post-unmask sum screen + sum-level clip/noise run before
        the global publishes.  The ledger gains ``mask_agreement`` and
        ``unmask`` phases.  Mutually exclusive with ``aggregate_fn`` /
        ``stream_agg`` / ``decode_upload`` — masked uploads have no
        plaintext to stack, stream, or decompress.

        ``stream_agg``: a `fedml_tpu.core.stream_agg.StreamingAggregator`
        — the O(model)-memory replacement for the ``[cohort, ...]``
        buffer entirely (``--agg_mode stream``).  Each admitted upload
        FOLDS into running state on the receive path (the ledger's
        ``fold`` phase) and the barrier-close runs one ``finalize``; no
        cohort-sized host buffer ever exists.  Mutually exclusive
        with ``aggregate_fn`` — the stack path stays behind
        ``--agg_mode stack`` for equivalence pinning (the ``mean``
        results are bit-identical; tests/test_stream_agg.py).

        ``journal``: a `fedml_tpu.utils.journal.RoundJournal` — crash
        consistency for the round IN FLIGHT (the checkpointer covers
        round boundaries).  Every report appends a crash-safe metadata
        record on the receive path, and on the resumable path (the
        streaming MEAN fold) the fold state snapshots atomically every
        ``snapshot_every`` folds — so a server killed mid-round resumes
        the SAME round, re-tasks only the silos whose uploads were not
        durably folded, and finishes with a global bit-identical to the
        uncrashed run (deterministic silos re-train the same bytes; the
        sequential fold preserves order; pinned in
        tests/test_crash_recovery.py).  Secagg rounds journal as
        ``resumable=False`` — resuming a half-masked ring fold would
        require self-mask shares nobody agreed to reveal — and recovery
        restarts them loudly from the boundary with the global
        unchanged; reservoir (order-statistic) stream rounds are
        likewise abort-only.  Requires ``stream_agg`` or ``secagg``:
        the stack path has no incremental fold state to snapshot.

        ``shard_wire``: a `fedml_tpu.shard_spine.ShardSpine` — the
        sharded global-model round (``--model_shards S``).  The
        broadcast ships S per-shard slice frames per silo (ONE
        encode-once `SharedPayload` per shard for the whole cohort;
        shard 0 carries the plan spec + per-silo params), uploads
        arrive as S slice frames screened PER SHARD by the spine's
        `ShardAdmission` (structural fingerprint against the shard
        template at arrival; the combined-norm outlier screen at silo
        completion), and an admitted silo's slices fold per shard into
        the spine's `ShardedStreamingAggregator` — ``stream_agg`` must
        BE that aggregator.  One bad slice rejects the whole silo at
        weight 0 before anything folds (the replicated rejection
        granularity).  The barrier counts SILOS, not slices: a silo
        satisfies it when its last slice completes admission (or its
        first slice fails it).  Requires ``stream_agg``; mutually
        exclusive with ``secagg`` (a masked ring word cannot be
        re-sliced), ``aggregate_fn`` (the stack path is whole-model by
        construction), and ``decode_upload`` (the delta codec
        reconstructs against the whole global).

        ``faultline``: a `fedml_tpu.robust.faultline.Faultline` — the
        seeded process-kill injector (test/soak only).  The round loop
        is threaded with the named crash points
        (`faultline.CRASH_POINTS`); an armed faultline raises
        `ActorKilled` (a BaseException — no receive-path guard survives
        it) out of the event loop with zero cleanup, emulating kill -9.

        ``ingest``: a `fedml_tpu.comm.ingest.IngestPipeline`
        (``--ingest_pipeline``) — the zero-copy pipelined receive path
        (ROADMAP item 4).  The transport thread only validates the
        envelope and enqueues; a single-consumer fold worker per shard
        runs decode → screen → fold, staging float payloads through the
        pipeline's pre-pinned arenas (one ``device_put`` per shard, the
        fused admission reduction) when attached.  Fold order per shard
        is the worker queue's FIFO — the deterministic arrival order —
        so the pipelined global is bit-identical to the inline path.
        Queue overflow dead-letters the frame as a NETWORK fault
        (``fedml_comm_dead_letter_total{reason="ingest_overflow"}``);
        the silo is simply not heard from this round — never struck.
        Mutually exclusive with ``faultline``: `ActorKilled` must
        escape the transport event loop to reach the harness, and a
        fold worker thread has no path there.
        """
        super().__init__(0, transport)
        if straggler_policy not in ("wait", "drop", "abort"):
            raise ValueError(f"unknown straggler_policy {straggler_policy!r}")
        self.params = init_params
        self.client_num_in_total = client_num_in_total
        self.client_num_per_round = client_num_per_round
        self.num_rounds = num_rounds
        self.round_idx = 0
        self.on_round_done = on_round_done
        self.straggler_policy = straggler_policy
        self.round_timeout_s = round_timeout_s
        self.min_silo_frac = min_silo_frac
        self.aborted = False
        # optional wire decompression: decode_upload(payload, global_params)
        # -> params (comm/compress.py rides here — uploads compressed, the
        # down-link broadcast stays exact)
        self.decode_upload = decode_upload
        self.failure_detector = failure_detector
        self.checkpointer = checkpointer
        self.publish = publish
        self.extra_state = extra_state
        self.admission = admission
        if aggregate_fn is not None and stream_agg is not None:
            raise ValueError("aggregate_fn (stack mode) and stream_agg "
                             "(stream mode) are mutually exclusive; pick "
                             "one --agg_mode")
        self.aggregate_fn = aggregate_fn
        self.stream_agg = stream_agg
        self.secagg = secagg
        if secagg is not None and (aggregate_fn is not None
                                   or stream_agg is not None
                                   or decode_upload is not None):
            raise ValueError(
                "secagg is mutually exclusive with aggregate_fn/"
                "stream_agg/decode_upload: masked uploads have no "
                "plaintext to stack, stream, or decompress")
        # secagg round stage: None | "agreement" | "upload" | "unmask"
        self._secagg_stage: Optional[str] = None
        self._secagg_quorum = 0
        self._secagg_unmask_laps = 0
        self._secagg_agreement_laps = 0
        self.encode_once = encode_once
        self.incremental_staging = incremental_staging
        self.perf = perf
        self.health = health
        if journal is not None and stream_agg is None and secagg is None:
            raise ValueError(
                "journal (crash consistency) rides the streaming-fold "
                "receive path: pass --agg_mode stream (or --secagg); the "
                "stack path has no incremental fold state to snapshot")
        self.journal = journal
        self.faultline = faultline
        # server_opt: a fedml_tpu.server_opt.ServerOptimizer — the round's
        # finalize output becomes a pseudo-gradient Δ = global − finalize
        # and the optimizer's one jitted step applies it (None keeps the
        # pre-seam assignment `self.params = finalize(...)` byte-for-byte)
        if server_opt is not None and secagg is not None:
            raise ValueError(
                "server_opt and secagg are mutually exclusive: the "
                "masked-sum finalize yields a plain mean by protocol "
                "construction; there is no seam to re-step it through "
                "a server optimizer without unmasking intermediate state")
        self.server_opt = server_opt
        # controller: a fedml_tpu.server_opt.AdaptiveController — consulted
        # once per round close on the health observatory's verdict
        if controller is not None and health is None:
            raise ValueError(
                "controller (--adaptive) requires the health observatory "
                "(--health): its decisions are a pure function of the "
                "per-round drift-alarm line")
        self.controller = controller
        # degrade: a fedml_tpu.robust.degrade.ReliabilityTracker — the
        # sustained-degradation spine (ISSUE 19): adaptive straggler
        # deadlines from observed per-silo completion quantiles,
        # min_quorum closure with correlated-partition holds, and
        # network-vs-payload fault attribution (deadline drops and dead
        # letters NEVER strike trust)
        if degrade is not None and degrade.adaptive_deadline \
                and round_timeout_s is None:
            raise ValueError(
                "adaptive_deadline requires round_timeout_s: the static "
                "timeout is the deadline's ceiling (and the cold-start "
                "fallback before the tracker warms)")
        self.degrade = degrade
        # the round's armed deadline (seconds) — derived ONCE per round
        # at broadcast from the tracker's ledgered history, so a resumed
        # round re-derives the same value (never recomputed on re-arms)
        self._round_deadline_s: Optional[float] = None
        self.shard_wire = shard_wire
        if shard_wire is not None:
            if secagg is not None:
                raise ValueError(
                    "shard_wire (--model_shards) and secagg are mutually "
                    "exclusive: a pairwise-masked uint32 ring word "
                    "cannot be re-sliced per shard without breaking "
                    "mask cancellation")
            if aggregate_fn is not None or decode_upload is not None:
                raise ValueError(
                    "shard_wire (--model_shards) requires the streaming "
                    "fold: the stack path and the wire-compression "
                    "decoder are whole-model by construction")
            if stream_agg is None:
                raise ValueError(
                    "shard_wire without its sharded stream_agg: pass "
                    "the spine's ShardedStreamingAggregator as "
                    "stream_agg (they are one subsystem)")
            if shard_wire.admission is None:
                raise ValueError(
                    "shard_wire without its ShardAdmission: the "
                    "per-shard structural screens ARE the sharded wire "
                    "protocol (slices route by screened structure) — "
                    "build the spine with admission_on=True")
        if ingest is not None and faultline is not None:
            raise ValueError(
                "--ingest_pipeline and --faultline are mutually "
                "exclusive: ActorKilled must escape the transport event "
                "loop to reach the harness, and an ingest fold worker "
                "thread has no path there")
        self.ingest = ingest
        # silos whose frames sit in the ingest queue, not yet folded:
        # the transport-thread duplicate guard must see them (the
        # authoritative `_received` check re-runs on the worker)
        self._ingest_inflight: Set[int] = set()
        # serializes the worker-side upload body against the timeout /
        # round-close paths (RLock: a worker-side barrier close calls
        # back into guarded methods)
        self._ingest_lock = threading.RLock()
        # a mid-round recovery found by start(): consumed by the next
        # _broadcast of the matching round
        self._pending_resume = None
        self.dropped_silos: Dict[int, list] = {}  # round -> missing silo ids
        self._received: Dict[int, tuple] = {}
        # per-round host mirror of self.params: the broadcast, checkpoint,
        # staging fill, and publish paths all read the SAME device→host
        # transfer instead of re-running jax.tree.map(np.asarray, ...)
        # up to 3x per round
        self._host_mirror = HostMirror()
        # incremental cohort staging (see __init__ docstring): allocated
        # once at the first admitted upload, slot i-1 belongs to silo i
        self._staging = None
        self._staging_leaves: Optional[list] = None
        self._staging_def = None
        self._staged: Set[int] = set()
        self._staged_seen = 0  # lifetime staged uploads (buffer is
        #                        released each round close — see
        #                        _complete_round — so this is the only
        #                        cross-round evidence staging ran)
        self._num_silos = 0  # silos contacted this round (= sampled cohort)
        self._expected: Set[int] = set()  # silos the barrier waits on
        self._timer = SelfMessageTimer()
        self._finished = False
        # silo ids whose uploads were aggregated last round, sent with the
        # next sync so silos can settle deferred error-feedback residuals
        # (a dropped upload must carry its FULL delta forward)
        self._last_accepted: Optional[np.ndarray] = None
        # round observability: duration / tail-wait / quorum histograms
        # (null no-ops when telemetry is disabled) + the per-round trace
        # span broadcast→aggregate child spans hang off
        reg = telemetry.get_registry()
        self._h_round = reg.histogram("fedml_round_duration_seconds")
        self._h_straggler = reg.histogram(
            "fedml_round_straggler_wait_seconds")
        self._h_quorum = reg.histogram(
            "fedml_round_quorum_size_total",
            buckets=(1, 2, 4, 8, 16, 32, 64, 128))
        self._round_t0: Optional[float] = None
        self._first_upload_t: Optional[float] = None
        self._round_span = None
        self._g_staged = reg.gauge("fedml_wire_staged_uploads_total")

    def register_handlers(self) -> None:
        self.register_handler(MsgType.C2S_MODEL, self._on_model)
        self.register_handler(MsgType.ROUND_TIMEOUT, self._on_timeout)
        self.register_handler(MsgType.C2S_HEARTBEAT, self._on_heartbeat)
        if self.secagg is not None:
            from fedml_tpu.secure.protocol import (MSG_SECAGG_ADVERT,
                                                   MSG_SECAGG_SHARES)
            self.register_handler(MSG_SECAGG_ADVERT, self._on_secagg_advert)
            self.register_handler(MSG_SECAGG_SHARES, self._on_secagg_shares)

    # -- round logic ---------------------------------------------------------
    def start(self) -> None:
        """Broadcast initial config (send_init_msg, FedAvgServerManager.py:31-39).

        With a ``checkpointer`` attached, a server that finds a saved
        round on disk resumes from it: params, round index, and the
        error-feedback ack all restore, and the broadcast picks up at the
        round after the last completed one."""
        if self.checkpointer is not None:
            step = self.checkpointer.latest_round()
            if step is not None:
                try:
                    state = self.checkpointer.restore(
                        step, like=self._checkpoint_state(step))
                except ValueError:
                    # schema drift: the on-disk checkpoint and the current
                    # config disagree about the "extra" leaf (a pre-EF
                    # checkpoint resumed with --error_feedback on, or the
                    # reverse).  Restore untemplated and take what's
                    # there — resuming beats crashing, and the extra
                    # guard below only applies state that exists.
                    log.warning("checkpoint %d does not match the current "
                                "state schema; restoring untemplated",
                                step)
                    state = self.checkpointer.restore(step)
                self.params = state["params"]
                self.round_idx = int(np.asarray(state["round_idx"])) + 1
                mask = np.asarray(state["accepted_mask"])
                # possibly-empty ARRAY, mirroring _complete_round: a
                # crash right after an all-rejected round must resume
                # broadcasting an EMPTY ack, not None — EF residual
                # settlement reads None as "assume accepted" and would
                # drop the rejected uploads' deltas from the carry
                self._last_accepted = (
                    np.flatnonzero(mask) + 1).astype(np.int32)
                if self.extra_state is not None and "extra" in state:
                    self.extra_state[1](state["extra"])
                if self.publish is not None:
                    self.publish(self._host_params(), self.round_idx - 1)
                log.info("resumed from checkpoint: continuing at round %d "
                         "of %d", self.round_idx, self.num_rounds)
        if self.journal is not None:
            # mid-round recovery: the journal may hold a round the crash
            # interrupted BETWEEN the checkpoint boundary and its
            # round_end — restore its durable fold prefix (or abandon it
            # loudly when the mode/round/global forbid resuming)
            self._pending_resume = self._journal_recovery()
        if self.round_idx >= self.num_rounds:
            # the federation already completed on disk: just dismiss silos
            cohort = len(sample_clients(0, self.client_num_in_total,
                                        self.client_num_per_round))
            for silo in range(1, cohort + 1):
                self.send(MsgType.S2C_FINISH, silo)
            self.finish()
            return
        self._broadcast(MsgType.S2C_INIT)

    def _journal_mode(self) -> str:
        """The journal's round-mode tag for THIS configuration.
        Recovery refuses a journal written under a different one
        (plain <-> sharded, a different shard count, secagg) instead of
        unflattening foreign fold state into the wrong slots."""
        if self.secagg is not None:
            return "secagg"
        # a non-plain server optimizer tags the mode: resuming its fold
        # into a run that would finalize through a DIFFERENT server step
        # (or none) silently changes the update the replay applies
        srvopt = ""
        if self.server_opt is not None and self.server_opt.name != "plain":
            srvopt = f"+srvopt={self.server_opt.name}"
        if self.shard_wire is not None:
            return self.shard_wire.journal_mode() + srvopt
        return f"stream_{self.stream_agg.method}{srvopt}"

    def _journal_recovery(self):
        """Inspect the journal for a round the crash left mid-flight.
        Returns a `utils.journal.Recovery` ONLY when resuming is safe:
        the open round is exactly the one the checkpoint boundary says
        comes next, its mode is resumable (streaming mean — never a
        half-masked secagg fold or a reservoir draw stream), its
        opening-global crc matches the restored global (folding against
        a different clip reference would mis-aggregate silently), and a
        durable snapshot exists.  Everything else is ABANDONED loudly:
        the round restarts from the boundary with the global unchanged —
        lost work, never a mis-aggregated global."""
        from fedml_tpu.utils.journal import tree_crc
        rec = self.journal.recover()
        if rec is None:
            return None
        if rec.round_idx != self.round_idx:
            log.warning(
                "journal holds mid-flight round %d but the checkpoint "
                "boundary resumes at round %d (checkpoint cadence gap); "
                "abandoning the journal round — rounds past the last "
                "checkpoint re-run from the boundary (set "
                "--checkpoint_every 1 for mid-round recovery)",
                rec.round_idx, self.round_idx)
            self.journal.abandon(rec.round_idx, "round mismatch")
            return None
        if rec.mode != self._journal_mode():
            log.error(
                "round %d journal was written in mode %r but this run "
                "aggregates in mode %r (the --agg_mode/--model_shards/"
                "--secagg configuration changed across the restart); "
                "restoring its fold state would land in the wrong "
                "layout — restarting the round from the boundary, "
                "global unchanged", rec.round_idx, rec.mode,
                self._journal_mode())
            self.journal.abandon(rec.round_idx,
                                 f"mode mismatch {rec.mode}")
            return None
        if not rec.resumable:
            log.error(
                "round %d crashed mid-flight in non-resumable mode %r "
                "(secagg rounds are abort-only: resuming a half-masked "
                "fold would require shares nobody agreed to reveal; "
                "reservoir rules have no durable draw stream) — "
                "restarting the round from the boundary, global "
                "unchanged", rec.round_idx, rec.mode)
            self.journal.abandon(rec.round_idx,
                                 f"non-resumable mode {rec.mode}")
            return None
        if rec.global_crc is not None \
                and rec.global_crc != tree_crc(self._host_params()):
            log.error(
                "round %d journal opened against a DIFFERENT global than "
                "the restored checkpoint (crc mismatch); refusing to "
                "resume the fold — restarting from the boundary",
                rec.round_idx)
            self.journal.abandon(rec.round_idx, "global crc mismatch")
            return None
        if rec.state is None or not rec.folded:
            log.warning("round %d crashed before any durable fold "
                        "snapshot; re-tasking the full cohort from the "
                        "boundary", rec.round_idx)
            self.journal.abandon(rec.round_idx, "no durable snapshot")
            return None
        log.warning("round %d: resuming MID-ROUND from the journal — %d "
                    "upload(s) durably folded (silos %s) will not be "
                    "re-tasked", rec.round_idx, len(rec.folded),
                    [s for s, _, _ in rec.folded])
        return rec

    def _sampled(self) -> np.ndarray:
        # deterministic per-round sampling, parity with
        # FedAVGAggregator.client_sampling:89-97 (np.random.seed(round_idx))
        per = self.client_num_per_round
        if self.controller is not None:
            # the adaptive cohort lever, capped at the CONFIGURED cohort:
            # the local backend constructs exactly client_num_per_round
            # silo actors, so cross_silo can never task a wider cohort
            # than exists (the controller ledgers the clamp; cross_device
            # samples from the full population and genuinely widens)
            per = min(max(1, self.controller.cohort),
                      self.client_num_per_round)
        return sample_clients(self.round_idx, self.client_num_in_total,
                              per)

    def _host_params(self):
        """The round's host copy of the global, transferred device→host
        at most once per params value (broadcast, checkpoint, staging
        fill, and publish all share it)."""
        return self._host_mirror.get(self.params)

    def _checkpoint_state(self, round_idx: int,
                          host_params=None) -> Dict[str, object]:
        """Round-state pytree saved after round ``round_idx`` completes.
        Every leaf has a restart-independent shape (the accepted-silo set
        rides as a fixed-length mask, not a variable-length id list) so
        the same structure doubles as the orbax restore template.
        ``host_params``: an already-materialized host copy of the globals
        (``_complete_round`` shares one copy between checkpoint and
        publish instead of device→host transferring twice)."""
        cohort = len(sample_clients(0, self.client_num_in_total,
                                    self.client_num_per_round))
        mask = np.zeros(cohort, np.int8)
        if self._last_accepted is not None:
            mask[np.asarray(self._last_accepted) - 1] = 1
        if host_params is None:
            host_params = self._host_params()
        out = {"params": host_params,
               "round_idx": np.asarray(round_idx, np.int64),
               "accepted_mask": mask}
        if self.extra_state is not None:
            out["extra"] = self.extra_state[0]()
        return out

    def _broadcast(self, msg_type) -> None:
        ids = self._sampled()
        # sample_clients caps the cohort at client_num_in_total, so the
        # receive barrier must track the actual cohort size, not the config
        self._num_silos = len(ids)
        cohort = set(range(1, self._num_silos + 1))
        # mid-round recovery (start() banked it): the durably-folded
        # silos are NOT re-tasked — their uploads already live in the
        # restored fold state — and they satisfy the barrier immediately
        resume = None
        if self._pending_resume is not None \
                and self._pending_resume.round_idx == self.round_idx:
            resume = self._pending_resume
        self._pending_resume = None
        folded = ({int(s): float(w) for s, w, _ in resume.folded}
                  if resume is not None else {})
        dead: Set[int] = set()
        if self.failure_detector is not None:
            for silo in cohort:
                self.failure_detector.register(silo)
            dead = self.failure_detector.dead_silos() & cohort
        # quarantined silos (TrustTracker strikes) are excluded exactly
        # like dead ones: weight 0, never waited on.  The sweep also
        # transitions expired quarantines to probation — a probation
        # silo is tasked again from THIS broadcast.  On the sharded
        # wire the spine's ShardAdmission owns the (same-protocol)
        # trust ledger.
        trust = (self.admission.trust if self.admission is not None
                 else self.shard_wire.admission.trust
                 if self.shard_wire is not None
                 and self.shard_wire.admission is not None else None)
        if trust is not None:
            dead = dead | trust.quarantined(self.round_idx, cohort)
        if dead == cohort:
            # every silo dead/quarantined: fall back to expecting the
            # full cohort (the classic timeout path), so a rejoin can
            # still revive the federation instead of the barrier
            # closing on nothing
            dead = set()
        if self.secagg is not None and len(cohort - dead) < 2:
            # runtime attrition left fewer than 2 live silos: a 1-member
            # "masked sum" IS that silo's update, so the group cannot
            # mask.  Clear the dead set like the all-dead fallback so
            # the masked sync reaches EVERYONE (the rejoin warm-up sync
            # carries no masking parameters, so a mid-round rejoin could
            # never advertise otherwise); truly-gone silos stall the
            # agreement, which abandons the round after its retry cap
            # instead of wedging.
            log.warning("round %d: fewer than 2 live silos for the "
                        "masking group; tasking the full cohort and "
                        "waiting for returns", self.round_idx)
            dead = set()
        # silos already known dead are dropped AT BROADCAST: they are
        # logged for this round immediately and the barrier never waits
        # on them (the quorum "shrinks" instead of re-paying the timeout)
        self._expected = cohort - dead
        if dead:
            log.info("round %d: excluding dead/quarantined silos %s from "
                     "the quorum", self.round_idx, sorted(dead))
            self.dropped_silos.setdefault(self.round_idx, []).extend(
                sorted(dead))
        self._round_t0 = time.monotonic()
        self._first_upload_t = None
        self._round_deadline_s = None
        if self.degrade is not None:
            self.degrade.round_start(self.round_idx, self._expected)
            # the deadline derives from history BEFORE any of this
            # round's arrivals (including journal-restored folds below):
            # the crashed process armed from exactly this state, so the
            # resumed round re-derives the same value
            self._round_deadline_s = self.degrade.deadline_s(
                self._expected, self.round_timeout_s)
            if resume is not None:
                # replay the restored folds' completion latencies (they
                # ride each accept record's extra) so the NEXT round's
                # deadline sees the same history the crashed process did
                for silo, _w, extra in resume.folded:
                    lat = (extra or {}).get("lat_s")
                    if lat is not None:
                        self.degrade.observe_completion(int(silo),
                                                        float(lat))
                    self.degrade.note_accept(int(silo))
        if self.perf is not None:
            # the ledger round opens HERE: broadcast serialize is its
            # first phase, round_end closes it after publish
            self.perf.round_start(self.round_idx)
        if self._tracer is not None:
            # one trace per round, rooted here: broadcast/recv/train/
            # upload/aggregate all stitch under this trace id
            self._round_span = self._tracer.start_span(
                "round", parent=None, node=self.node_id,
                trace_id=self._tracer.new_trace_id(
                    f"round{self.round_idx}"),
                round=self.round_idx)
        if self.stream_agg is not None:
            # stream mode: open the fold state against the new global
            # (the round's clip reference)
            self.stream_agg.reset(self.params)
            if resume is not None:
                # continue the crashed round's fold exactly where the
                # last durable snapshot left it — the sequential mean
                # fold is order-preserving, so prefix + re-trained
                # suffix equals the uncrashed reduction bit for bit
                with self._perf_phase("journal"):
                    self.stream_agg.load_state_dict(resume.state)
                    # note_resume re-arms the fresh journal instance's
                    # round state (fold prefix included), so the resumed
                    # round keeps snapshotting on its cadence
                    self.journal.note_resume(self.round_idx, resume.folded,
                                             global_crc=resume.global_crc)
        host_params = self._host_params()
        if self.shard_wire is not None:
            # per-round spine state: the admission's f64 reference
            # slices + cleared upload holds (works on the resume path
            # too — re-tasked silos' slices screen against this round's
            # reference like any other)
            with self._perf_phase("admission"):
                self.shard_wire.round_start(host_params)
        if self.ingest is not None and self.ingest.has_arenas:
            # stage the round's screen reference into each shard arena
            # (one transfer per arena per round — the _ref_cache
            # discipline, on the device)
            with self._perf_phase("admission"):
                if self.shard_wire is not None:
                    refs = list(
                        self.shard_wire.broadcast_slices(host_params))
                else:
                    refs = [host_params]
                self.ingest.round_start(refs)
        if self.journal is not None and resume is None:
            from fedml_tpu.utils.journal import tree_crc
            mode = self._journal_mode()
            resumable = (self.secagg is None
                         and self.stream_agg.method == "mean")
            with self._perf_phase("journal"):
                self.journal.round_start(
                    self.round_idx, mode=mode, resumable=resumable,
                    global_crc=tree_crc(host_params),
                    expected=sorted(self._expected))
        if self.health is not None:
            # the health round opens against the SAME host mirror the
            # broadcast ships — no extra device→host transfer; silos
            # excluded at broadcast (dead/quarantined) tick their
            # fairness counters without ever reaching an upload
            with self._perf_phase("health"):
                self.health.round_start(self.round_idx, host_params,
                                        expected=sorted(self._expected),
                                        excluded=sorted(dead))
        extra = ({} if self._last_accepted is None
                 else {Message.ARG_ACCEPTED: self._last_accepted})
        if self.secagg is not None:
            # open the mask-agreement phase: the sync frame carries the
            # round's masking parameters (group / threshold / clip /
            # weight normalizer) so silos need zero secagg configuration
            # (the <2-live-silos fallback above guarantees the group
            # size here)
            with self._perf_phase("mask_agreement"):
                self.secagg.round_start(self.round_idx,
                                        sorted(self._expected))
                self._secagg_stage = "agreement"
                self._secagg_agreement_laps = 0
                extra[Message.ARG_SECAGG] = self.secagg.sync_info()
        with self._span("broadcast", parent=self._round_span,
                        round=self.round_idx), \
                self._perf_phase("broadcast_serialize"):
            if self.shard_wire is not None:
                # per-shard fan-out: S encode-once SharedPayloads for
                # the whole cohort (one serialization PER SHARD, never
                # per receiver).  Shard 0's frames carry the round
                # metadata, the plan spec, and each silo's client
                # assignment; the other shards ship only their slice.
                receivers = sorted(
                    silo for silo in cohort
                    if silo not in dead and silo not in folded)
                per_silo = {
                    silo: {Message.ARG_CLIENT_INDEX:
                           int(ids[silo - 1])}
                    for silo in receivers}
                n_shards = self.shard_wire.num_shards
                for s, slice_s in enumerate(
                        self.shard_wire.broadcast_slices(host_params)):
                    shared = {Message.ARG_MODEL_PARAMS: slice_s,
                              Message.ARG_ROUND: self.round_idx,
                              Message.ARG_SHARD: s,
                              Message.ARG_SHARD_COUNT: n_shards}
                    if s == 0:
                        shared.update(extra)
                        shared[Message.ARG_SHARD_SPEC] = \
                            self.shard_wire.spec()
                    self.send_many(
                        msg_type, receivers, shared_params=shared,
                        per_receiver_params=(per_silo if s == 0
                                             else None))
            elif self.encode_once:
                # one payload serialization for the whole cohort: only
                # the per-silo client assignment varies per frame
                per_silo = {
                    silo: {Message.ARG_CLIENT_INDEX: int(client_idx)}
                    for silo, client_idx in enumerate(ids, start=1)
                    if silo not in dead and silo not in folded}
                self.send_many(
                    msg_type, sorted(per_silo),
                    shared_params={Message.ARG_MODEL_PARAMS: host_params,
                                   Message.ARG_ROUND: self.round_idx,
                                   **extra},
                    per_receiver_params=per_silo)
            else:
                # seed path (wire_bench baseline): N full encodes
                for silo, client_idx in enumerate(ids, start=1):
                    if silo in dead or silo in folded:
                        continue
                    self.send(msg_type, silo,
                              **{Message.ARG_MODEL_PARAMS: host_params,
                                 Message.ARG_CLIENT_INDEX: int(client_idx),
                                 Message.ARG_ROUND: self.round_idx, **extra})
        if folded:
            # the restored uploads satisfy the barrier like live reports
            # (their bytes are already in the fold); a fully-durable
            # round closes right here — the crash cost the federation
            # nothing but the restart
            for silo, weight in folded.items():
                self._received[silo] = (self._STAGED, weight)
            if self._barrier_met():
                self._complete_round()
                return
        self._arm_timer()

    def _barrier_met(self) -> bool:
        if self._expected:
            return self._expected <= set(self._received)
        return len(self._received) >= self._num_silos

    # -- straggler timer ----------------------------------------------------
    def _effective_timeout_s(self) -> Optional[float]:
        """The round's armed deadline: the tracker's adaptive value
        (derived once at broadcast) when degrade is on, else the static
        ``round_timeout_s``."""
        if self._round_deadline_s is not None:
            return self._round_deadline_s
        return self.round_timeout_s

    def _arm_timer(self) -> None:
        timeout = self._effective_timeout_s()
        if timeout is None:
            return
        round_at_arm = self.round_idx
        # fire only ENQUEUES a self-message; all policy logic runs on the
        # transport's event loop, so handler state stays single-threaded
        # (SURVEY.md §5.2)
        self._timer.arm(
            timeout,
            lambda: self.send(MsgType.ROUND_TIMEOUT, 0,
                              **{Message.ARG_ROUND: round_at_arm}))

    def _cancel_timer(self, join: bool = False) -> None:
        self._timer.cancel(join=join)

    def _on_timeout(self, msg: Message) -> None:
        if self.ingest is not None:
            # frames already off the wire but still queued are NOT
            # stragglers: drain the pipeline before judging the barrier
            # (a queued fold may close the round right here — then the
            # stale-round guard below sees the advanced round and bails)
            self.ingest.drain()
        with self._ingest_lock:
            self._on_timeout_locked(msg)

    def _on_timeout_locked(self, msg: Message) -> None:
        if msg.get(Message.ARG_ROUND) != self.round_idx:
            return  # stale timer from an already-completed round
        if self._secagg_stage == "agreement":
            self._secagg_agreement_timeout()
            return
        if self._secagg_stage == "unmask":
            self._secagg_unmask_timeout()
            return
        missing = sorted(self._expected - set(self._received))
        if not missing:
            return
        if self.failure_detector is not None:
            states = self.failure_detector.states()
            log.warning("round %d: silo health %s", self.round_idx,
                        {s: states.get(s, "?") for s in missing})
        log.warning("round %d: silos %s have not reported after %.1fs "
                    "(policy=%s)", self.round_idx, missing,
                    self.round_timeout_s, self.straggler_policy)
        if self.straggler_policy == "abort":
            self.aborted = True
            for silo in range(1, self._num_silos + 1):
                self.send(MsgType.S2C_FINISH, silo)
            self.finish()
            return
        # quorum over the EXPECTED (live) cohort: dead-excluded silos
        # neither count toward nor against it
        quorum = max(1, math.ceil(self.min_silo_frac * len(self._expected)))
        if self.degrade is not None and self.straggler_policy == "drop":
            # degrade spine (ISSUE 19): --min_quorum may RAISE the close
            # threshold (never lower it below min_silo_frac's), and the
            # tracker adjudicates close/hold/abandon with partition
            # evidence (dead-letters this round, detector states)
            floor = self.degrade.quorum_for(len(self._expected))
            if floor is not None:
                quorum = max(quorum, floor)
            verdict = self.degrade.assess_timeout(
                self.round_idx, self._expected, set(self._received), quorum,
                detector_states=(self.failure_detector.states()
                                 if self.failure_detector is not None
                                 else None))
            log.warning("round %d: degrade verdict %s", self.round_idx,
                        verdict.as_dict())
            if verdict.action == "hold":
                # correlated miss with network evidence: a partition, not
                # a mass failure — hold the round (global unchanged) and
                # give the partition a chance to heal before folding a
                # minority view into the global
                self._arm_timer()
                return
            if verdict.action == "abandon":
                self._abandon_partitioned_round(missing, verdict)
                return
            if verdict.action == "close":
                # the dropped silos are HONEST until payload evidence
                # says otherwise: debt accrues (priority re-task next
                # round), the fault ledger books a network entry, and
                # TrustTracker is never touched from here
                for silo in missing:
                    self.degrade.note_drop(silo)
                self.dropped_silos.setdefault(self.round_idx, []).extend(
                    missing)
                self._complete_round()
                return
            self._arm_timer()  # below quorum: keep waiting
            return
        if self.straggler_policy == "drop" and len(self._received) >= quorum:
            self.dropped_silos.setdefault(self.round_idx, []).extend(missing)
            self._complete_round()
            return
        self._arm_timer()  # wait (or drop below quorum): keep waiting

    def _abandon_partitioned_round(self, missing, verdict) -> None:
        """The suspected partition outlived its hold budget: abandon the
        round LOUDLY with the global unchanged (the secagg-abandon
        pattern) plus an explicit journal abandon record, so the resume
        path never re-folds the minority view."""
        log.error("round %d: abandoning after %d partition holds "
                  "(missing=%s; %s); the global model is unchanged",
                  self.round_idx, verdict.holds, missing, verdict.reason)
        self._cancel_timer()
        self.dropped_silos.setdefault(self.round_idx, []).extend(missing)
        self._received.clear()
        self._last_accepted = np.asarray([], np.int32)
        if self.journal is not None:
            with self._perf_phase("journal"):
                self.journal.abandon(self.round_idx,
                                     "partition: " + verdict.reason)
        self._finish_round(0)

    # -- secure aggregation (secure/protocol.py) -----------------------------
    def _on_secagg_advert(self, msg: Message) -> None:
        """Mask-agreement phase: bank a silo's pk + share envelopes;
        when the whole expected group advertised, relay the rosters."""
        self._beat(msg.sender_id)
        if msg.get(Message.ARG_ROUND) != self.round_idx \
                or self._secagg_stage != "agreement":
            log.info("discarding stale/late secagg advert from silo %d",
                     msg.sender_id)
            return
        with self._perf_phase("mask_agreement"):
            complete = self.secagg.note_advert(msg.sender_id,
                                               msg.get(Message.ARG_SECAGG))
        if complete:
            self._send_rosters()

    def _send_rosters(self, subset=None) -> None:
        """Fix the round's masking roster and fan the roster frames out
        (encode-once: the pks repeat, only each silo's inbound share
        envelope differs).  Silos that never advertised fall out of the
        roster AND the barrier — they are this round's dropouts."""
        from fedml_tpu.secure.protocol import MSG_SECAGG_ROSTER, SecAggError
        with self._perf_phase("mask_agreement"):
            try:
                rosters = self.secagg.flush_roster(subset)
            except SecAggError as e:
                # below the share threshold: a roster this small could
                # never unmask — keep waiting for more adverts
                log.warning("round %d: cannot fix secagg roster yet (%s)",
                            self.round_idx, e)
                self._arm_timer()
                return
            self._secagg_stage = "upload"
            lost = self._expected - set(rosters)
            if lost:
                log.warning("round %d: silos %s never advertised; dropped "
                            "from the masking roster and the barrier",
                            self.round_idx, sorted(lost))
                self.dropped_silos.setdefault(self.round_idx, []).extend(
                    sorted(lost))
                self._expected = self._expected - lost
            per = {silo: {Message.ARG_SECAGG: payload}
                   for silo, payload in rosters.items()}
            self.send_many(MSG_SECAGG_ROSTER, sorted(per),
                           shared_params={Message.ARG_ROUND: self.round_idx},
                           per_receiver_params=per)
        self._arm_timer()

    def _secagg_agreement_timeout(self) -> None:
        advertised = self.secagg.advertised()
        missing = sorted(self._expected - advertised)
        if not missing:
            return  # roster flush is already in flight
        log.warning("round %d: silos %s have not advertised after %.1fs "
                    "(policy=%s)", self.round_idx, missing,
                    self.round_timeout_s, self.straggler_policy)
        if self.straggler_policy == "abort":
            self.aborted = True
            for silo in range(1, self._num_silos + 1):
                self.send(MsgType.S2C_FINISH, silo)
            self.finish()
            return
        quorum = max(1, math.ceil(self.min_silo_frac * len(self._expected)))
        if self.straggler_policy == "drop" and len(advertised) >= quorum:
            self._send_rosters(subset=sorted(advertised))
            # _send_rosters re-armed the timer either way; when the
            # subset sat below the SHARE threshold the roster was
            # refused — count the lap so a cohort that can never reach
            # t abandons the round instead of stalling forever (the
            # agreement twin of the unmask retry cap)
            if self._secagg_stage == "agreement":
                self._secagg_agreement_laps += 1
                if self._secagg_agreement_laps > \
                        self._SECAGG_UNMASK_RETRIES:
                    log.error(
                        "round %d: mask agreement cannot reach the share "
                        "threshold after %d laps; abandoning the round",
                        self.round_idx, self._secagg_agreement_laps - 1)
                    self._secagg_stage = None
                    self._cancel_timer()
                    self._finish_round(0)
            return
        self._arm_timer()  # wait policy (or below quorum): keep waiting

    # a lost UNMASK/SHARES frame must not wedge the round: the request
    # re-sends on each timer lap, and after this many laps below the
    # share threshold the round is abandoned loudly (global unchanged)
    _SECAGG_UNMASK_RETRIES = 3

    def _begin_unmask(self, admitted_count: int) -> None:
        """Barrier closed over masked uploads: ask the survivors for the
        shares that unmask the sum (self-mask seeds of every uploader,
        pairwise secrets of every dead roster member)."""
        self._secagg_stage = "unmask"
        self._secagg_quorum = admitted_count
        self._secagg_unmask_laps = 0
        self._send_unmask_request()
        self._arm_timer()

    def _send_unmask_request(self) -> None:
        from fedml_tpu.secure.protocol import MSG_SECAGG_UNMASK
        with self._span("ingest:unmask", deterministic=True), \
                self._perf_phase("unmask"):
            survivors, dead = self.secagg.unmask_request()
            if dead:
                log.warning("round %d: reconstructing %d dead silo(s) %s "
                            "from surviving shares", self.round_idx,
                            len(dead), dead)
            self.send_many(
                MSG_SECAGG_UNMASK, survivors,
                shared_params={Message.ARG_ROUND: self.round_idx,
                               Message.ARG_SECAGG: {"survivors": survivors,
                                                    "dead": dead}})

    def _on_secagg_shares(self, msg: Message) -> None:
        self._beat(msg.sender_id)
        if msg.get(Message.ARG_ROUND) != self.round_idx \
                or self._secagg_stage != "unmask":
            return
        with self._span("ingest:unmask", deterministic=True), \
                self._perf_phase("unmask"):
            complete = self.secagg.note_reveal(msg.sender_id,
                                               msg.get(Message.ARG_SECAGG))
        if complete:
            self._finalize_secagg()

    def _secagg_unmask_timeout(self) -> None:
        if self.secagg.can_finalize():
            log.warning("round %d: unmask quorum reached but not every "
                        "survivor revealed; finalizing from the available "
                        "shares", self.round_idx)
            self._finalize_secagg()
            return
        self._secagg_unmask_laps += 1
        if self._secagg_unmask_laps > self._SECAGG_UNMASK_RETRIES:
            # unrecoverable: too many survivors unreachable to ever reach
            # the share threshold — the round is LOST loudly, the global
            # stays put (a partially-unmasked sum must never publish)
            log.error("round %d: unmask share threshold unreachable after "
                      "%d request retries; abandoning the round",
                      self.round_idx, self._SECAGG_UNMASK_RETRIES)
            self._secagg_stage = None
            self._finish_round(0)
            return
        log.warning("round %d: below the unmask share threshold; re-"
                    "requesting reveals (lap %d/%d)", self.round_idx,
                    self._secagg_unmask_laps, self._SECAGG_UNMASK_RETRIES)
        self._send_unmask_request()
        self._arm_timer()

    def _finalize_secagg(self) -> None:
        """Unmask the ring sum, run the post-unmask sum defenses, publish
        (or — on an unrecoverable round — keep the global and say so)."""
        from fedml_tpu.secure.protocol import SecAggError
        if self.faultline is not None:
            # shares collected, sum not yet recovered: the abort-only
            # proof point — recovery must restart the round from the
            # boundary with the global unchanged, never a partial unmask
            self.faultline.maybe_crash("mid_unmask",
                                       round_idx=self.round_idx)
        self._secagg_stage = None
        self._cancel_timer()
        quorum = self._secagg_quorum
        with self._span("aggregate", parent=self._round_span,
                        round=self.round_idx, quorum=quorum), \
                self._perf_phase("unmask"):
            try:
                mean, den = self.secagg.finalize(
                    reference=self._host_params())
            except SecAggError:
                log.exception("round %d: secure unmask FAILED; the global "
                              "model is unchanged this round",
                              self.round_idx)
                mean = None
            if mean is None:
                # unmask failure or the post-unmask sum screen fired:
                # the round is lost loudly, never mis-aggregated
                quorum = 0
            else:
                self.params = mean
        self._finish_round(quorum)

    # -- health --------------------------------------------------------------
    def _on_heartbeat(self, msg: Message) -> None:
        self._beat(msg.sender_id)

    def _beat(self, silo: int) -> None:
        if self.failure_detector is None:
            return
        rejoined = self.failure_detector.beat(silo)
        if rejoined and not self._finished and not self.aborted \
                and self.round_idx < self.num_rounds:
            # rejoin protocol: the returning silo immediately gets the
            # current global + round index (+ a client assignment), so it
            # is warm when the next broadcast re-includes it.  Its upload
            # for THIS round is not expected (the quorum already closed
            # over its absence) and will be discarded by _on_model.
            log.info("silo %d rejoined at round %d; syncing current global",
                     silo, self.round_idx)
            ids = self._sampled()
            client_idx = int(ids[silo - 1]) if silo - 1 < len(ids) else 0
            self.send(MsgType.S2C_SYNC, silo,
                      **{Message.ARG_MODEL_PARAMS: self._host_params(),
                         Message.ARG_CLIENT_INDEX: client_idx,
                         Message.ARG_ROUND: self.round_idx})

    def _on_model(self, msg: Message) -> None:
        self._beat(msg.sender_id)
        if not self._upload_guards(msg, check_inflight=True):
            return
        # one wire arrival per upload frame (shard slices each count —
        # they are distinct frames): the critical-path observatory's
        # idle classifier (network → straggler → barrier_wait) keys on
        # this timeline
        self._note_arrival()
        if self.ingest is not None:
            # pipelined receive: this thread's work ENDS here — header
            # facts only, then enqueue to the shard's fold worker.  The
            # worker re-runs the guards under the ingest lock (the
            # authoritative check: round/stage may move while queued).
            shard = 0
            if self.shard_wire is not None:
                s = msg.get(Message.ARG_SHARD)
                if isinstance(s, int) and 0 <= s < self.ingest.num_shards:
                    shard = s
                # a malformed/missing shard tag rides queue 0: the
                # worker's offer() rejects it as structural damage
            else:
                # replicated: the queued frame must trip the duplicate
                # guard for this silo until its fold lands
                self._ingest_inflight.add(msg.sender_id)
            ok = self.ingest.submit(
                shard, lambda: self._ingest_task(msg),
                detail=f"silo {msg.sender_id} round {self.round_idx}")
            if not ok and self.shard_wire is None:
                # overflow: the pipeline already dead-lettered + fed the
                # fault ledger (a NETWORK fault — never a strike); the
                # silo is simply not heard from this round
                self._ingest_inflight.discard(msg.sender_id)
            return
        self._upload_body(msg)

    def _upload_guards(self, msg: Message,
                       check_inflight: bool = True) -> bool:
        """The receive-path envelope guards (round tag, secagg stage,
        quorum membership, duplicates).  Factored so the pipelined path
        can run them twice: a cheap screen on the transport thread, and
        the AUTHORITATIVE re-check on the fold worker under the ingest
        lock (round state may have moved while the frame sat queued).
        ``check_inflight`` adds the queued-but-unfolded duplicate guard
        (transport side only — the worker IS the inflight entry)."""
        # stale-round guard: a straggler's upload arriving after its round
        # was closed out (drop policy) must not pollute the next barrier
        upload_round = msg.get(Message.ARG_ROUND)
        if upload_round is not None and upload_round != self.round_idx:
            log.warning("discarding round-%s upload from silo %d (current "
                        "round %d)", upload_round, msg.sender_id,
                        self.round_idx)
            return False
        if self.secagg is not None and self._secagg_stage != "upload":
            # a masked upload outside the upload stage (a straggler
            # landing after the barrier closed, mid-unmask) must not
            # mutate the fold: the unmask request already snapshotted
            # survivors/dead, and folding now would demand self-mask
            # shares nobody was asked to reveal — the round that HAD
            # quorum would be abandoned.  Same guard as the edge path.
            log.info("round %d: discarding masked upload from silo %d "
                     "outside the upload stage (stage=%s)", self.round_idx,
                     msg.sender_id, self._secagg_stage)
            return False
        if self._expected and msg.sender_id not in self._expected:
            # an upload from a silo outside the expected quorum (it was
            # declared dead at broadcast, then rejoined mid-round): the
            # round's accounting already closed over it — drop, it will
            # participate again from the next broadcast
            log.info("discarding round-%d upload from unexpected silo %d",
                     self.round_idx, msg.sender_id)
            return False
        if msg.sender_id in self._received:
            # duplicate delivery of this round's report (chaos dup,
            # transport retry): the first copy already went through
            # decode + admission — re-admitting would double-strike the
            # silo, double-count the telemetry, bank its norm twice, and
            # could even overwrite an ACCEPTED entry with a rejection
            log.info("ignoring duplicate round-%d upload from silo %d",
                     self.round_idx, msg.sender_id)
            return False
        if check_inflight and self.ingest is not None \
                and self.shard_wire is None \
                and msg.sender_id in self._ingest_inflight:
            log.info("ignoring duplicate round-%d upload from silo %d "
                     "(first copy still queued)", self.round_idx,
                     msg.sender_id)
            return False
        return True

    def _ingest_task(self, msg: Message) -> None:
        """One queued upload, on its shard's fold worker: arena staging
        (gather + one device_put + the fused screen) OUTSIDE the ingest
        lock — that is where per-shard parallelism lives — then the
        guard re-check and the full upload body under it."""
        silo = msg.sender_id
        try:
            pre = None
            if self.shard_wire is not None:
                s = msg.get(Message.ARG_SHARD)
                arena = (self.ingest.arena_for(s)
                         if isinstance(s, int)
                         and 0 <= s < self.ingest.num_shards else None)
            else:
                arena = self.ingest.arena_for(0)
            if arena is not None:
                with self._span("ingest:decode", deterministic=True), \
                        self._perf_phase("decode"):
                    pre = arena.stage_message(msg,
                                              Message.ARG_MODEL_PARAMS)
                    if pre is None:
                        # in-process object message (pump mode without a
                        # codec roundtrip): stage from the decoded tree
                        pre = arena.stage_tree(
                            msg.get(Message.ARG_MODEL_PARAMS))
            with self._ingest_lock:
                if not self._upload_guards(msg, check_inflight=False):
                    return
                self._upload_body(msg, pre=pre)
        finally:
            if self.shard_wire is None:
                with self._ingest_lock:
                    self._ingest_inflight.discard(silo)

    def _upload_body(self, msg: Message, pre=None) -> None:
        """Everything past the envelope guards: decode, admission (the
        ``pre`` seam carries the arena's precomputed screens), health,
        and the fold/stage via `_note_upload`.  Inline mode calls this
        straight from `_on_model`; pipelined mode from the fold worker
        under the ingest lock."""
        if self.shard_wire is not None:
            self._on_shard_upload(msg, pre=pre)
            return
        # barrier semantics: wait for every sampled silo
        # (check_whether_all_receive, FedAvgServerManager.py:51)
        upload = msg.get(Message.ARG_MODEL_PARAMS)
        # compression-scheme handshake: a payload with a "scheme" tag is a
        # compressed frame (comm/compress.py) — both mismatch directions
        # would otherwise crash far from the misconfiguration.  Without
        # the admission pipeline, mismatches keep the fail-loudly
        # contract (a misconfigured fleet should crash at the server);
        # WITH it, a mismatched payload is attacker-reachable structural
        # damage and takes the reject-and-strike path instead of killing
        # the handler thread.
        is_compressed = isinstance(upload, dict) and "scheme" in upload
        handshake_err = None
        if self.decode_upload is None and is_compressed:
            handshake_err = (
                f"silo {msg.sender_id} sent a compressed upload "
                f"(scheme={upload['scheme']!r}) but the server has no "
                f"--wire_compression configured")
        elif self.decode_upload is not None and not is_compressed:
            handshake_err = (
                f"server expects compressed uploads but silo "
                f"{msg.sender_id} sent plain parameters; launch silos "
                f"with the same --wire_compression")
        if handshake_err is not None:
            if self.admission is None:
                raise ValueError(handshake_err)
            log.warning("round %d: rejecting upload from silo %d "
                        "(handshake mismatch: %s)", self.round_idx,
                        msg.sender_id, handshake_err)
            self.admission.reject(msg.sender_id, self.round_idx,
                                  "fingerprint")
            if self.health is not None:
                with self._perf_phase("health"):
                    self.health.observe_rejected(msg.sender_id,
                                                 "fingerprint")
            if self._first_upload_t is None:
                self._first_upload_t = time.monotonic()
            self._note_upload(msg.sender_id, None)
            return
        if self.decode_upload is not None:
            try:
                # the codec decode is its own micro-span AND perf phase
                # (ISSUE 17): "is this round decode-bound?" needs the
                # interval, not a share of an opaque aggregate
                with self._span("ingest:decode", deterministic=True), \
                        self._perf_phase("decode"):
                    upload = self.decode_upload(upload, self.params)
            except Exception:  # noqa: BLE001 — damaged compressed frame
                if self.admission is None:
                    raise  # legacy fail-loudly contract
                # a frame corrupted in flight (chaos 'corrupt', bad wire)
                # can make the codec itself throw; with the admission
                # pipeline on, that is structural damage, not a server
                # crash — leave the raw payload in place and let the
                # fingerprint check below reject + strike it
                log.warning("round %d: undecodable upload from silo %d; "
                            "routing to admission as structural damage",
                            self.round_idx, msg.sender_id)
        if self._first_upload_t is None:
            self._first_upload_t = time.monotonic()
        if pre is not None and pre.structural_ok and pre.tree is not None:
            # the arena already staged the payload on the device —
            # downstream (fold/health) consumes the staged tree, so the
            # fold's H2D transfer is the arena's ONE device_put
            upload = pre.tree
        entry = (upload, msg.get(Message.ARG_NUM_SAMPLES))
        upload_norm = None
        if self.admission is not None:
            with self._span("ingest:admission", deterministic=True), \
                    self._perf_phase("admission"):
                verdict = self.admission.admit(
                    msg.sender_id, upload, msg.get(Message.ARG_NUM_SAMPLES),
                    self.params, self.round_idx, pre=pre)
            if verdict.ok:
                entry = (upload, verdict.num_samples)
                # the screen's one O(model) norm pass is shared: health
                # reuses it instead of re-walking the tree
                upload_norm = verdict.norm
            else:
                # the silo DID report — the barrier closes over it — but
                # its payload is inadmissible: weight 0, never aggregated
                log.warning("round %d: rejecting upload from silo %d "
                            "(reason=%s)", self.round_idx, msg.sender_id,
                            verdict.reason)
                entry = None
                if self.health is not None:
                    with self._perf_phase("health"):
                        self.health.observe_rejected(msg.sender_id,
                                                     verdict.reason)
        if entry is not None and self.health is not None:
            # fold the health stats at arrival, BEFORE the aggregation
            # fold can consume (stream mode) or stage the upload —
            # after it, the evidence is gone
            with self._perf_phase("health"):
                # an edge frame carries its block's rollup beside the
                # pre-reduced mean; the flat topology never sets it
                edge_summary = msg.get(Message.ARG_HEALTH)
                if edge_summary is not None:
                    self.health.note_edge(msg.sender_id, edge_summary)
                self.health.observe_admitted(msg.sender_id, entry[0],
                                             entry[1], norm=upload_norm)
        self._note_upload(msg.sender_id, entry)

    def _on_shard_upload(self, msg: Message, pre=None) -> None:
        """One shard slice of a silo's upload (the sharded wire): screen
        it per shard at arrival; the silo reaches the barrier only when
        its LAST slice completes admission (or its first slice fails
        it).  A whole-model upload on the sharded wire (a rejoin
        warm-up train, a mis-launched silo) is structural damage — it
        rejects at weight 0 like any fingerprint mismatch instead of
        wedging the fold.  ``pre`` is the shard arena's precomputed
        screen (pipelined path): `ShardAdmission.offer` consumes its
        facts and banks the staged device slice."""
        from fedml_tpu.shard_spine.admission import ACCEPT, WAIT
        silo = msg.sender_id
        if self._first_upload_t is None:
            self._first_upload_t = time.monotonic()
        shard = msg.get(Message.ARG_SHARD)
        payload = msg.get(Message.ARG_MODEL_PARAMS)
        if pre is not None and pre.structural_ok and pre.tree is not None:
            payload = pre.tree
        with self._span("ingest:admission", deterministic=True), \
                self._perf_phase("admission"):
            if shard is None:
                log.warning("round %d: silo %d sent a whole-model "
                            "upload on the sharded wire; rejecting as "
                            "structural damage", self.round_idx, silo)
                status, info = self.shard_wire.admission.reject(
                    silo, self.round_idx, "fingerprint")
            else:
                status, info = self.shard_wire.admission.offer(
                    silo, shard, msg.get(Message.ARG_SHARD_COUNT),
                    payload,
                    msg.get(Message.ARG_NUM_SAMPLES), self.round_idx,
                    pre=pre)
        if status == WAIT:
            return
        if status != ACCEPT:
            log.warning("round %d: rejecting sharded upload from silo "
                        "%d (reason=%s)", self.round_idx, silo,
                        info.get("reason"))
            if self.health is not None:
                with self._perf_phase("health"):
                    self.health.observe_rejected(silo,
                                                 info.get("reason"))
            self._note_upload(silo, None)
            return
        if self.health is not None:
            # the observatory reads the ASSEMBLED update (one host join
            # per admitted silo — the cosine/norm stats are whole-model
            # quantities); the fold itself stays per-shard
            with self._perf_phase("health"):
                self.health.observe_admitted(
                    silo, self.shard_wire.join(info["slices"]),
                    info["num_samples"], norm=info["norm"])
        self._note_upload(silo, (info["slices"], info["num_samples"]))

    # sentinel entry marker: the upload's bytes already live in the
    # staging buffer, so the decoded frame (and the wire buffer it views)
    # can be released immediately instead of held until the barrier
    _STAGED = object()

    def _note_upload(self, silo: int, entry: Optional[tuple]) -> None:
        """Record a silo's report (``None`` = reported-but-inadmissible)
        and close the round when the barrier is satisfied
        (check_whether_all_receive, FedAvgServerManager.py:51).

        With incremental staging on, an admitted upload is written into
        its cohort slot HERE — on the receive path, while the round is
        still waiting on stragglers — so the barrier-close does no
        per-leaf stacking at all.  In stream mode the upload FOLDS into
        the O(model) running aggregate here instead, and nothing
        model-sized survives the fold."""
        # degrade spine: the arrival's round-relative latency feeds the
        # adaptive-deadline history, and it rides the journal accept
        # record (extra={"lat_s"}) so a resumed round replays the SAME
        # history the crashed process observed
        payload_rejected = entry is None
        lat_s = (None if self._round_t0 is None
                 else round(time.monotonic() - self._round_t0, 6))
        lat_extra = {"lat_s": lat_s} if lat_s is not None else None
        if entry is not None and self.faultline is not None:
            # admitted, not yet folded: the crash that loses exactly
            # this one upload (its fold never happened)
            self.faultline.maybe_crash("post_admission_pre_fold",
                                       round_idx=self.round_idx, silo=silo)
        if entry is not None and self.secagg is not None:
            # ring addition IS the fold: the masked upload lands in the
            # O(model) uint32 accumulator at arrival (the PR 7 streaming
            # spine, preserved under masking) and nothing model-sized
            # survives per silo
            from fedml_tpu.secure.protocol import SecAggError
            try:
                with self._span("ingest:fold", deterministic=True), \
                        self._perf_phase("fold"):
                    self.secagg.fold(silo, entry[0], entry[1])
            except SecAggError as e:
                # an upload from outside the fixed roster (e.g. a silo
                # whose advert was dropped but whose upload got through):
                # inadmissible — its masks cannot cancel
                log.warning("round %d: rejecting masked upload from silo "
                            "%d (%s)", self.round_idx, silo, e)
                entry = None
            else:
                if self.journal is not None:
                    # metadata only — a masked fold never snapshots
                    # (the round is journalled abort-only)
                    with self._span("ingest:journal", deterministic=True), \
                            self._perf_phase("journal"):
                        self.journal.note_accept(self.round_idx, silo,
                                                 float(entry[1]),
                                                 extra=lat_extra)
                entry = (self._STAGED, entry[1])
        elif entry is not None and self.stream_agg is not None:
            with self._span("ingest:fold", deterministic=True), \
                    self._perf_phase("fold"):
                if self.shard_wire is not None:
                    # the admitted silo's S slices fold per shard —
                    # each shard's device touches only its O(model/S)
                    # piece of the update
                    self.stream_agg.fold_slices(entry[0], entry[1])
                else:
                    self.stream_agg.fold(entry[0], entry[1])
            if self.journal is not None:
                # the accept record is durable per report; the fold
                # STATE snapshots on the journal's cadence (mean fold
                # only — the journal ignores state_fn on abort-only
                # rounds)
                state_fn = (self.stream_agg.state_dict
                            if self.stream_agg.method == "mean" else None)
                with self._span("ingest:journal", deterministic=True), \
                        self._perf_phase("journal"):
                    self.journal.note_accept(self.round_idx, silo,
                                             float(entry[1]),
                                             extra=lat_extra,
                                             state_fn=state_fn)
            entry = (self._STAGED, entry[1])
        elif entry is not None and self._staging_active():
            with self._span("ingest:fold", deterministic=True), \
                    self._perf_phase("staging"):
                self._stage(silo, entry[0])
            entry = (self._STAGED, entry[1])
        elif entry is None and self.journal is not None:
            # reported-but-inadmissible: journalled so the soak
            # invariant checker can account every report
            with self._perf_phase("journal"):
                self.journal.note_accept(self.round_idx, silo, 0.0,
                                         folded=False, reason="rejected")
        if self.faultline is not None:
            # folded (or recorded), report not yet banked: on resume the
            # fold is durable up to the snapshot cadence and this silo
            # re-tasks only past it
            self.faultline.maybe_crash("post_fold_pre_ack",
                                       round_idx=self.round_idx, silo=silo)
        if self.degrade is not None:
            # admitted OR rejected, the silo completed the round trip:
            # its latency is real evidence either way (an unmeasured
            # silo would otherwise pin the deadline at the static cap)
            if lat_s is not None:
                self.degrade.observe_completion(silo, lat_s)
            if entry is not None:
                self.degrade.note_accept(silo)
            elif payload_rejected:
                # admission-rejected report: a PAYLOAD fault on the
                # attribution ledger (the strike itself already landed
                # at the admission site)
                from fedml_tpu.robust.degrade import FaultClass
                self.degrade.note_fault(FaultClass.PAYLOAD, silo=silo)
        self._received[silo] = entry
        if not self._barrier_met():
            return
        self._complete_round()

    def _staging_active(self) -> bool:
        return self.aggregate_fn is not None and self.incremental_staging

    def _stage(self, silo: int, upload) -> None:
        """Copy one admitted upload into staging slot ``silo - 1``."""
        if self._staging is None:
            host = self._host_params()
            n = self._num_silos
            self._staging_def = jax.tree.structure(host)
            self._staging = jax.tree.map(
                lambda l: np.empty((n,) + np.shape(l),
                                   np.asarray(l).dtype), host)
            self._staging_leaves = jax.tree.leaves(self._staging)
        if jax.tree.structure(upload) != self._staging_def:
            # unreachable with the admission fingerprint armed; without
            # it this keeps the legacy fail-loudly contract the same way
            # a mismatched np.stack did
            raise ValueError(
                f"silo {silo} upload does not match the global template "
                f"(treedef mismatch)")
        for buf, leaf in zip(self._staging_leaves, jax.tree.leaves(upload)):
            arr = np.asarray(leaf)
            if arr.dtype != buf.dtype:
                # slot assignment would silently cast (the seed np.stack
                # promoted instead, retracing the jit) — a dtype drift is
                # a malformed upload either way: fail loudly, like every
                # other template mismatch
                raise ValueError(
                    f"silo {silo} upload leaf dtype {arr.dtype} does not "
                    f"match the global template ({buf.dtype})")
            buf[silo - 1] = arr
        self._staged.add(silo)
        self._staged_seen += 1
        self._g_staged.set(len(self._staged))

    def _stack_cohort(self, admitted: Dict[int, tuple]):
        """Stack admitted uploads into the STATIC ``[cohort, ...]`` tree
        the defended aggregate jits against: slot ``i-1`` belongs to silo
        ``i``; silos that were dropped, quarantined, or rejected hold a
        copy of the current global with weight 0 (a zero diff that every
        defense masks out) — the shape never depends on who showed up,
        so the jit compiles once at round 1 and never again."""
        n = self._num_silos
        host_global = jax.tree.map(np.asarray, self.params)
        trees, w = [], np.zeros(n, np.float32)
        for silo in range(1, n + 1):
            if silo in admitted:
                trees.append(admitted[silo][0])
                w[silo - 1] = admitted[silo][1]
            else:
                trees.append(host_global)
        stacked = jax.tree.map(
            lambda *xs: np.stack([np.asarray(x) for x in xs]), *trees)
        return stacked, w

    def _staged_cohort(self, admitted: Dict[int, tuple]):
        """The incremental-staging counterpart of `_stack_cohort`: the
        admitted uploads were already written into their slots at arrival
        time, so the barrier-close only refills the ABSENT slots (dropped,
        quarantined, rejected) with the current global — weight 0, the
        same zero diff every defense masks out.  The buffer is released
        at round close and reallocated per round with the SAME static
        ``[cohort, ...]`` shapes/dtypes, so the defended jit still
        compiles exactly once."""
        n = self._num_silos
        if self._staging is None:
            # every upload this round was rejected before staging; the
            # caller skips aggregation on an empty admitted set, so this
            # only triggers when admitted is non-empty but nothing staged
            # — impossible by construction (_note_upload stages every
            # admitted entry), kept as a loud invariant
            raise RuntimeError("staging buffer missing at round close")
        w = np.zeros(n, np.float32)
        for silo, (_, num_samples) in admitted.items():
            w[silo - 1] = num_samples
        missing = [s for s in range(1, n + 1) if s not in self._staged]
        if missing:
            host_leaves = jax.tree.leaves(self._host_params())
            for buf, leaf in zip(self._staging_leaves, host_leaves):
                for silo in missing:
                    buf[silo - 1] = np.asarray(leaf)
        return self._staging, w

    def _complete_round(self) -> None:
        if self.faultline is not None:
            self.faultline.maybe_crash("barrier_close",
                                       round_idx=self.round_idx)
        self._cancel_timer()
        now = time.monotonic()
        self._h_quorum.observe(len(self._received))
        if self._round_t0 is not None:
            self._h_round.observe(now - self._round_t0)
        if self._first_upload_t is not None:
            # tail wait: how long the round's LAST accepted upload (or the
            # drop-policy timeout) trailed the first one
            self._h_straggler.observe(now - self._first_upload_t)
            if self.perf is not None:
                self.perf.add_phase("straggler_wait",
                                    now - self._first_upload_t)
        if self.round_idx in self.dropped_silos:  # normalize the drop log
            self.dropped_silos[self.round_idx] = sorted(
                set(self.dropped_silos[self.round_idx]))
        # admission-rejected reports ride as None entries: they satisfied
        # the barrier but must not aggregate (and must not be EF-acked)
        admitted = {s: v for s, v in self._received.items() if v is not None}
        # possibly EMPTY (all uploads rejected) — never None here: None
        # means "no ack info" and EF residual settlement would wrongly
        # assume the rejected uploads were aggregated
        self._last_accepted = np.asarray(sorted(admitted), np.int32)
        self._received.clear()
        if self.secagg is not None:
            if admitted:
                # the barrier is met but the sum is still masked: the
                # round closes asynchronously once the unmask share
                # reveals arrive (_finalize_secagg)
                self._begin_unmask(len(admitted))
                return
            self._secagg_stage = None
            log.warning("round %d: no admissible masked uploads; the "
                        "global model is unchanged this round",
                        self.round_idx)
            self._finish_round(0)
            return
        defended = (self.aggregate_fn is not None
                    or (self.stream_agg is not None
                        and self.stream_agg.defended))
        # the sharded spine's finalize gets its OWN phase label
        # (one XLA program or fused Pallas launch per shard) so the
        # trend gate never compares a sharded round against a
        # replicated baseline under one name
        agg_phase = ("shard_finalize" if self.shard_wire is not None
                     else "defended_aggregate" if defended
                     else "aggregate")
        with self._span("aggregate", parent=self._round_span,
                        round=self.round_idx, quorum=len(admitted)), \
                self._perf_phase(agg_phase):
            finalized = None
            if not admitted:
                log.warning("round %d: no admissible uploads; the global "
                            "model is unchanged this round", self.round_idx)
            elif self.stream_agg is not None:
                # stream mode: every admitted upload already folded at
                # arrival — the barrier-close is one finalize, O(model)
                finalized = self.stream_agg.finalize(self.round_idx)
            elif self.aggregate_fn is not None:
                if self._staging_active():
                    stacked, w = self._staged_cohort(admitted)
                else:
                    stacked, w = self._stack_cohort(admitted)
                # normalize the global to device arrays first: round 0's
                # numpy init and later rounds' jax outputs would otherwise
                # key TWO jit cache entries (numpy vs committed-array
                # shardings) — a silent double compile of the defended
                # aggregate.  jnp.asarray is a no-op on a jax output.
                dev_params = jax.tree.map(jnp.asarray, self.params)
                finalized = self.aggregate_fn(dev_params, stacked, w,
                                              self.round_idx)
            else:
                trees = [admitted[s][0] for s in sorted(admitted)]
                weights = np.array([admitted[s][1] for s in sorted(admitted)],
                                   dtype=np.float32)
                finalized = tree_weighted_mean(trees, weights)
            if finalized is not None:
                # the server-optimizer seam: the finalize output becomes
                # the pseudo-gradient Δ = global − finalize and the
                # optimizer's jitted step applies it.  server_opt=None
                # (and the plain optimizer, which returns `finalized`
                # itself) keep this assignment byte-for-byte pre-seam.
                if self.server_opt is not None:
                    self.params = self.server_opt.apply(
                        self.params, finalized, self.round_idx)
                else:
                    self.params = finalized
        self._finish_round(len(admitted))

    def _finish_round(self, quorum: int) -> None:
        """The round-close tail shared by the plaintext barrier close and
        the secagg unmask completion: staging release, health/checkpoint/
        publish/perf hooks, then the next broadcast (or FINISH)."""
        # release the staged cohort at round close: the defended jit
        # already copied the host buffer to the device, so holding the
        # [cohort, ...] block between rounds keeps server RSS at the
        # cohort watermark for no benefit — dropped here, the allocator
        # returns to baseline between rounds (pinned with the PR 6 RSS
        # sampler's per-round reset) and the next round reallocates on
        # its first staged arrival
        self._staging = self._staging_leaves = self._staging_def = None
        self._staged.clear()
        self._g_staged.set(0)
        if self.shard_wire is not None:
            # drop half-assembled straggler slices: the round closed
            # over them at weight 0, and a late slice must never splice
            # into the NEXT round's assembly
            self.shard_wire.round_end()
        if self._round_span is not None:
            self._round_span.end()
            self._round_span = None
        if self.health is not None:
            # closes the health round on the post-aggregate host mirror
            # (shared with checkpoint/publish — still one device→host
            # transfer per round), BEFORE perf.round_end so the health
            # phase lands in THIS round's ledger line
            with self._perf_phase("health"):
                self.health.round_end(self.round_idx,
                                      new_global=self._host_params(),
                                      quorum=quorum)
        decision = None
        if self.controller is not None:
            # the adaptive verdict for the NEXT round, decided BEFORE the
            # checkpoint thunk runs so the controller's levers land in
            # this round's boundary (a resume continues the trajectory)
            kw = {}
            if self.degrade is not None:
                # composition contract (ISSUE 19): the controller may
                # WIDEN the cohort on participation debt, but a shrink
                # can never fight the quorum floor
                kw["debt"] = self.degrade.max_debt()
                qf = self.degrade.quorum_for(self._num_silos)
                if qf is not None:
                    kw["quorum_floor"] = qf
            decision = self.controller.decide(
                self.round_idx,
                self.health.last_line if self.health is not None else None,
                **kw)

        if self.faultline is not None:
            # the aggregate is applied in memory but not yet durable:
            # the recovery here re-finalizes the round from the journal
            # snapshot (or re-runs it from the boundary)
            self.faultline.maybe_crash("mid_checkpoint_write",
                                       round_idx=self.round_idx)
        if self.checkpointer is not None:
            # thunk: rounds the save_every gate skips pay no device→host
            # copy and no EF serialization (_host_params memoizes the
            # transfer, and the next broadcast reuses the same copy)
            with self._perf_phase("checkpoint"):
                self.checkpointer.maybe_save(
                    self.round_idx,
                    lambda: self._checkpoint_state(
                        self.round_idx, host_params=self._host_params()),
                    last_round=self.round_idx + 1 >= self.num_rounds)
        if self.journal is not None:
            # round_end lands AFTER the checkpoint is durable: a crash
            # between the two leaves an open journal round whose
            # snapshot re-finalizes to the same global on resume
            with self._perf_phase("journal"):
                self.journal.round_end(self.round_idx)
        if self.faultline is not None:
            self.faultline.maybe_crash("publish", round_idx=self.round_idx)
        if self.publish is not None:
            # serve-while-train: hand the registry a HOST copy so the
            # serving path never holds references into device buffers the
            # next round's aggregation will donate/overwrite
            with self._perf_phase("publish"):
                self.publish(self._host_params(), self.round_idx)
        if self.perf is not None:
            # ledger line closes BEFORE the eval hook: round_s measures
            # the server's own round costs, not the eval cadence.  A
            # strict-mode RecompileError raises here, on the event loop,
            # and fails the run loudly (the test-mode contract).
            extra = ({"shards": self.shard_wire.num_shards}
                     if self.shard_wire is not None else {})
            # the round's post-aggregate global CRC: the ingest bench's
            # bit-parity gate compares this sequence between the inline
            # and pipelined twins (utils.journal.tree_crc — the same
            # checksum the crash journal trusts)
            from fedml_tpu.utils.journal import tree_crc
            extra["global_crc"] = tree_crc(self._host_params())
            if self.server_opt is not None:
                extra["server_opt"] = self.server_opt.name
            if decision is not None:
                # every pacing decision named on the round's ledger line
                extra["adapt"] = decision.as_ledger()
            if self.degrade is not None:
                # every degrade decision named on the round's ledger
                # line: deadline, accepts/drops, holds, fault mix
                extra["degrade"] = self.degrade.as_ledger()
            self.perf.round_end(self.round_idx, quorum=quorum,
                                dropped=len(self.dropped_silos.get(
                                    self.round_idx, [])), **extra)
        if self.on_round_done is not None:
            self.on_round_done(self.round_idx, self.params)
        self.round_idx += 1
        if self.round_idx >= self.num_rounds:
            for silo in range(1, self._num_silos + 1):
                self.send(MsgType.S2C_FINISH, silo)
            self.finish()
        else:
            self._broadcast(MsgType.S2C_SYNC)

    def finish(self) -> None:
        self._finished = True
        self._cancel_timer(join=True)
        if self.ingest is not None:
            # no drain here: finish may run ON a fold worker (the last
            # round's barrier closed there) and a worker draining its
            # own queue would deadlock; stop() skips joining the calling
            # thread for the same reason.  Frames still queued are
            # post-federation stragglers — stale by construction.
            self.ingest.stop()
        super().finish()


class FedAvgClientActor(ClientManager):
    """Silo-side trainer actor (reference FedAvgClientManager.py:18-75).

    ``heartbeat_interval_s``: when set, a daemon thread sends
    C2S_HEARTBEAT beats (tagged with the last synced round) every
    interval while the actor runs — the signal the server's
    `FailureDetector` uses to tell a slow silo from a dead one between
    uploads.  The thread stops with ``finish()``.

    ``server_id``: where uploads and heartbeats go.  The flat topology
    keeps the default root (0); under the multi-level aggregator
    topology (`algorithms/hierarchical.EdgeAggregatorActor`) a silo
    reports to its EDGE, which folds locally and ships one pre-reduced
    update to the root.
    """

    def __init__(self, node_id: int, transport: Transport,
                 train_fn: SiloTrainFn,
                 encode_upload: Optional[Callable] = None,
                 on_accepted: Optional[Callable] = None,
                 heartbeat_interval_s: Optional[float] = None,
                 server_id: int = 0,
                 secagg=None):
        """``secagg``: a `fedml_tpu.secure.protocol.SecAggClient` — the
        silo speaks the secure-aggregation choreography: on sync it
        advertises its round keys (then trains while the agreement
        completes), uploads only after the ROSTER fixes the masking
        cohort — quantized into the ring, pairwise- and self-masked —
        and answers the server's UNMASK request with exactly the share
        kinds requested (never both for one silo).  Every masking
        parameter rides the sync frame; the client needs no
        configuration beyond this object."""
        super().__init__(node_id, transport)
        self.server_id = server_id
        self.train_fn = train_fn
        # optional wire compression: encode_upload(new_params,
        # global_params) -> payload (comm/compress.py)
        self.encode_upload = encode_upload
        # optional ack hook: on_accepted(accepted_silo_ids | None) fires on
        # every sync BEFORE training, so deferred error-feedback residuals
        # settle (ErrorFeedback.resolve) before the next encode reads them
        self.on_accepted = on_accepted
        self.heartbeat_interval_s = heartbeat_interval_s
        self.secagg = secagg
        if secagg is not None and encode_upload is not None:
            raise ValueError("secagg and encode_upload (wire compression) "
                             "are mutually exclusive: a compressed payload "
                             "cannot ride the masking ring")
        # (round, trained host params, num_samples) awaiting its roster
        self._pending_upload: Optional[tuple] = None
        self._round: Optional[int] = None  # last round synced from server
        # sharded wire (fedml_tpu/shard_spine): built lazily on the
        # first sync frame carrying ARG_SHARD — the plan spec rides
        # shard 0's frame, so the silo needs zero shard configuration
        self._shard_rx = None
        self._hb_stop = threading.Event()
        self._hb_thread: Optional[threading.Thread] = None

    def register_handlers(self) -> None:
        self.register_handler(MsgType.S2C_INIT, self._on_sync)
        self.register_handler(MsgType.S2C_SYNC, self._on_sync)
        self.register_handler(MsgType.S2C_FINISH, lambda m: self.finish())
        if self.secagg is not None:
            from fedml_tpu.secure.protocol import (MSG_SECAGG_ROSTER,
                                                   MSG_SECAGG_UNMASK)
            self.register_handler(MSG_SECAGG_ROSTER, self._on_secagg_roster)
            self.register_handler(MSG_SECAGG_UNMASK, self._on_secagg_unmask)

    def run(self) -> None:
        if self.heartbeat_interval_s is not None and self._hb_thread is None:
            self._hb_thread = threading.Thread(
                target=self._heartbeat_loop, daemon=True,
                name=f"heartbeat-silo-{self.node_id}")
            self._hb_thread.start()
        super().run()

    def _heartbeat_loop(self) -> None:
        while not self._hb_stop.wait(self.heartbeat_interval_s):
            try:
                self.send(MsgType.C2S_HEARTBEAT, self.server_id,
                          **({} if self._round is None
                             else {Message.ARG_ROUND: self._round}))
            except Exception:  # noqa: BLE001 — transport mid-shutdown
                return

    def finish(self) -> None:
        self._hb_stop.set()
        if self._hb_thread is not None:
            self._hb_thread.join(timeout=5)
            self._hb_thread = None
        super().finish()

    def _on_sync(self, msg: Message) -> None:
        if msg.get(Message.ARG_SHARD) is not None:
            self._on_shard_sync(msg)
            return
        params = msg.get(Message.ARG_MODEL_PARAMS)
        client_idx = msg.get(Message.ARG_CLIENT_INDEX)
        round_idx = msg.get(Message.ARG_ROUND)
        self._round = round_idx
        if self.on_accepted is not None:
            self.on_accepted(msg.get(Message.ARG_ACCEPTED))
        secagg_info = (msg.get(Message.ARG_SECAGG)
                       if self.secagg is not None else None)
        if self.secagg is not None and secagg_info is None:
            # a sync without masking parameters (e.g. the rejoin warm-up
            # sync) must NEVER fall through to a plaintext upload — that
            # is the one frame the whole protocol exists to prevent.
            # Bank the global and wait for the next masked broadcast.
            log.info("silo %d: sync without secagg parameters (rejoin "
                     "warm-up?); not uploading this round", self.node_id)
            return
        if secagg_info is not None:
            # advertise BEFORE training so the mask agreement overlaps
            # the local-SGD wall time instead of serializing after it
            from fedml_tpu.secure.protocol import MSG_SECAGG_ADVERT
            advert = self.secagg.begin_round(round_idx, secagg_info)
            self.send(MSG_SECAGG_ADVERT, self.server_id,
                      **{Message.ARG_SECAGG: advert,
                         Message.ARG_ROUND: round_idx})
        # deterministic span ids: a chaos-duplicated sync re-trains, but
        # its train/upload spans collapse onto the first delivery's
        with self._span("train", deterministic=True, round=round_idx,
                        client=client_idx):
            new_params, num_samples = self.train_fn(params, client_idx,
                                                    round_idx)
        upload = jax.tree.map(np.asarray, new_params)
        if secagg_info is not None:
            # the upload waits for the roster: masks are derived from the
            # FIXED cohort, so uploading pre-roster is impossible
            self._pending_upload = (round_idx, upload, float(num_samples))
            self._maybe_masked_upload()
            return
        if self.encode_upload is not None:
            upload = self.encode_upload(upload, params)
        with self._span("upload", deterministic=True, round=round_idx):
            self.send(MsgType.C2S_MODEL, self.server_id,
                      **{Message.ARG_MODEL_PARAMS: upload,
                         Message.ARG_NUM_SAMPLES: int(num_samples),
                         Message.ARG_ROUND: round_idx})

    # -- sharded wire (fedml_tpu/shard_spine) --------------------------------
    def _on_shard_sync(self, msg: Message) -> None:
        """Bank one broadcast shard slice; when the round's model is
        complete, train on the joined tree and upload it back as S
        slice frames (split by the plan spec shard 0's frame shipped —
        the silo derives everything from the wire)."""
        if self.secagg is not None or self.encode_upload is not None:
            raise ValueError(
                "sharded sync frames cannot compose with secagg or "
                "wire compression on the silo (masked/compressed "
                "payloads are whole-model by construction); this "
                "combination should have failed at config time")
        from fedml_tpu.shard_spine import SiloShardAssembler
        if self._shard_rx is None:
            self._shard_rx = SiloShardAssembler()
        round_idx = msg.get(Message.ARG_ROUND)
        meta = {}
        if msg.get(Message.ARG_CLIENT_INDEX) is not None:
            meta["client_idx"] = msg.get(Message.ARG_CLIENT_INDEX)
        if msg.get(Message.ARG_ACCEPTED) is not None:
            meta["accepted"] = msg.get(Message.ARG_ACCEPTED)
        done = self._shard_rx.offer(
            round_idx, msg.get(Message.ARG_SHARD),
            msg.get(Message.ARG_SHARD_COUNT),
            msg.get(Message.ARG_MODEL_PARAMS),
            msg.get(Message.ARG_SHARD_SPEC), meta=meta)
        if not done:
            return
        params, meta = self._shard_rx.take()
        self._round = round_idx
        if self.on_accepted is not None:
            self.on_accepted(meta.get("accepted"))
        client_idx = meta.get("client_idx")
        with self._span("train", deterministic=True, round=round_idx,
                        client=client_idx):
            new_params, num_samples = self.train_fn(params, client_idx,
                                                    round_idx)
        slices = self._shard_rx.split_upload(new_params)
        with self._span("upload", deterministic=True, round=round_idx):
            for s, sl in enumerate(slices):
                self.send(MsgType.C2S_MODEL, self.server_id,
                          **{Message.ARG_MODEL_PARAMS: sl,
                             Message.ARG_NUM_SAMPLES: int(num_samples),
                             Message.ARG_ROUND: round_idx,
                             Message.ARG_SHARD: s,
                             Message.ARG_SHARD_COUNT: len(slices)})

    # -- secure aggregation --------------------------------------------------
    def _on_secagg_roster(self, msg: Message) -> None:
        round_idx = msg.get(Message.ARG_ROUND)
        if self.secagg.on_roster(round_idx, msg.get(Message.ARG_SECAGG)):
            self._maybe_masked_upload()

    def _maybe_masked_upload(self) -> None:
        """Ship the trained update once BOTH the training and the roster
        have landed (either order — sync trains first, roster may beat
        or trail it)."""
        if self._pending_upload is None:
            return
        round_idx, update, num_samples = self._pending_upload
        if not self.secagg.has_roster(round_idx):
            return
        masked = self.secagg.mask(round_idx, update, num_samples)
        self._pending_upload = None
        with self._span("upload", deterministic=True, round=round_idx):
            self.send(MsgType.C2S_MODEL, self.server_id,
                      **{Message.ARG_MODEL_PARAMS: masked,
                         Message.ARG_NUM_SAMPLES: int(num_samples),
                         Message.ARG_ROUND: round_idx})

    def _on_secagg_unmask(self, msg: Message) -> None:
        from fedml_tpu.secure.protocol import MSG_SECAGG_SHARES, SecAggError
        round_idx = msg.get(Message.ARG_ROUND)
        info = msg.get(Message.ARG_SECAGG) or {}
        try:
            reveal = self.secagg.reveal(round_idx, info.get("survivors", []),
                                        info.get("dead", []))
        except SecAggError as e:
            # a malformed/adversarial request (e.g. naming a silo as both
            # survivor and dead): refuse loudly, reveal nothing
            log.error("silo %d: refusing unmask request for round %s: %s",
                      self.node_id, round_idx, e)
            return
        self.send(MSG_SECAGG_SHARES, self.server_id,
                  **{Message.ARG_SECAGG: reveal,
                     Message.ARG_ROUND: round_idx})
