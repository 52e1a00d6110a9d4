"""Zero-copy pipelined ingest (ISSUE 20): the `--ingest_pipeline`
receive path is BIT-IDENTICAL to inline — fold order per shard is
deterministic arrival order — while the transport thread only validates
headers and enqueues.

Fast tier: the arena's fused-screen numeric pin against the host path
in `robust/admission.py`, per-shard order preservation under
out-of-order arrivals, the backpressure bound + network-fault
dead-letter attribution, pipelined==inline bit-parity over the live
pump-mode federation (replicated, sharded, secagg ring-fold), the
kill-mid-queue journal-recovery composition (queued-but-unfolded
frames stay un-journaled, so recovery re-tasks exactly those silos),
the config-gate matrix, and the one-ledger-entry compile pin.  Fold
overlap, wall clock against network time and wire speed are timings: no
cell of the benchmark runs this path yet (PERF.md section 7).
"""

import threading

import jax
import numpy as np
import pytest

from fedml_tpu.algorithms.cross_silo import (FedAvgClientActor,
                                             FedAvgServerActor)
from fedml_tpu.comm.ingest import (ArenaScreen, IngestArena,
                                   IngestPipeline, OVERFLOW_REASON)
from fedml_tpu.comm.local import LocalHub
from fedml_tpu.comm.message import Message
from fedml_tpu.core.stream_agg import StreamingAggregator
from fedml_tpu.obs.telemetry import TelemetryRegistry
from fedml_tpu.robust.admission import AdmissionPipeline
from fedml_tpu.utils.checkpoint import RoundCheckpointer
from fedml_tpu.utils.journal import RoundJournal


def _params(seed=3, big=False):
    rng = np.random.RandomState(seed)
    if big:   # splittable under the shard planner's min_split_elems
        return {"dense": {"kernel": rng.randn(64, 8).astype(np.float32),
                          "bias": rng.randn(8).astype(np.float32)}}
    return {"dense": {"kernel": rng.randn(4, 3).astype(np.float32),
                      "bias": rng.randn(3).astype(np.float32)}}


def _train_fn(silo):
    def fn(params, client_idx, round_idx):
        rng = np.random.RandomState(1000 * silo + int(round_idx or 0))
        return jax.tree.map(
            lambda v: v + rng.randn(*np.shape(v)).astype(np.float32) * 0.1,
            params), 10 + silo
    return fn


def _leaves_equal(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _make_pipeline(**kw):
    kw.setdefault("registry", TelemetryRegistry())
    return IngestPipeline(**kw)


# ---------------------------------------------------------------------------
# the arena: fused screen vs the host path, structural fingerprint,
# zero-walk frame staging
# ---------------------------------------------------------------------------

class TestArena:
    def test_fused_screen_matches_host_norm(self):
        """The arena's one-reduction screen must agree with the host
        O(model) pass it replaces (`robust/admission.py` computes
        ||upload - global|| leaf-by-leaf in float32)."""
        ref = _params(1)
        upload = jax.tree.map(
            lambda v: v + np.float32(0.25) * np.sign(v), ref)
        arena = IngestArena(ref)
        assert arena.supported
        arena.round_start(ref)
        screen = arena.stage_tree(upload)
        assert screen.structural_ok and screen.finite
        host = float(np.sqrt(sum(
            float(np.sum((np.asarray(u, np.float64)
                          - np.asarray(r, np.float64)) ** 2))
            for u, r in zip(jax.tree.leaves(upload), jax.tree.leaves(ref)))))
        assert screen.norm == pytest.approx(host, rel=1e-5)
        # delta reference (round_start(None)): norm measures the payload
        arena.round_start(None)
        screen = arena.stage_tree(upload)
        flat = np.concatenate([np.asarray(l, np.float64).ravel()
                               for l in jax.tree.leaves(upload)])
        assert screen.norm == pytest.approx(float(np.linalg.norm(flat)),
                                            rel=1e-5)

    def test_nonfinite_flagged(self):
        ref = _params(1)
        arena = IngestArena(ref)
        bad = jax.tree.map(np.copy, ref)
        bad["dense"]["bias"][0] = np.nan
        screen = arena.stage_tree(bad)
        assert screen.structural_ok and not screen.finite

    def test_staged_tree_is_value_identical(self):
        """The device leaves the worker folds must be bit-equal to the
        frame's host views — the whole bit-parity contract rests here."""
        ref = _params(1)
        upload = _params(7)
        arena = IngestArena(ref)
        screen = arena.stage_tree(upload)
        assert _leaves_equal(screen.tree, upload)

    def test_structural_rejects_without_payload_read(self):
        ref = _params(1)
        arena = IngestArena(ref)
        # same shapes, different leaf keys: as strong as the host
        # params_fingerprint — still a reject
        renamed = {"dense": {"kernel2": ref["dense"]["kernel"],
                             "bias": ref["dense"]["bias"]}}
        assert arena.stage_tree(renamed).structural_ok is False
        wrong_shape = {"dense": {"kernel": ref["dense"]["kernel"][:2],
                                 "bias": ref["dense"]["bias"]}}
        assert arena.stage_tree(wrong_shape).structural_ok is False
        assert arena.stage_tree("garbage").structural_ok is False

    def test_stage_message_from_wire_frame(self):
        """The zero-walk path: a decoded frame's raw header + buffer
        views stage without materializing a host tree, and the staged
        values match the payload bit-for-bit."""
        ref = _params(1)
        upload = _params(9)
        arena = IngestArena(ref)
        msg = Message.from_bytes(
            Message(1, 2, 0).add("model_params", upload).to_bytes())
        screen = arena.stage_message(msg, "model_params")
        assert screen is not None and screen.structural_ok
        assert _leaves_equal(screen.tree, upload)
        # a frame whose payload is structurally foreign: reject from the
        # header alone
        other = Message.from_bytes(
            Message(1, 2, 0).add("model_params",
                                 {"w": np.ones(5, np.float32)}).to_bytes())
        assert arena.stage_message(other, "model_params").structural_ok \
            is False
        # an in-process object message has no raw frame: None = caller
        # falls back to stage_tree / the host path
        assert arena.stage_message(Message(1, 2, 0).add(
            "model_params", upload), "model_params") is None

    def test_non_float32_template_unsupported(self):
        arena = IngestArena({"m": np.zeros(4, np.uint32)})
        assert not arena.supported
        assert arena.stage_tree({"m": np.zeros(4, np.uint32)}) is None

    def test_single_compile_ledger_entry(self, tmp_path):
        """The arena split and the fused screen each key exactly ONE
        compile-ledger entry across uploads — the bench's 0-recompile
        gate, pinned in-process."""
        from fedml_tpu.obs.perf import PerfRecorder
        perf = PerfRecorder(str(tmp_path / "perf.jsonl"),
                            registry=TelemetryRegistry())
        arena = IngestArena(_params(1), perf=perf)
        for seed in (5, 6, 7):
            assert arena.stage_tree(_params(seed)).structural_ok
        sizes = perf.sentry.cache_sizes()
        assert sizes.get("ingest_screen") == 1
        assert sizes.get("ingest_arena") == 1


# ---------------------------------------------------------------------------
# the pipeline: per-shard FIFO, backpressure, failure surfacing
# ---------------------------------------------------------------------------

class TestPipeline:
    def test_per_shard_order_preserved_under_out_of_order_arrival(self):
        """Folds within a shard run in exactly arrival order even when
        arrivals interleave across shards arbitrarily — the determinism
        half of the bit-parity contract."""
        pipe = _make_pipeline(num_shards=3, depth=32)
        try:
            folded = {s: [] for s in range(3)}
            pipe.pause()   # hold everything queued, then release at once
            order = [(2, 0), (0, 0), (1, 0), (2, 1), (0, 1), (2, 2),
                     (1, 1), (0, 2), (1, 2), (2, 3)]
            for shard, seq in order:
                assert pipe.submit(
                    shard, (lambda s=shard, q=seq: folded[s].append(q)))
            pipe.resume()
            assert pipe.drain() == len(order)
            for s in range(3):
                want = [q for sh, q in order if sh == s]
                assert folded[s] == want
        finally:
            pipe.stop()

    def test_backpressure_bound_and_network_fault_attribution(self):
        """A full queue bounds memory: the overflow frame is dead-
        lettered (``fedml_comm_dead_letter_total{reason=
        "ingest_overflow"}`` + the fault feed's NETWORK attribution) and
        the task is NEVER silently run or dropped without the books
        knowing."""
        reg = TelemetryRegistry()
        faults = []
        pipe = IngestPipeline(num_shards=1, depth=2, registry=reg,
                              fault_feed=lambda r, d: faults.append((r, d)))
        try:
            gate, started = threading.Event(), threading.Event()
            ran = []

            def _block():
                started.set()
                gate.wait(timeout=30)
                ran.append("head")

            pipe.submit(0, _block)
            assert started.wait(timeout=10)   # worker busy, queue empty
            assert pipe.submit(0, lambda: ran.append("a"))
            assert pipe.submit(0, lambda: ran.append("b"))
            # queue full (depth=2): the next frame is load-shed
            dropped = pipe.submit(0, lambda: ran.append("DROPPED"),
                                  detail="silo 7 round 3")
            assert dropped is False
            assert faults == [(OVERFLOW_REASON, "silo 7 round 3")]
            gate.set()
            pipe.drain()
            assert ran == ["head", "a", "b"]   # the shed task never ran
            counters = reg.snapshot()["counters"]
            dead = [v for k, v in counters.items()
                    if "dead_letter" in k and OVERFLOW_REASON in k]
            assert dead == [1.0]
            over = [v for k, v in counters.items()
                    if "ingest_overflow_total" in k]
            assert over == [1.0]
            enq = [v for k, v in counters.items()
                   if "ingest_enqueued_total" in k]
            assert enq == [3.0]
        finally:
            pipe.stop()

    def test_wave_path_blocks_instead_of_shedding(self):
        """``submit_wait`` (the cross-device producer): backpressure
        means WAIT — a server-produced wave is never a droppable
        network frame."""
        pipe = _make_pipeline(num_shards=1, depth=1)
        try:
            gate, started = threading.Event(), threading.Event()
            pipe.submit(0, lambda: (started.set(), gate.wait(30)))
            assert started.wait(timeout=10)
            pipe.submit(0, lambda: None)   # queue now full
            done = []
            t = threading.Thread(
                target=lambda: (pipe.submit_wait(0, lambda: None),
                                done.append(True)))
            t.start()
            t.join(timeout=0.3)
            assert t.is_alive() and not done   # producer paced, not shed
            gate.set()
            t.join(timeout=10)
            assert done == [True]
            pipe.drain()
        finally:
            pipe.stop()

    def test_worker_exception_fails_the_drain_loudly(self):
        pipe = _make_pipeline(num_shards=1, depth=4)
        try:
            pipe.submit(0, lambda: 1 / 0)
            with pytest.raises(RuntimeError, match="fold worker died"):
                pipe.drain()
        finally:
            pipe.stop()

    def test_construction_and_shard_bounds(self):
        with pytest.raises(ValueError, match="num_shards"):
            _make_pipeline(num_shards=0)
        with pytest.raises(ValueError, match="ingest_queue_depth"):
            _make_pipeline(depth=0)
        pipe = _make_pipeline(num_shards=2)
        try:
            with pytest.raises(ValueError, match="shard 2"):
                pipe.submit(2, lambda: None)
            with pytest.raises(ValueError, match="1 arenas for 2 shard"):
                pipe.attach_arenas([None])
        finally:
            pipe.stop()


# ---------------------------------------------------------------------------
# pipelined == inline bit-parity over the live pump-mode federation
# ---------------------------------------------------------------------------

def _run_replicated(init, rounds, n=3, pipelined=False, jr=None, ck=None):
    hub = LocalHub(codec_roundtrip=True)
    stream = StreamingAggregator(init, method="mean", kind="params",
                                 norm_clip=1.0, seed=0, reservoir_k=8)
    adm = AdmissionPipeline(init, kind="params")
    ing = None
    if pipelined:
        ing = _make_pipeline(num_shards=1, depth=8)
        ing.attach_arenas([IngestArena(init)])
    server = FedAvgServerActor(
        hub.transport(0), init, n, n, rounds, stream_agg=stream,
        admission=adm, journal=jr, checkpointer=ck, ingest=ing)
    silos = [FedAvgClientActor(i, hub.transport(i), _train_fn(i))
             for i in range(1, n + 1)]
    server.register_handlers()
    for s in silos:
        s.register_handlers()
    server.start()
    hub.pump(idle_hook=(ing.drain if ing is not None else None))
    if ing is not None:
        ing.stop()
    return server


class TestBitParity:
    def test_replicated_stream(self):
        init = _params(3)
        inline = _run_replicated(init, 3)
        piped = _run_replicated(init, 3, pipelined=True)
        assert piped.round_idx == inline.round_idx == 3
        assert _leaves_equal(piped.params, inline.params)

    def test_sharded_wire(self):
        from fedml_tpu.shard_spine import build_shard_spine
        init = _params(3, big=True)

        def run(pipelined):
            hub = LocalHub(codec_roundtrip=True)
            spine = build_shard_spine(init, num_shards=2, norm_clip=0.0,
                                      fused="off", min_split_elems=64,
                                      mesh=None)
            ing = None
            if pipelined:
                ing = _make_pipeline(num_shards=spine.num_shards, depth=8)
                ing.attach_arenas(
                    [IngestArena(sl, name=f"ingest_s{s}") for s, sl in
                     enumerate(spine.broadcast_slices(init))])
            server = FedAvgServerActor(
                hub.transport(0), init, 3, 3, 2, stream_agg=spine.agg,
                shard_wire=spine, ingest=ing)
            silos = [FedAvgClientActor(i, hub.transport(i), _train_fn(i))
                     for i in range(1, 4)]
            server.register_handlers()
            for s in silos:
                s.register_handlers()
            server.start()
            hub.pump(idle_hook=(ing.drain if ing is not None else None))
            if ing is not None:
                ing.stop()
            return server

        inline, piped = run(False), run(True)
        assert piped.round_idx == inline.round_idx == 2
        assert _leaves_equal(piped.params, inline.params)

    def test_cross_device_waves(self):
        """The wave engine folds a finished wave on the pipeline's worker
        while the next wave trains: the same global as folding inline."""
        from fedml_tpu.algorithms.cross_device import (CrossDevice,
                                                       CrossDeviceConfig)
        from fedml_tpu.data import load_data
        from fedml_tpu.experiments.models import (create_workload,
                                                  sample_shape_of)
        data = load_data("mnist", data_dir=None, batch_size=4,
                         num_clients=12, seed=0)
        wl = create_workload("lr", "mnist", data.class_num,
                             sample_shape_of(data))
        cfg = CrossDeviceConfig(comm_round=3, client_num_per_round=10,
                                epochs=1, batch_size=4, wave_size=4,
                                seed=0, frequency_of_the_test=10)

        def run(pipelined):
            ing = _make_pipeline(num_shards=1, depth=8) \
                if pipelined else None
            try:
                return CrossDevice(wl, data, cfg, ingest=ing).run()
            finally:
                if ing is not None:
                    ing.stop()

        assert _leaves_equal(run(True), run(False))

    def test_secagg_ring_fold(self):
        """Masked uploads ride the pipeline WITHOUT an arena (uint32 by
        construction): the worker ring-folds at arrival in arrival
        order, and the unmasked global is bit-equal to inline."""
        from fedml_tpu.secure.protocol import SecAggClient, SecAggServer

        def run(pipelined):
            init = {"w": np.zeros(6, np.float32),
                    "v": np.zeros(2, np.float32)}
            hub = LocalHub(codec_roundtrip=True)
            ing = _make_pipeline(num_shards=1, depth=8) \
                if pipelined else None
            server = FedAvgServerActor(
                hub.transport(0), init, 4, 4, 2,
                secagg=SecAggServer(threshold=0, clip=8.0,
                                    weight_cap=20.0),
                ingest=ing)
            silos = [FedAvgClientActor(i, hub.transport(i), _train_fn(i),
                                       secagg=SecAggClient(i))
                     for i in range(1, 5)]
            server.register_handlers()
            for s in silos:
                s.register_handlers()
            server.start()
            hub.pump(idle_hook=(ing.drain if ing is not None else None))
            if ing is not None:
                ing.stop()
            return server

        inline, piped = run(False), run(True)
        assert piped.round_idx == inline.round_idx == 2
        assert _leaves_equal(piped.params, inline.params)


# ---------------------------------------------------------------------------
# kill-mid-queue: the journal's durable-prefix recovery composes
# ---------------------------------------------------------------------------

class TestKillMidQueue:
    def test_queued_frames_stay_unjournaled_and_recovery_retasks_them(
            self, tmp_path):
        """A kill with frames still QUEUED (validated + enqueued, never
        folded) journals nothing for them — `note_accept` runs on the
        fold worker, after the fold.  Recovery therefore re-tasks
        exactly the un-journaled silos and lands on the uncrashed
        final, bit-identical."""
        init = _params(3)
        want = _run_replicated(init, 2).params

        hub = LocalHub(codec_roundtrip=True)
        stream = StreamingAggregator(init, method="mean", kind="params",
                                     norm_clip=1.0, seed=0, reservoir_k=8)
        ing = _make_pipeline(num_shards=1, depth=8)
        ing.attach_arenas([IngestArena(init)])
        jr = RoundJournal(str(tmp_path / "j"), snapshot_every=1)
        ck = RoundCheckpointer(str(tmp_path / "ck"), save_every=1)
        server = FedAvgServerActor(
            hub.transport(0), init, 3, 3, 2, stream_agg=stream,
            admission=AdmissionPipeline(init, kind="params"),
            journal=jr, checkpointer=ck, ingest=ing)
        silos = [FedAvgClientActor(i, hub.transport(i), _train_fn(i))
                 for i in range(1, 4)]
        server.register_handlers()
        for s in silos:
            s.register_handlers()
        server.start()
        # deliver the 3 broadcasts (each trains its silo and enqueues
        # its upload) plus silo 1's upload, then fold ONLY that one
        hub.pump(max_messages=4)
        ing.drain()
        # hold the workers; the remaining two uploads arrive and sit in
        # the queue — validated, enqueued, NEVER folded
        ing.pause()
        hub.pump()
        # the kill: read what a fresh process would recover.  The
        # durable set is exactly the folded prefix — the queued silos
        # are un-journaled by construction.
        rec = RoundJournal(str(tmp_path / "j")).recover()
        assert rec is not None and rec.resumable
        assert [s for s, _, _ in rec.folded] == [1]
        # resume on fresh actors: the un-journaled silos {2, 3} are
        # re-tasked and the final equals the uncrashed run's, bit-equal
        resumed = _run_replicated(
            init, 2,
            jr=RoundJournal(str(tmp_path / "j"), snapshot_every=1),
            ck=RoundCheckpointer(str(tmp_path / "ck"), save_every=1),
            pipelined=True)
        assert resumed.round_idx == 2
        assert _leaves_equal(resumed.params, want)


# ---------------------------------------------------------------------------
# config gates: every unproven combination refuses loudly by name
# ---------------------------------------------------------------------------

_BASE = ["--model", "lr", "--dataset", "mnist",
         "--client_num_in_total", "4", "--client_num_per_round", "4",
         "--comm_round", "1", "--batch_size", "4", "--epochs", "1",
         "--log_stdout", "false"]


class TestConfigGates:
    @pytest.mark.parametrize("argv,match", [
        (["--algo", "fedavg", "--ingest_pipeline", "true"],
         "no ingest hot path"),
        (["--algo", "cross_silo", "--ingest_pipeline", "true",
          "--wire_compression", "int8"], "wire_compression"),
        (["--algo", "cross_silo", "--ingest_pipeline", "true",
          "--edge_aggregators", "2"], "edge_aggregators"),
        (["--algo", "cross_silo", "--ingest_pipeline", "true",
          "--chaos_drop", "0.1"], "chaos"),
        (["--algo", "cross_silo", "--ingest_pipeline", "true",
          "--agg_mode", "stack"], "stream"),
        (["--algo", "cross_silo", "--ingest_queue_depth", "0"],
         "ingest_queue_depth"),
    ])
    def test_unproven_combination_refused(self, argv, match):
        from fedml_tpu.experiments.main import main
        with pytest.raises(ValueError, match=match):
            main(argv + _BASE)

    def test_faultline_refused_at_the_actor(self):
        from fedml_tpu.robust.faultline import Faultline
        ing = _make_pipeline(num_shards=1)
        try:
            with pytest.raises(ValueError, match="mutually"):
                FedAvgServerActor(
                    LocalHub().transport(0), _params(), 3, 3, 1,
                    stream_agg=StreamingAggregator(
                        _params(), method="mean", kind="params"),
                    journal=None, faultline=Faultline(), ingest=ing)
        finally:
            ing.stop()
