"""Round critical-path observatory (fedml_tpu/obs/critical_path.py,
ISSUE 17): the attribution sweep partitions a round's wall clock across
the constraint vocabulary, the binding constraint is named correctly
under seeded straggler / slow-fold shapes, the disabled mode stays
zero-allocation, the trend gate accepts both pre- and post-observatory
ledger shapes, and the config gates fail loud.
"""

import gc
import json
import tracemalloc

import numpy as np
import pytest

from fedml_tpu.comm.actors import NodeManager, ServerManager
from fedml_tpu.comm.local import LocalHub
from fedml_tpu.obs import critical_path as cpath
from fedml_tpu.obs import telemetry, trace, trend
from fedml_tpu.obs.perf import PerfRecorder


def _cp():
    """Accumulator with a pinned origin; samples pass explicit t1."""
    return cpath.RoundCriticalPath(t0=0.0, clock=lambda: 0.0)


# ---------------------------------------------------------------------------
# the attribution sweep
# ---------------------------------------------------------------------------

def test_attribution_partitions_wall_clock():
    """Every second of the round lands in exactly one constraint:
    sum(attribution) == round_s, coverage == 1.0 — the >= 0.95 bench
    gate holds by construction, not by luck."""
    cp = _cp()
    cp.note_arrival(t=1.0)
    cp.note("decode", 1.0, t1=2.0)
    cp.note_arrival(t=4.0)
    cp.note("fold", 3.0, t1=5.0)
    rec = cp.finalize(duration=10.0)
    assert sum(rec["attribution"].values()) == pytest.approx(10.0)
    assert rec["coverage"] == pytest.approx(1.0)
    assert rec["round_s"] == pytest.approx(10.0)
    assert rec["uploads"] == 2
    # [0,1) pre-first-arrival idle -> network; [1,2) decode; [2,5) fold;
    # [5,10) post-last-arrival idle -> barrier_wait
    assert rec["attribution"]["network"] == pytest.approx(1.0)
    assert rec["attribution"]["decode"] == pytest.approx(1.0)
    assert rec["attribution"]["fold"] == pytest.approx(3.0)
    assert rec["attribution"]["barrier_wait"] == pytest.approx(5.0)
    assert rec["binding"] == "barrier_wait"
    assert cpath.validate_record(rec) == []


def test_straggler_binding_under_seeded_slow_silo():
    """A quorum trickling in (first upload early, last upload late, the
    host idle in between) must name ``straggler``, not network."""
    cp = _cp()
    cp.note_arrival(t=1.0)
    cp.note("fold", 0.5, t1=1.5)
    cp.note_arrival(t=9.0)
    cp.note("fold", 0.5, t1=9.5)
    rec = cp.finalize(duration=10.0)
    assert rec["binding"] == "straggler"
    assert rec["attribution"]["straggler"] == pytest.approx(7.5)
    assert cpath.validate_record(rec) == []


def test_fold_binding_under_seeded_slow_fold():
    """A host that serializes a long fold after the last upload must
    name ``fold`` — and its fold-overlap ratio exposes that none of the
    fold hid behind the network."""
    cp = _cp()
    cp.note_arrival(t=0.5)
    cp.note_arrival(t=1.0)
    cp.note("fold", 7.9, t1=9.0)
    rec = cp.finalize(duration=9.5)
    assert rec["binding"] == "fold"
    assert rec["fold_overlap_ratio"] == pytest.approx(0.0)
    assert cpath.validate_record(rec) == []


def test_fold_overlap_ratio_full_when_fold_hides_behind_wire():
    """Fold busy time entirely inside the arrival window reads 1.0 —
    the aggregation-hidden-behind-the-network number."""
    cp = _cp()
    cp.note_arrival(t=1.0)
    cp.note("fold", 1.0, t1=2.0)
    cp.note_arrival(t=5.0)
    rec = cp.finalize(duration=6.0)
    assert rec["fold_overlap_ratio"] == pytest.approx(1.0)


def test_compile_carved_out_preserves_the_partition():
    """Known compile wall time relabels fold/decode work as ``compile``
    without changing the total."""
    cp = _cp()
    cp.note("fold", 4.0, t1=4.0)
    cp.note_arrival(t=4.0)
    rec = cp.finalize(duration=5.0, compile_s=1.5)
    assert rec["attribution"]["compile"] == pytest.approx(1.5)
    assert rec["attribution"]["fold"] == pytest.approx(2.5)
    assert sum(rec["attribution"].values()) == pytest.approx(5.0)
    assert cpath.validate_record(rec) == []


def test_overlapping_work_segments_take_priority_bucket():
    """Concurrent receive threads: a fold∩decode segment goes to fold
    (the work-priority order), and is never counted twice."""
    cp = _cp()
    cp.note("decode", 2.0, t1=2.0)
    cp.note("fold", 2.0, t1=3.0)     # [1,3) overlaps decode on [1,2)
    cp.note_arrival(t=3.0)
    rec = cp.finalize(duration=3.0)
    assert rec["attribution"]["decode"] == pytest.approx(1.0)
    assert rec["attribution"]["fold"] == pytest.approx(2.0)
    assert sum(rec["attribution"].values()) == pytest.approx(3.0)


def test_phase_vocabulary_mapping():
    """straggler_wait (an idle measurement) is excluded; unknown phase
    names land in fold (host-side round work); the mapped names agree
    with the constraint vocabulary."""
    assert cpath.phase_bucket("straggler_wait") is None
    assert cpath.phase_bucket("some_future_phase") == "fold"
    assert cpath.phase_bucket("decode") == "decode"
    assert cpath.phase_bucket("broadcast_serialize") == "network"
    assert cpath.phase_bucket("admission") == "admission"
    # a cross-device "wave" span is the server *producing* an upload —
    # it plays the network's role in the round (the fold either hides
    # behind it, pipelined, or doesn't), so it buckets as network
    assert cpath.phase_bucket("wave") == "network"
    for name in ("fold", "journal", "unmask", "shard_finalize"):
        assert cpath.phase_bucket(name) == "fold"
    cp = _cp()
    cp.note("straggler_wait", 5.0, t1=5.0)
    rec = cp.finalize(duration=5.0)
    assert "fold" not in rec["attribution"]


def test_validate_record_rejects_malformed_records():
    assert cpath.validate_record("nope") == ["critical_path: not a dict"]
    bad_binding = {"binding": "vibes", "attribution": {}, "coverage": 1.0,
                   "round_s": 1.0}
    assert any("binding" in p for p in cpath.validate_record(bad_binding))
    lying_coverage = {"binding": "fold", "attribution": {"fold": 0.2},
                      "coverage": 1.0, "round_s": 1.0}
    assert any("coverage" in p
               for p in cpath.validate_record(lying_coverage))
    unknown_key = {"binding": "fold", "attribution": {"gremlins": 0.5},
                   "coverage": 0.5, "round_s": 1.0}
    assert any("gremlins" in p for p in cpath.validate_record(unknown_key))


# ---------------------------------------------------------------------------
# telemetry export
# ---------------------------------------------------------------------------

def test_ingest_gauges_export():
    reg = telemetry.TelemetryRegistry()
    gauges = cpath.IngestGauges(reg)
    rec = {"binding": "fold", "round_s": 2.0, "uploads": 3,
           "fold_overlap_ratio": 0.75,
           "attribution": {"fold": 1.0, "network": 1.0}, "coverage": 1.0}
    gauges.export(rec, wire_bytes_in=4000)
    snap = reg.snapshot()
    assert snap["gauges"][
        "fedml_ingest_bytes_per_second_value"] == pytest.approx(2000.0)
    assert snap["gauges"][
        "fedml_ingest_fold_overlap_ratio"] == pytest.approx(0.75)
    assert snap["gauges"][
        'fedml_ingest_phase_utilization_ratio{constraint="fold"}'] == \
        pytest.approx(0.5)
    assert snap["gauges"][
        'fedml_ingest_phase_utilization_ratio{constraint="decode"}'] == 0.0
    assert snap["counters"]["fedml_ingest_uploads_total"] == 3


def test_perf_recorder_emits_critical_path_on_every_line(tmp_path):
    """The analyzer rides PerfRecorder: every round_end line carries a
    valid critical_path record, and the ingest gauges land in the SAME
    registry the recorder exports."""
    reg = telemetry.TelemetryRegistry()
    rec = PerfRecorder(str(tmp_path / "perf.jsonl"), registry=reg)
    try:
        for r in range(2):
            rec.round_start(r)
            rec.add_phase("decode", 0.002)
            rec.note_arrival()
            rec.add_phase("fold", 0.003)
            rec.round_end(r)
    finally:
        rec.close()
    with open(rec.path) as f:
        rows = [json.loads(line) for line in f]
    assert len(rows) == 2
    for row in rows:
        cp = row["critical_path"]
        assert cpath.validate_record(cp) == []
        assert cp["coverage"] >= 0.95
        assert cp["uploads"] == 1
        assert cp["binding"] in cpath.CONSTRAINTS
    assert trend.validate_ledger(rows) == []
    assert "fedml_ingest_uploads_total" in reg.snapshot()["counters"]


@pytest.mark.parametrize("traced", [False, True])
def test_live_federation_rounds_carry_critical_path(traced, tmp_path):
    """End to end on the actor path: a local 2-silo federation with the
    flight recorder writes a critical_path record on every ledger line,
    with one arrival per upload and >= 95% coverage; traced, the
    streaming receive path also leaves its per-upload `ingest:` spans."""
    from fedml_tpu.algorithms.cross_silo import (FedAvgClientActor,
                                                 FedAvgServerActor)
    from fedml_tpu.core.stream_agg import StreamingAggregator
    reg = telemetry.TelemetryRegistry()
    perf = PerfRecorder(str(tmp_path / "perf.jsonl"), registry=reg)
    hub = LocalHub(codec_roundtrip=True)
    rng = np.random.RandomState(0)
    params = {"w": rng.randn(3, 2).astype(np.float32)}

    def train_fn(p, client_idx, round_idx):
        import jax
        return jax.tree.map(lambda v: v + 1.0, p), 10

    tracer = trace.enable() if traced else None     # actors read it once
    try:
        server = FedAvgServerActor(
            hub.transport(0), params, client_num_in_total=2,
            client_num_per_round=2, num_rounds=2, perf=perf,
            stream_agg=StreamingAggregator(params) if traced else None)
        server.register_handlers()
        silos = [FedAvgClientActor(i, hub.transport(i), train_fn)
                 for i in (1, 2)]
        for s in silos:
            s.register_handlers()
        server.start()
        hub.pump()
    finally:
        trace.disable()
    perf.close()
    if traced:
        names = [s["name"] for s in tracer.spans]
        assert names.count("ingest:fold") == 4      # one an upload
        assert any(n.startswith("recv:") for n in names)
    rows = trend.load_ledger(perf.path)
    assert len(rows) == 2
    assert trend.validate_ledger(rows) == []
    for row in rows:
        cp = row["critical_path"]
        assert cp["uploads"] == 2
        assert cp["coverage"] >= 0.95
        assert cp["binding"] in cpath.CONSTRAINTS


# ---------------------------------------------------------------------------
# the cost contract: disabled mode
# ---------------------------------------------------------------------------

def test_disabled_span_helpers_reuse_the_shared_null_context():
    """With tracing and perf off, the instrumented helpers return the
    ONE module-level null context — identity, not equality."""
    assert trace.get_tracer() is None

    class Probe(ServerManager):
        def register_handlers(self):
            pass

    hub = LocalHub()
    mgr = Probe(0, hub.transport(0))
    assert mgr._span("ingest:fold", deterministic=True) \
        is trace.NULL_CONTEXT
    assert mgr._root_span("round") is trace.NULL_CONTEXT
    assert mgr._perf_phase("fold") is trace.NULL_CONTEXT


def test_disabled_mode_is_zero_allocation():
    """The pin behind the bench's disabled-overhead gate: exercising the
    ingest span + arrival helpers with observability off retains NOTHING
    (transients may spike; retained delta must be zero)."""
    assert trace.get_tracer() is None

    class Probe(ServerManager):
        def register_handlers(self):
            pass

    hub = LocalHub()
    mgr = Probe(0, hub.transport(0))
    # the cross-device round path's one timing-site helper with nothing
    # reading it, and the staging spans it parents (ISSUE 27)
    from types import SimpleNamespace
    from fedml_tpu.algorithms.cross_device import CrossDevice
    engine = SimpleNamespace(_timed=False)

    def hot_path():
        for _ in range(200):
            with mgr._span("ingest:decode", deterministic=True):
                pass
            with mgr._perf_phase("decode"):
                pass
            mgr._note_arrival()
            with CrossDevice._span(engine, "wave", "wave", None) as site:
                assert site is None
            with trace.child("stage.gather"):
                pass

    # two warm-up passes: the second crosses the interpreter's adaptive
    # specialization threshold, so the measured pass is steady-state
    hot_path()
    hot_path()
    tracemalloc.start()
    gc.collect()
    before = tracemalloc.take_snapshot()
    hot_path()
    gc.collect()   # collectible cycles are transients, not retention
    after = tracemalloc.take_snapshot()
    tracemalloc.stop()
    # attribute retained bytes to the observatory's own code — the pin
    # is about what the disabled helpers keep, not interpreter noise
    # elsewhere in a busy pytest process
    flt = [tracemalloc.Filter(True, "*fedml_tpu*")]
    stats = after.filter_traces(flt).compare_to(
        before.filter_traces(flt), "lineno")
    retained = sum(s.size_diff for s in stats)
    assert retained <= 0, \
        f"disabled observability retained {retained} bytes: {stats[:5]}"


# ---------------------------------------------------------------------------
# trend gate: old and new ledger shapes
# ---------------------------------------------------------------------------

def _row(r, critical_path=None):
    row = {"round": r, "round_s": 0.2, "phases": {"fold": 0.1},
           "recompiles": 0, "wire": {"bytes_out": 10, "bytes_in": 10}}
    if critical_path is not None:
        row["critical_path"] = critical_path
    return row


def test_trend_gate_accepts_old_and_new_ledger_shapes():
    old = [_row(0), _row(1)]                      # pre-observatory
    assert trend.validate_ledger(old) == []
    good = {"binding": "fold", "attribution": {"fold": 0.2},
            "coverage": 1.0, "round_s": 0.2, "uploads": 2,
            "fold_overlap_ratio": 0.0}
    new = [_row(0, good), _row(1, good)]
    assert trend.validate_ledger(new) == []


def test_trend_gate_rejects_malformed_critical_path():
    bad = {"binding": "vibes", "attribution": {"fold": 0.2},
           "coverage": 1.0, "round_s": 0.2}
    problems = trend.validate_ledger([_row(0, bad)])
    assert problems and all("critical_path" in p for p in problems)


# ---------------------------------------------------------------------------
# config gates
# ---------------------------------------------------------------------------

class TestMetricsPortConfigGates:
    def test_metrics_port_prom_port_disagreement_fails_loud(self):
        from fedml_tpu.experiments.main import main
        with pytest.raises(ValueError, match="metrics_port"):
            main(["--algo", "cross_silo", "--metrics_port", "9001",
                  "--prom_port", "9002"])

    def test_metrics_endpoint_requires_live_registry(self):
        assert isinstance(telemetry.get_registry(), telemetry.NullRegistry)
        with pytest.raises(ValueError, match="telemetry is disabled"):
            telemetry.start_http_server(0, host="127.0.0.1")
