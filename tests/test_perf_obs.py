"""Performance flight recorder + trend gate (fedml_tpu/obs/perf.py,
fedml_tpu/obs/trend.py) — the ISSUE 6 acceptance pins:

* ledger schema: every ``perf.jsonl`` line carries round / phases /
  wire deltas / RSS watermark / recompile verdict, written as ONE
  append so readers tolerate at most a torn tail;
* RSS sampler: start/stop idempotent, no thread leaks, per-round
  watermark protocol;
* recompile sentry: silent across clean rounds, fires on a forced
  re-jit, hard-fails under strict mode BEFORE a misleading clean
  ledger line can be written;
* trend gate: passes on identical ledgers, fails (named phase,
  non-zero exit) on a seeded +50% regression;
* SLO evaluator: breach counters + the serve frontend's
  ``/healthz?deep=1`` path (200 holding, 503 + verdict on breach).
"""

import http.client
import json
import os
import threading

import numpy as np
import pytest

from fedml_tpu.obs import telemetry, trend
from fedml_tpu.obs.perf import (DEFAULT_SLOS, PerfRecorder, RecompileError,
                                RecompileSentry, RssSampler, SloEvaluator,
                                histogram_quantile, parse_slo_spec,
                                read_rss_bytes)


class _FakeJit:
    """A hot function whose jit cache the test grows at will."""

    def __init__(self, n=1):
        self.n = n

    def _cache_size(self):
        return self.n


def _reg():
    return telemetry.TelemetryRegistry()


# ---------------------------------------------------------------------------
# ledger schema + atomic writes
# ---------------------------------------------------------------------------

def test_ledger_schema_and_per_round_lines(tmp_path):
    reg = _reg()
    out = reg.counter("fedml_comm_send_bytes_total", link="0->1")
    inn = reg.counter("fedml_comm_wire_bytes_total", link="1->0")
    rec = PerfRecorder(str(tmp_path / "perf.jsonl"), node="server",
                       registry=reg)
    try:
        for r in range(2):
            rec.round_start(r)
            out.inc(100)
            inn.inc(40)
            with rec.phase("broadcast_serialize"):
                pass
            # re-entering a phase ACCUMULATES (admission runs per upload)
            rec.add_phase("admission", 0.01)
            rec.add_phase("admission", 0.02)
            line = rec.round_end(r, quorum=3)
            assert line["quorum"] == 3
    finally:
        rec.close()

    with open(rec.path) as f:
        rows = [json.loads(l) for l in f]          # every line parses
    assert [r["round"] for r in rows] == [0, 1]
    assert trend.validate_ledger(rows) == []       # full schema
    for row in rows:
        assert row["node"] == "server"
        assert row["round_s"] > 0
        assert row["phases"]["admission"] == pytest.approx(0.03)
        assert "broadcast_serialize" in row["phases"]
        # wire deltas are PER ROUND, not cumulative
        assert row["wire"] == {"bytes_out": 100, "bytes_in": 40}
        assert row["recompiles"] == 0
        if read_rss_bytes() is not None:           # Linux: watermark real
            assert row["rss"]["peak_bytes"] > 0
    # phase histograms + round counter exported
    snap = reg.snapshot()
    assert snap["counters"]["fedml_perf_rounds_total"] == 2
    assert any(k.startswith("fedml_perf_phase_seconds")
               for k in snap["histograms"])


def test_ledger_round_end_without_start_is_noop(tmp_path):
    rec = PerfRecorder(str(tmp_path / "perf.jsonl"), registry=_reg())
    try:
        assert rec.round_end(0) is None
        assert not os.path.exists(rec.path)
    finally:
        rec.close()


def test_ledger_reader_tolerates_torn_tail_only(tmp_path):
    rec = PerfRecorder(str(tmp_path / "perf.jsonl"), registry=_reg())
    try:
        for r in range(3):
            rec.round_start(r)
            rec.round_end(r)
    finally:
        rec.close()
    with open(rec.path, "a") as f:
        f.write('{"round": 3, "pha')          # crash mid-write
    rows = trend.load_ledger(rec.path)
    assert [r["round"] for r in rows] == [0, 1, 2]
    # a torn line ANYWHERE ELSE is corruption, not a crash artifact
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"round": 0}\n{"torn\n{"round": 2}\n')
    with pytest.raises(ValueError, match="malformed"):
        trend.load_ledger(str(bad))


# ---------------------------------------------------------------------------
# RSS sampler
# ---------------------------------------------------------------------------

def test_rss_sampler_lifecycle_no_thread_leak():
    def sampler_threads():
        return [t for t in threading.enumerate()
                if t.name == "perf-rss-sampler"]

    n0 = len(sampler_threads())
    s = RssSampler(interval_s=0.005)
    s.start()
    s.start()                              # idempotent
    if read_rss_bytes() is None:
        pytest.skip("no /proc on this platform")
    assert len(sampler_threads()) == n0 + 1
    s.sample()
    assert s.peak_bytes > 0
    first = s.reset_peak()
    assert first > 0
    # after a reset the watermark restarts from a FRESH sample, not 0
    s.sample()
    assert s.peak_bytes > 0
    s.stop()
    s.stop()                               # idempotent
    assert len(sampler_threads()) == n0    # joined, not leaked


def test_recorder_close_stops_sampler(tmp_path):
    rec = PerfRecorder(str(tmp_path / "p.jsonl"), registry=_reg())
    rec.round_start(0)                     # starts the sampler thread
    rec.round_end(0)
    rec.close()
    rec.close()                            # safe to call twice
    assert not any(t.name == "perf-rss-sampler"
                   for t in threading.enumerate())


# ---------------------------------------------------------------------------
# recompile sentry
# ---------------------------------------------------------------------------

def test_sentry_silent_on_clean_rounds_counts_growth():
    reg = _reg()
    sentry = RecompileSentry(registry=reg)
    fn = _FakeJit(1)
    assert sentry.register("agg", fn)
    assert sentry.check(0) == {}           # baseline round
    for r in (1, 2, 3):
        assert sentry.check(r) == {}       # 3 clean rounds: silent
    fn.n = 3
    assert sentry.check(4) == {"agg": 2}
    assert reg.snapshot()["counters"]["fedml_perf_recompiles_total"] == 2
    # a shrunk cache (explicit clear) re-baselines silently
    fn.n = 1
    assert sentry.check(5) == {}
    fn.n = 2
    assert sentry.check(6) == {"agg": 1}


def test_sentry_fires_on_forced_rejit():
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 2.0)
    f(jnp.ones((4,)))
    sentry = RecompileSentry(registry=_reg())
    if not sentry.register("f", f):
        pytest.skip("this jax version exposes no _cache_size probe")
    assert sentry.check(0) == {}
    for r in (1, 2, 3):
        f(jnp.ones((4,)))                  # cache hit
        assert sentry.check(r) == {}
    f(jnp.ones((8,)))                      # new shape → retrace
    assert sentry.check(4) == {"f": 1}


def test_sentry_skips_functions_without_probe():
    sentry = RecompileSentry(registry=_reg())
    assert not sentry.register("plain", lambda x: x)
    assert sentry.names() == []
    assert sentry.check(0) == {}


def test_strict_sentry_raises_before_ledger_line(tmp_path):
    """The strict verdict must fire BEFORE the round's ledger line is
    written — a recompiling round must never ledger as clean."""
    rec = PerfRecorder(str(tmp_path / "perf.jsonl"), registry=_reg(),
                       strict_recompiles=True)
    fn = _FakeJit(1)
    assert rec.register_jit("agg", fn)
    try:
        rec.round_start(0)
        assert rec.round_end(0)["recompiles"] == 0   # baseline: fine
        rec.round_start(1)
        fn.n = 2
        with pytest.raises(RecompileError, match="retracing"):
            rec.round_end(1)
    finally:
        rec.close()
    rows = trend.load_ledger(rec.path)
    assert [r["round"] for r in rows] == [0]         # no misleading line


# ---------------------------------------------------------------------------
# trend gate
# ---------------------------------------------------------------------------

def _write_ledger(path, rows):
    with open(path, "w") as f:
        for row in rows:
            f.write(json.dumps(row) + "\n")
    return str(path)


def _rows(agg_s=0.2, n=4, recompiles=0):
    return [{"round": i, "round_s": agg_s + 0.1,
             "phases": {"defended_aggregate": agg_s,
                        "broadcast_serialize": 0.05},
             "wire": {"bytes_out": 10, "bytes_in": 10},
             "rss": {"peak_bytes": 1 << 20},
             "recompiles": recompiles if i else 0}
            for i in range(n)]


def test_trend_gate_passes_identical_fails_seeded_regression(tmp_path,
                                                             capsys):
    base = _write_ledger(tmp_path / "base.jsonl", _rows(0.2))
    same = _write_ledger(tmp_path / "same.jsonl", _rows(0.2))
    slow = _write_ledger(tmp_path / "slow.jsonl", _rows(0.3))  # +50%

    assert trend.main(["--ledger", same, "--baseline", base]) == 0
    assert trend.main(["--ledger", slow, "--baseline", base]) == 1
    out = capsys.readouterr().out
    assert "phase regression: defended_aggregate" in out
    assert "1.50x" in out


def test_trend_gate_noise_band_and_abs_floor(tmp_path):
    base = _rows(0.2)
    # +20% stays inside the default +25% band
    within = _write_ledger(tmp_path / "w.jsonl", _rows(0.24))
    basep = _write_ledger(tmp_path / "b.jsonl", base)
    assert trend.main(["--ledger", within, "--baseline", basep]) == 0
    # a 2ms phase doubling trips the relative band but not the absolute
    # floor — noise, not a regression
    tiny_b = _write_ledger(tmp_path / "tb.jsonl", [
        {**r, "phases": {"publish": 0.002}} for r in base])
    tiny_c = _write_ledger(tmp_path / "tc.jsonl", [
        {**r, "phases": {"publish": 0.004}} for r in base])
    assert trend.main(["--ledger", tiny_c, "--baseline", tiny_b]) == 0


def test_trend_gate_recompile_after_round0_fails(tmp_path, capsys):
    led = _write_ledger(tmp_path / "r.jsonl", _rows(0.2, recompiles=1))
    assert trend.main(["--ledger", led]) == 1
    assert "recompile gate" in capsys.readouterr().out
    assert trend.main(["--ledger", led, "--no_recompile_gate"]) == 0


def test_trend_gate_missing_inputs_exit_2(tmp_path, capsys):
    assert trend.main(["--ledger", str(tmp_path / "absent.jsonl")]) == 2
    assert trend.main([]) == 2
    capsys.readouterr()


def test_trend_schema_validation_names_missing_keys(tmp_path):
    rows = [{"round": 0, "phases": {}}]            # no recompiles/wire
    problems = trend.validate_ledger(rows)
    assert any("recompiles" in p for p in problems)
    assert any("wire" in p for p in problems)
    assert trend.validate_ledger([]) == ["ledger is empty"]


# ---------------------------------------------------------------------------
# SLO evaluator + deep health
# ---------------------------------------------------------------------------

def test_histogram_quantile():
    assert histogram_quantile({}, 0.95) is None
    stats = {"count": 100, "max": 9.0,
             "buckets": {"0.1": 50, "0.5": 45, "1.0": 0, "+Inf": 5}}
    assert histogram_quantile(stats, 0.5) == pytest.approx(0.1)
    assert histogram_quantile(stats, 0.95) == pytest.approx(0.5)
    # the +Inf tail falls back to the observed max
    assert histogram_quantile(stats, 0.999) == pytest.approx(9.0)


def test_parse_slo_spec():
    assert parse_slo_spec("") == {}
    spec = parse_slo_spec("serve_shed_rate=0.01, quarantine_rate=2")
    assert spec == {"serve_shed_rate": 0.01, "quarantine_rate": 2.0}
    with pytest.raises(ValueError, match="unknown SLO"):
        parse_slo_spec("tpyo_rate=1")
    with pytest.raises(ValueError, match="name=value"):
        parse_slo_spec("just_a_name")


def test_slo_evaluator_breach_counters_and_overrides():
    reg = _reg()
    reg.counter("fedml_serve_requests_total").inc(100)
    reg.counter("fedml_serve_shed_total").inc(50)
    ev = SloEvaluator(registry=reg)
    verdict = ev.evaluate()
    assert set(verdict) == set(DEFAULT_SLOS)
    assert verdict["serve_shed_rate"]["value"] == pytest.approx(0.5)
    assert not verdict["serve_shed_rate"]["ok"]
    assert verdict["torn_frame_rate"]["ok"]       # no traffic: vacuous
    assert not ev.healthy()
    snap = reg.snapshot()
    assert snap["gauges"]["fedml_slo_serve_shed_ratio"] \
        == pytest.approx(0.5)
    breaches = [v for k, v in snap["counters"].items()
                if k.startswith("fedml_slo_breaches_total")
                and "serve_shed_rate" in k]
    assert breaches and breaches[0] >= 1
    # a deployment that tolerates 60% shed passes the same registry
    lax = SloEvaluator(registry=reg, thresholds={"serve_shed_rate": 0.6})
    assert lax.healthy()
    with pytest.raises(ValueError, match="unknown SLO"):
        SloEvaluator(registry=reg, thresholds={"nope": 1.0})


def test_slo_round_duration_p95_from_histograms():
    reg = _reg()
    h = reg.histogram("fedml_round_duration_seconds")
    for _ in range(20):
        h.observe(0.2)
    ev = SloEvaluator(registry=reg,
                      thresholds={"round_duration_p95_seconds": 0.1})
    verdict = ev.evaluate()
    assert verdict["round_duration_p95_seconds"]["value"] >= 0.2
    assert not verdict["round_duration_p95_seconds"]["ok"]


def _get(port, path):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
    conn.request("GET", path)
    resp = conn.getresponse()
    body = resp.read()
    conn.close()
    return resp.status, json.loads(body) if body.startswith(b"{") else body


def test_deep_healthz_http_path():
    from fedml_tpu.serve import MicroBatcher, ModelRegistry, ServeFrontend

    reg = _reg()
    slo = SloEvaluator(registry=reg)
    registry = ModelRegistry(lambda p, x: x, history=8)
    batcher = MicroBatcher(registry, buckets=(1,))
    frontend = ServeFrontend(registry, batcher, port=0, slo=slo).start()
    try:
        port = frontend.port
        registry.publish({"w": np.ones(2, np.float32)}, 0)
        # shallow stays shallow; deep evaluates and holds
        status, body = _get(port, "/healthz")
        assert status == 200 and "slo" not in body
        status, body = _get(port, "/healthz?deep=1")
        assert status == 200 and body["status"] == "ok"
        assert body["slo"]["serve_shed_rate"]["ok"]
        # breach the shed SLO → deep probes 503 with the verdict, so an
        # LB rotates out an instance that is up but violating objectives
        reg.counter("fedml_serve_requests_total").inc(100)
        reg.counter("fedml_serve_shed_total").inc(50)
        status, body = _get(port, "/healthz?deep=1")
        assert status == 503 and body["status"] == "slo_breach"
        assert not body["slo"]["serve_shed_rate"]["ok"]
        # shallow probes still answer 200 — liveness is not SLO health
        status, _ = _get(port, "/healthz")
        assert status == 200
    finally:
        frontend.stop(drain=False)


def test_deep_healthz_unconfigured():
    from fedml_tpu.serve import MicroBatcher, ModelRegistry, ServeFrontend

    registry = ModelRegistry(lambda p, x: x, history=8)
    frontend = ServeFrontend(registry, MicroBatcher(registry, buckets=(1,)),
                             port=0).start()
    try:
        registry.publish({"w": np.ones(2, np.float32)}, 0)
        status, body = _get(frontend.port, "/healthz?deep=1")
        assert status == 200 and body["deep"] == "unconfigured"
    finally:
        frontend.stop(drain=False)


# ---------------------------------------------------------------------------
# telemetry HTTP endpoint hardening (satellite: bind failure + /healthz)
# ---------------------------------------------------------------------------

def test_start_http_server_bind_failure_returns_none():
    reg = _reg()
    first = telemetry.start_http_server(0, reg, host="127.0.0.1")
    assert first is not None
    try:
        port = first.server_address[1]
        # same port again: warn-and-None, never an exception that would
        # kill a training run over its scrape endpoint
        assert telemetry.start_http_server(port, reg,
                                           host="127.0.0.1") is None
        # and the surviving server answers /healthz beside /metrics
        reg.counter("fedml_comm_send_total").inc(3)
        status, body = _get(port, "/healthz")
        assert status == 200 and body["status"] == "ok"
        status, body = _get(port, "/metrics")
        assert status == 200 and b"fedml_comm_send_total 3" in body
    finally:
        first.shutdown()
        first.server_close()


# ---------------------------------------------------------------------------
# report merger hardening (satellite: --merge_trace clean no-op)
# ---------------------------------------------------------------------------

def test_merge_trace_missing_or_empty_dir_is_clean_noop(tmp_path, capsys):
    from fedml_tpu.obs import report

    out = tmp_path / "merged.json"
    # missing dir: no output file, message instead of an error
    assert report.merge_traces(str(tmp_path / "absent"), str(out)) is None
    assert not out.exists()
    # empty dir: same
    empty = tmp_path / "empty"
    empty.mkdir()
    assert report.merge_traces(str(empty), str(out)) is None
    assert not out.exists()
    # the CLI stays exit-0 and says so
    assert report.main(["--merge_trace", str(out),
                        "--trace_dir", str(empty)]) == 0
    assert "nothing written" in capsys.readouterr().out
    assert report.main(["--merge_trace", str(out)]) == 0
    assert "nothing to merge" in capsys.readouterr().out


def test_ledger_rotates_previous_run_instead_of_appending(tmp_path):
    """Two runs at the same path must not splice into one ledger — the
    second run's compile-paying round 0 would land mid-file and poison
    the trend gate's skip-first-round medians."""
    path = str(tmp_path / "perf.jsonl")
    first = PerfRecorder(path, registry=_reg())
    try:
        first.round_start(0)
        first.round_end(0)
    finally:
        first.close()
    second = PerfRecorder(path, registry=_reg())
    try:
        second.round_start(0)
        second.round_end(0)
        second.round_start(1)
        second.round_end(1)
    finally:
        second.close()
    rows = trend.load_ledger(path)
    assert [r["round"] for r in rows] == [0, 1]    # second run only
    prev = trend.load_ledger(path + ".prev")       # first run preserved
    assert [r["round"] for r in prev] == [0]


def test_probe_paths_do_not_count_breaches():
    """Breach counting belongs to the round cadence: `healthy()` and
    `evaluate(count_breaches=False)` (the /healthz?deep=1 path) must
    read the objectives without ticking `fedml_slo_breaches_total` —
    otherwise one sustained breach counts once per LB probe instead of
    once per round and every "breaches > N" alert threshold breaks."""
    reg = _reg()
    reg.counter("fedml_serve_requests_total").inc(100)
    reg.counter("fedml_serve_shed_total").inc(50)
    ev = SloEvaluator(registry=reg)

    def breaches():
        return sum(v for k, v in reg.snapshot()["counters"].items()
                   if k.startswith("fedml_slo_breaches_total"))

    assert not ev.healthy()                        # query: no tick
    ev.evaluate(count_breaches=False)              # probe: no tick
    assert breaches() == 0
    ev.evaluate()                                  # round cadence: ticks
    assert breaches() == 1


def _live_round_phases(tmp_path, aggregate_fn, name):
    """One live 2-silo round through FedAvgServerActor with a recorder;
    returns the single ledger line's phase dict."""
    from fedml_tpu.algorithms.cross_silo import (FedAvgClientActor,
                                                 FedAvgServerActor)
    from fedml_tpu.comm.local import LocalHub

    hub = LocalHub()
    init = {"w": np.ones(4, np.float32)}
    rec = PerfRecorder(str(tmp_path / name), registry=_reg())
    server = FedAvgServerActor(hub.transport(0), init, 2, 2, 1,
                               aggregate_fn=aggregate_fn, perf=rec)
    server.register_handlers()
    silos = [FedAvgClientActor(i, hub.transport(i),
                               lambda p, c, r: (p, 5)) for i in (1, 2)]
    for s in silos:
        s.register_handlers()
    try:
        server.start()
        hub.pump()
    finally:
        rec.close()
    rows = trend.load_ledger(rec.path)
    assert len(rows) == 1
    return rows[0]["phases"]


def test_aggregate_phase_named_by_what_ran(tmp_path):
    """The ledger names the aggregate span by the code path that ran:
    plain `aggregate` without a defense, `defended_aggregate` only when
    a make_defended_aggregate product is wired — a defended run must
    never trend-compare against an undefended baseline under one
    label."""
    from fedml_tpu.robust.defense import make_defended_aggregate

    phases = _live_round_phases(tmp_path, None, "plain.jsonl")
    assert "aggregate" in phases
    assert "defended_aggregate" not in phases
    defended = make_defended_aggregate("mean", norm_clip=5.0)
    phases = _live_round_phases(tmp_path, defended, "defended.jsonl")
    assert "defended_aggregate" in phases
    assert "aggregate" not in phases


def test_trend_gate_single_round_ledger_is_not_a_regression(tmp_path,
                                                            capsys):
    """A one-round ledger's only line pays the jit compiles; gated
    against a steady-state baseline it must NOT read as a regression —
    the gate says there is nothing steady-state to compare and passes
    (the recompile/schema checks still ran)."""
    base = _write_ledger(tmp_path / "base.jsonl", _rows(0.2))
    smoke = _write_ledger(tmp_path / "smoke.jsonl", _rows(5.0, n=1))
    assert trend.main(["--ledger", smoke, "--baseline", base]) == 0
    assert "no steady-state rounds" in capsys.readouterr().out


def test_report_renders_explicit_perf_ledger_path(tmp_path):
    """`--perf_ledger` points the report at a ledger written outside
    run_dir; an explicitly named ledger with no rows must say so instead
    of silently rendering the run as uninstrumented."""
    from fedml_tpu.obs import report

    led = _write_ledger(tmp_path / "elsewhere.jsonl", _rows(0.2, n=2))
    text = report.render_report(str(tmp_path), None, perf_ledger=led)
    assert "perf ledger" in text
    assert "defended_aggregate"[:14] in text  # phase columns clip to 14
    missing = str(tmp_path / "nope.jsonl")
    text = report.render_report(str(tmp_path), None, perf_ledger=missing)
    assert f"no rows at {missing}" in text
