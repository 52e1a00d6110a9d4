"""The compiler's time as spans (ISSUE 41).

`fedml_tpu.obs.trace`'s one JAX-monitoring listener turns each stage of a
compile into a span (`jit.trace`, `jit.lower`, `jit.compile`) under the
`TimedSpan` site open on the compiling thread, else a root span of the
open recorder's tracer, else none, and keeps the process's totals either
way; `benchmark/setup_spans.py` reads the stages that ran before the
window.
"""

import json
import os
import time

import jax
import jax.monitoring as monitoring
import jax.numpy as jnp
import pytest

from fedml_tpu.obs import trace
from fedml_tpu.obs.perf import PerfRecorder

STAGES = {"jit.trace", "jit.lower", "jit.compile"}
BACKEND = "/jax/core/compile/backend_compile_duration"


def _stages(tracer):
    return [s for s in tracer.spans if s["name"] in STAGES]


def _under(spans, span_id):
    """Every span whose chain of parents reaches ``span_id``."""
    by_id = {s["span_id"]: s for s in spans}
    out = []
    for s in spans:
        p = s["parent_id"]
        while p is not None and p != span_id:
            p = by_id[p]["parent_id"] if p in by_id else None
        if p == span_id:
            out.append(s)
    return out


def test_a_fresh_jit_under_a_site_leaves_its_stages_under_it():
    trace.compile_totals()                   # the listener is there
    tr = trace.SpanTracer()
    scale = jnp.float32(5.0)

    @jax.jit
    def inner(x):
        return x * scale

    @jax.jit
    def outer(x):
        return inner(x) - 2

    with trace.TimedSpan(tr, "wave.dispatch") as site:
        outer(jnp.ones(3)).block_until_ready()
    stages = _stages(tr)
    assert {s["name"] for s in stages} == STAGES
    assert _under(tr.spans, site.span.span_id) == stages
    assert {s["trace_id"] for s in stages} == {site.span.trace_id}
    for s in stages:
        assert s["args"]["fun"]
        assert site.t0_ns <= s["t0_ns"]
        assert s["t0_ns"] + s["dur_ns"] <= site.t0_ns + site.dur_ns
    for s in (s for s in stages if s["name"] == "jit.compile"):
        assert s["args"]["cache_hit"] in (0, 1)
        assert s["args"]["cache_miss"] in (0, 1)
    # the inner jit is traced inside the outer's trace: its child
    by_fun = {(s["name"], s["args"]["fun"]): s for s in stages}
    assert by_fun[("jit.trace", "inner")]["parent_id"] \
        == by_fun[("jit.trace", "outer")]["span_id"]
    # so no two leaves of the thread overlap
    parents = {s["parent_id"] for s in tr.spans}
    leaves = sorted((s["t0_ns"], s["t0_ns"] + s["dur_ns"])
                    for s in tr.spans if s["span_id"] not in parents)
    for (_, end), (start, _) in zip(leaves, leaves[1:]):
        assert start >= end


def test_with_no_recorder_open_no_span_is_kept_but_totals_count(tmp_path):
    perf = PerfRecorder(str(tmp_path / "perf.jsonl"))
    try:
        before = trace.compile_totals()
        jax.jit(lambda x: x + 11)(jnp.ones(4)).block_until_ready()
        # no site open: a root of the open recorder's tracer
        roots = _stages(perf.tracer)
        assert {s["name"] for s in roots} == STAGES
        # the lambda's own stages are roots (`+` is traced inside it)
        assert [s["parent_id"] for s in roots
                if "<lambda>" in s["args"]["fun"]] == [None] * 3
    finally:
        perf.close()
    mid = trace.compile_totals()
    jax.jit(lambda x: x - 13)(jnp.ones(5)).block_until_ready()
    after = trace.compile_totals()
    assert len(_stages(perf.tracer)) == len(roots)   # closed: none kept
    for t0, t1 in ((before, mid), (mid, after)):
        assert t1["compiles"] > t0["compiles"]
        for k in ("trace_s", "lower_s", "compile_s"):
            assert t1[k] > t0[k], k


def test_the_cache_verdict_follows_the_two_events():
    """Driven by hand: a hit seen inside one backend compile, a write
    inside the next, nothing inside the third (as where no cache is on);
    each compile's verdict is its own."""
    trace.compile_totals()
    tr = trace.SpanTracer()
    before = trace.compile_totals()
    with trace.TimedSpan(tr, "eval"):
        for seen in ("cache_hits", "cache_misses", None):
            monitoring.record_scalar(BACKEND, time.time(), fun_name="f")
            if seen:
                monitoring.record_event(f"/jax/compilation_cache/{seen}")
            monitoring.record_event_duration_secs(BACKEND, 0.001,
                                                  fun_name="f")
    got = [(s["args"]["cache_hit"], s["args"]["cache_miss"])
           for s in _stages(tr)]
    assert got == [(1, 0), (0, 1), (0, 0)]
    after = trace.compile_totals()
    assert after["cache_hits"] - before["cache_hits"] == 1
    assert after["cache_misses"] - before["cache_misses"] == 1
    assert after["compiles"] - before["compiles"] == 3


# -- the benchmark's reader ----------------------------------------------------

def _event(name, t0_s, dur_s, span_id, parent=None, **args):
    return {"name": name, "ph": "X", "ts": 0, "dur": 0, "pid": 0, "tid": 1,
            "args": {"trace_id": "s", "span_id": span_id,
                     "parent_id": parent, "t0_ns": int(t0_s * 1e9),
                     "dur_ns": int(dur_s * 1e9), **args}}


@pytest.fixture
def hand_made(tmp_path, monkeypatch):
    """A ``trace.json`` where the harness's reader finds it: set-up with
    nested stages, then a window from 100 s that compiles once."""
    from benchmark import span_readers
    events = [
        _event("wave.dispatch", 10, 8, "d"),
        _event("jit.trace", 10, 4, "t1", "d", fun="wave_fn"),
        _event("jit.trace", 11, 1, "t2", "t1", fun="inner"),
        _event("jit.lower", 14, 1, "l1", "d", fun="jit(wave_fn)"),
        _event("jit.compile", 15, 2, "c1", "d", fun="jit_wave_fn",
               cache_hit=0, cache_miss=1),
        _event("jit.compile", 17, 0.5, "c2", "d", fun="jit_inner",
               cache_hit=1, cache_miss=0),
        _event("jit.compile", 101, 1, "c3", None, fun="late",
               cache_hit=0, cache_miss=1),
    ]
    run = tmp_path / "runs" / "hand.made"
    run.mkdir(parents=True)
    (run / "trace.json").write_text(json.dumps({"traceEvents": events}))
    monkeypatch.setattr(span_readers, "CACHE", str(tmp_path))
    return {"cell": "hand.made", "edges": [100.0, 102.0], "n_rounds": 1}


def test_before_window_reads_the_union_of_set_ups_stages(hand_made,
                                                         capsys):
    from benchmark.setup_spans import before_window
    # the inner trace lies inside the outer: counted once
    assert before_window(hand_made, "jit.trace") == pytest.approx(4.0)
    assert before_window(hand_made, "jit.lower") == pytest.approx(1.0)
    assert before_window(hand_made, "jit.compile") == pytest.approx(2.5)
    assert before_window(hand_made, "jit.compile", part="cache_miss") \
        == pytest.approx(2.0)
    assert before_window(hand_made, "jit.compile", part="cache_hit") \
        == pytest.approx(0.5)
    assert before_window(hand_made, "no.such.span") is None
    err = capsys.readouterr().err
    # the operator's view, once: own seconds by program, `jit_` dropped
    assert err.count("compile: set-up's 2 programs") == 1
    assert "wave_fn" in err and "3.0000 | 1.0000 | 2.0000 (0 / 1 of 1)" \
        in err
    assert "late" not in err


def test_before_window_is_none_for_a_program_without_stages(tmp_path,
                                                            monkeypatch):
    from benchmark import span_readers
    from benchmark.setup_spans import before_window
    run = tmp_path / "runs" / "parent"
    run.mkdir(parents=True)
    (run / "trace.json").write_text(json.dumps(
        {"traceEvents": [_event("eval", 1, 1, "e")]}))
    monkeypatch.setattr(span_readers, "CACHE", str(tmp_path))
    ctx = {"cell": "parent", "edges": [100.0, 102.0], "n_rounds": 1}
    for name in STAGES:
        assert before_window(ctx, name) is None
    assert os.path.exists(run / "trace.json")
