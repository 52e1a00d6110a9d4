"""The span readers' own tests.  CPU, run by hand like the others:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_span_readers.py -q

Every reader on a small synthetic span list and event list, None on an
empty context, and a CPU rehearsal that shows the readers find the
``trace.json`` a run leaves in its run directory.
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

from benchmark import run, span_readers, trace_reduce  # noqa: E402
from benchmark.probe import ROUND_SPAN  # noqa: E402
import rehearse  # noqa: E402

S = 10 ** 9


def _event(name, t0_s, dur_s, span_id, parent=None, trace_id="r0", **args):
    return {"name": name, "ph": "X", "ts": 0, "dur": 0, "pid": 0, "tid": 1,
            "args": {"trace_id": trace_id, "span_id": span_id,
                     "parent_id": parent, "t0_ns": int(t0_s * S),
                     "dur_ns": int(dur_s * S), **args}}


def _two_rounds():
    """Set-up (data, round 0 with its evaluation), then two window rounds
    of 2 s between the edges 100, 102, 104, then one traced round."""
    ev = [_event("setup.data", 10, 7, "d", trace_id="s"),
          _event("setup.init", 17, 3, "i", trace_id="i"),
          _event("round", 20, 30, "R0", trace_id="r0", round=0),
          _event("wave", 21, 15, "R0w", "R0", "r0", phase="wave"),
          _event("eval", 40, 9, "R0e", "R0", "r0")]
    for k, t in enumerate((100.0, 102.0, 104.0)):
        tid, r = f"r{k + 3}", f"R{k + 3}"
        ev += [
            _event("round", t - 0.01, 2.0, r, None, tid, round=k + 3),
            _event("round.sample", t - 0.01, 0.01, r + "s", r, tid),
            _event("wave", t + 0.1, 1.6, r + "w", r, tid, phase="wave"),
            _event("stage.gather", t + 0.1, 0.2, r + "g", r + "w", tid,
                   bytes=1000, rows_real=70, rows_padded=100),
            _event("stage.put", t + 0.3, 0.1 + 0.1 * k, r + "p", r + "w",
                   tid),
            _event("wave.wait", t + 0.5, 1.2, r + "t", r + "w", tid,
                   wait="device"),
            _event("round.sync", t + 1.8, 0.1, r + "y", r, tid,
                   wait="device"),
        ]
    return ev


def _ctx(**kw):
    ctx = {"cell": "no.such.cell", "edges": [100.0, 102.0, 104.0],
           "n_rounds": 2, "trace": {"rounds": 1},
           "_spans": span_readers.spans_of(_two_rounds())}
    ctx.update(kw)
    return ctx


def test_span_seconds_a_round_come_from_the_window_only():
    ctx = _ctx()
    assert span_readers.span_per_round(ctx, "stage.gather") \
        == pytest.approx(0.2)
    # 0.1 and 0.2 in the window; the traced round's 0.3 is not counted
    assert span_readers.span_per_round(ctx, "stage.put") \
        == pytest.approx(0.15)
    assert span_readers.span_per_round(ctx, "wave.wait") \
        == pytest.approx(1.2)
    assert span_readers.span_per_round(ctx, "no.such.span") is None


def test_counts_and_waits_of_the_window():
    ctx = _ctx()
    assert span_readers.arg_share(ctx, "stage.gather", "rows_real",
                                  "rows_padded") == pytest.approx(70.0)
    # 1.2 + 0.1 of every 2 s blocked on the device
    assert span_readers.host_wait_share(ctx) == pytest.approx(65.0)
    # leaves a round: gather 0.2, put 0.1 / 0.2, wait 1.2, sync 0.1 and
    # the next round's 0.01 of sampling: 3.8 s of 4 s minus 0.02
    assert span_readers.round_unspanned(ctx) == pytest.approx(
        (4.0 - (0.2 + 0.1 + 1.2 + 0.1) - (0.2 + 0.2 + 1.2 + 0.1)
         - 0.02) / 2)


def test_setup_spans_add_up_without_counting_the_evaluation_twice():
    ctx = _ctx()
    assert span_readers.setup_span(ctx, "setup.data") == pytest.approx(7.0)
    assert span_readers.setup_span(ctx, "round", of_round=0, less="eval") \
        == pytest.approx(21.0)
    assert span_readers.setup_span(ctx, "eval", of_round=0) \
        == pytest.approx(9.0)
    assert span_readers.setup_span(ctx, "eval", of_round=7) is None


def test_rounds_are_told_apart_by_their_trace_id():
    kinds = span_readers._rounds_by_kind(_ctx())
    assert kinds == {"window": {"r3", "r4"}, "traced": {"r5"}}


def test_the_span_table_names_window_traced_bytes_and_set_up(capsys):
    """What an operator reads on stderr of a traced run: the table
    PERF.md section 5 is copied from, and the only reader of the span
    arg ``bytes`` and of the span ``setup.init``."""
    span_readers.log_span_table(_ctx())
    rows = {ln.split()[1]: ln.split()[2:] for ln in
            capsys.readouterr().err.splitlines() if ln.startswith("spans:  ")}
    # window 0.2 s a round | traced 0.2 | 1000 bytes a round
    assert rows["stage.gather"][:5] == ["0.200000", "|", "0.200000", "|",
                                        "1000"]
    assert rows["stage.put"] == ["0.150000", "|", "0.300000"]
    # the leaves before the window's first edge; round 0's wave has no
    # child here, so it is one
    assert rows["setup.data"] == ["7.000000"]
    assert rows["setup.init"] == ["3.000000"]
    assert rows["eval"] == ["9.000000"]


def _xplane():
    """One chip, one cycle of 1,000 ns: busy 100-400 and 600-700, so idle
    0-100, 400-600 and 700-1000 (600 ns); leaf spans cover 20-100 and
    450-600 of it."""
    dev, host = "/device:TPU:0", trace_reduce.HOST_PLANE
    return [
        (host, "host", ROUND_SPAN, 0.0, 1000.0),
        (dev, trace_reduce.MODULES_LINE, "jit_wave_fn(7)", 100.0, 300.0),
        (dev, trace_reduce.OPS_LINE, "%fusion.1 = f32[8] fusion()", 100.0,
         300.0),
        (dev, trace_reduce.MODULES_LINE, "jit__fold_wave(9)", 600.0, 60.0),
        (dev, trace_reduce.OPS_LINE, "%add.2 = f32[8] add()", 600.0, 50.0),
        (dev, trace_reduce.MODULES_LINE, "jit__finalize(11)", 660.0, 40.0),
        # an op that starts before its module's event: cut to the run
        (dev, trace_reduce.OPS_LINE, "%div.3 = f32[8] divide()", 650.0,
         50.0),
        (host, "host", "stage.gather", 20.0, 200.0),
        (host, "host", "fold.dispatch", 450.0, 200.0),
    ]


def test_idle_arithmetic_agrees_with_trace_reduce():
    events = _xplane()
    ctx = _ctx(_xplane=events)
    reduced = trace_reduce.reduce(events, program="jit_wave_fn",
                                  window=ROUND_SPAN)
    idle_ns = (reduced["window_s"] - reduced["busy_s"]) * 1e9
    assert idle_ns == pytest.approx(600.0)
    busy = trace_reduce._union([(100.0, 400.0), (600.0, 650.0),
                                (650.0, 700.0)])
    assert sum(b - a for a, b in busy) == pytest.approx(
        reduced["busy_s"] * 1e9)
    named = 80.0 + 150.0
    assert span_readers.idle_unnamed_share(ctx) == pytest.approx(
        100.0 * (idle_ns - named) / idle_ns)
    # the ops inside the two programs' runs: 50 + 10 ns, and 40 of the 50
    assert span_readers.module_seconds(
        ctx, ["jit__fold_wave", "jit__finalize"]) == pytest.approx(100e-9)
    assert span_readers.module_seconds(ctx, ["jit_nothing"]) is None


def test_overlap_of_two_unions():
    assert span_readers._overlap([(0, 10), (5, 20)], [(8, 12), (18, 30)]) \
        == pytest.approx(6.0)
    assert span_readers._overlap([], [(0, 1)]) == 0.0


def _line(name, shape, rest="fusion(%p)"):
    """One op of a trace as the profiler names it: its HLO line."""
    return f"%{name} = {shape}{{1,0:T(8,128)}} {rest}"


def test_expert_group_is_found_whatever_the_attention_keys():
    """GLM's ops keep their labels; Keye's model file (grouped-query
    heads, ``head_dim`` only) gets its expert ops found and no attention
    group, where the reader once raised a ``KeyError``."""
    from benchmark import expert_attention as ea

    def model(name):
        cfg = run.load_json(os.path.join(ROOT, "benchmark", "configs",
                                         name + ".json"))
        return dict(cfg["model"], block=int(cfg["cli"]["attn_block_size"]),
                    batch=int(cfg["cli"]["batch_size"]))
    glm, keye = model("glm47_flash"), model("keye_vl2_30b_a3b")
    assert all(k in glm for k in ea.LATENT_KEYS)
    assert not any(k in keye for k in ea.LATENT_KEYS)
    glm_ops = {
        _line("latent_attention_forward.3",
              "(f32[1,20,8192,256]{3,2,1,0:T(8,128)}, f32[1,20,1,8192]",
              ") custom-call(%a, %b)"): "attention",
        _line("fusion.41", "f32[1,8192,20,256]"): "attention",
        _line("fusion.42", "f32[1,20,1024,8192]"): "attention",
        _line("fusion.7", "f32[512,1536]"): "experts",
        _line("convolution.3", "f32[512,2048]"): "experts",
        _line("fusion.8", "f32[2048,1536]"): "experts",
        _line("fusion.9", "f32[1536,2048]"): "experts",
        _line("fusion.177", "f32[2048,10240]"): None,
        _line("fusion.350", "f32[2048,19360]"): None,
        _line("while.2", "(s32[], f32[512,1536])", "while(%t)"): None,
    }
    keye_ops = {
        _line("selected_attention_forward.2",
              "(f32[1,32,8192,128]{3,2,1,0:T(8,128)}, f32[1,32,1,8192]",
              ") custom-call(%q, %k, %v, %s)"): None,
        _line("fusion.12", "f32[1,8192,32,128]"): None,
        _line("fusion.13", "f32[512,768]"): "experts",
        _line("convolution.4", "f32[512,2048]"): "experts",
        _line("fusion.14", "f32[2048,768]"): "experts",
        _line("fusion.15", "f32[768,2048]"): "experts",
        _line("fusion.350", "f32[2048,18992]"): None,
    }
    for m, ops in ((glm, glm_ops), (keye, keye_ops)):
        assert {line: ea.group_of(line, m) for line in ops} == ops


def test_every_reader_returns_none_on_an_empty_context():
    bench = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    mine = 0
    for m in bench["per_layer"]:
        spec = run.load_json(os.path.join(
            ROOT, "benchmark", "layer_metrics", m["name"] + ".json"))
        if not spec["reader"].startswith("benchmark.span_readers:"):
            continue
        mine += 1
        ctx = {"cell": "no.such.cell", "edges": [0.0, 1.0], "n_rounds": 1,
               "trace": {}}
        assert run.call(spec["reader"])(ctx, **spec.get("args", {})) is None
    assert mine


def test_a_rehearsal_leaves_a_trace_json_the_readers_find(monkeypatch,
                                                          capfd):
    """A whole run at a tiny size on the CPU: the program writes
    ``trace.json`` where the readers look, on the clock of the harness's
    edges."""
    seen = {}
    original = run.window_metrics

    def keep_edges(edges, samples, setup_s):
        seen["edges"] = list(edges)
        return original(edges, samples, setup_s)

    monkeypatch.setattr(run, "window_metrics", keep_edges)
    result = rehearse.rehearse("femnist", seed=2147483659)
    assert result["correct"]
    n = len(seen["edges"]) - 1
    ctx = {"cell": rehearse.CELLS["femnist"][2], "edges": seen["edges"],
           "n_rounds": n, "trace": {}}
    path = os.path.join(span_readers.CACHE, "runs", ctx["cell"],
                        "trace.json")
    assert os.path.exists(path)
    with open(path) as f:
        assert json.load(f)["otherData"]["clock"] == "perf_counter_ns"
    parts = [span_readers.span_per_round(ctx, name) for name in
             ("stage.gather", "stage.put", "wave.dispatch", "wave.wait")]
    assert all(p is not None and p > 0 for p in parts)
    # the window's rounds are found by time: two waves a round
    assert len(span_readers.in_window(ctx, "wave")) == 2 * n
    kinds = span_readers._rounds_by_kind(ctx)
    assert len(kinds["window"]) == n
    round_s = (seen["edges"][-1] - seen["edges"][0]) / n
    assert 0 <= span_readers.round_unspanned(ctx) < round_s
    assert 0 < span_readers.host_wait_share(ctx) < 100
    assert 0 < span_readers.arg_share(ctx, "stage.gather", "rows_real",
                                      "rows_padded") <= 100
    for name, kw in (("setup.data", {}), ("eval", {"of_round": 0}),
                     ("round", {"of_round": 0, "less": "eval"})):
        assert span_readers.setup_span(ctx, name, **kw) > 0
    assert "spans: seconds a round" in capfd.readouterr().err
