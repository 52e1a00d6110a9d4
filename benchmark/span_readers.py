"""Readers of the per-layer metrics that come from the program's own spans.

With ``--perf`` the program writes its run's spans as ``trace.json`` into
its run directory (Chrome ``trace_event`` JSON; every event carries the raw
``perf_counter_ns`` start and duration in ``args``), and every span is also
a ``TraceAnnotation`` of the same name on the host plane of the profiler's
trace.  The harness stamps its round edges with ``time.perf_counter()`` in
the same process, so spans are placed between ``ctx["edges"]`` by time.

Like ``layer_readers``: a reader takes the traced run's context and returns
the metric's value, or None where there is nothing to read (a program
without spans writes no ``trace.json``).  Host-clock numbers come from the
untraced window's rounds only; what is read against the device's ops comes
from the traced rounds, on the trace's own clock.  Both files are found the
way ``run.py`` lays them out and are loaded once, then shared through the
context.
"""

from __future__ import annotations

import json
import os
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

from benchmark import trace_reduce
from benchmark.probe import ROUND_SPAN

CACHE = os.path.join(os.path.dirname(os.path.abspath(__file__)), ".cache")
Interval = Tuple[float, float]


def say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


# -- the program's span file ---------------------------------------------------

def spans_of(events: Sequence[dict]) -> List[dict]:
    """Chrome ``X`` events -> spans with ``t0``/``t1`` in seconds on the
    ``perf_counter`` clock, and ``leaf`` (no span names it as parent)."""
    out = []
    for e in events:
        a = e.get("args") or {}
        if e.get("ph") != "X" or "t0_ns" not in a:
            continue
        t0 = a["t0_ns"] / 1e9
        out.append({"name": e["name"], "t0": t0,
                    "t1": t0 + a["dur_ns"] / 1e9, "args": a})
    parents = {s["args"].get("parent_id") for s in out}
    for s in out:
        s["leaf"] = s["args"].get("span_id") not in parents
    return out


def spans(ctx) -> List[dict]:
    """The run's spans, read once; [] where the program wrote none."""
    if "_spans" not in ctx:
        path = os.path.join(CACHE, "runs", ctx["cell"], "trace.json")
        try:
            with open(path) as f:
                ctx["_spans"] = spans_of(json.load(f)["traceEvents"])
        except (OSError, ValueError, KeyError):
            ctx["_spans"] = []
        if ctx["_spans"]:
            log_span_table(ctx)
    return ctx["_spans"]


def in_window(ctx, name: Optional[str] = None) -> List[dict]:
    """Spans that start inside the untraced window (between the harness's
    first and last edge)."""
    lo, hi = ctx["edges"][0], ctx["edges"][-1]
    return [s for s in spans(ctx) if lo <= s["t0"] < hi
            and (name is None or s["name"] == name)]


def _measure(intervals) -> float:
    return sum(b - a for a, b in trace_reduce._union(intervals))


def _clip(intervals, lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def _overlap(xs: Sequence[Interval], ys: Sequence[Interval]) -> float:
    """Length of the intersection of two unions of intervals."""
    xs, ys = trace_reduce._union(xs), trace_reduce._union(ys)
    i = j = 0
    total = 0.0
    while i < len(xs) and j < len(ys):
        lo, hi = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if hi > lo:
            total += hi - lo
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def _rounds_by_kind(ctx) -> Dict[str, set]:
    """Trace ids of the window's rounds and of the traced rounds after
    them (a round's spans share its trace id)."""
    roots = sorted((s for s in spans(ctx) if s["name"] == "round"),
                   key=lambda s: s["t0"])
    # the round whose entry the harness stamped as the window's first edge
    first = [i for i, s in enumerate(roots)
             if s["t0"] <= ctx["edges"][0] < s["t1"]]
    if not first:
        return {"window": set(), "traced": set()}
    a = first[0]
    b = a + ctx["n_rounds"]
    c = b + int((ctx.get("trace") or {}).get("rounds") or 0)
    return {"window": {s["args"]["trace_id"] for s in roots[a:b]},
            "traced": {s["args"]["trace_id"] for s in roots[b:c]}}


def log_span_table(ctx) -> None:
    """Seconds a round by span name: the untraced window's rounds beside
    the traced rounds (which gaps the profiler stretches), with the bytes
    a round of the spans that count them; then the seconds of every leaf
    span before the window, which is where ``setup_s`` goes."""
    kinds = _rounds_by_kind(ctx)
    sums = {k: defaultdict(float) for k in kinds}
    nbytes = defaultdict(int)
    for s in ctx["_spans"]:
        for k, ids in kinds.items():
            if s["args"].get("trace_id") in ids:
                sums[k][s["name"]] += s["t1"] - s["t0"]
                if k == "window":
                    nbytes[s["name"]] += s["args"].get("bytes", 0)
    n = {k: max(len(ids), 1) for k, ids in kinds.items()}
    say(f"spans: seconds a round, window ({len(kinds['window'])} rounds) "
        f"| traced ({len(kinds['traced'])} rounds) | bytes a round")
    for name in sorted(sums["window"], key=lambda k: -sums["window"][k]):
        say(f"spans:   {name:<20} {sums['window'][name] / n['window']:.6f}"
            f" | {sums['traced'].get(name, 0.0) / n['traced']:.6f}"
            + (f" | {nbytes[name] // n['window']}" if nbytes[name] else ""))
    before = defaultdict(float)
    for s in ctx["_spans"]:
        if s["leaf"] and s["t0"] < ctx["edges"][0]:
            before[s["name"]] += min(s["t1"], ctx["edges"][0]) - s["t0"]
    say("spans: before the window (set-up of the call), seconds by leaf "
        "span")
    for name in sorted(before, key=lambda k: -before[k]):
        say(f"spans:   {name:<20} {before[name]:.6f}")


# -- readers: host clock, the untraced window ------------------------------------

def span_per_round(ctx, name):
    """Seconds a round of the window spent in spans called ``name``."""
    found = in_window(ctx, name)
    if not found:
        return None
    return sum(s["t1"] - s["t0"] for s in found) / ctx["n_rounds"]


def arg_share(ctx, name, part, whole):
    """100 x the sum of span arg ``part`` over the sum of ``whole``, over
    the window's spans called ``name`` (a count made where the work is)."""
    found = [s for s in in_window(ctx, name) if whole in s["args"]]
    total = sum(s["args"][whole] for s in found)
    if not total:
        return None
    return 100.0 * sum(s["args"][part] for s in found) / total


def host_wait_share(ctx):
    """The share of the window's wall the host spent in spans that only
    wait for the device (``wait: "device"``)."""
    lo, hi = ctx["edges"][0], ctx["edges"][-1]
    waits = [(s["t0"], s["t1"]) for s in in_window(ctx)
             if s["args"].get("wait") == "device"]
    if not waits:
        return None
    return 100.0 * _measure(_clip(waits, lo, hi)) / (hi - lo)


def round_unspanned(ctx):
    """An untraced cycle minus the union of the program's leaf spans
    inside it: what the round does under no name."""
    lo, hi = ctx["edges"][0], ctx["edges"][-1]
    leaves = [(s["t0"], s["t1"]) for s in spans(ctx) if s["leaf"]]
    if not leaves:
        return None
    return ((hi - lo) - _measure(_clip(leaves, lo, hi))) / ctx["n_rounds"]


def setup_span(ctx, name, of_round=None, less=None):
    """Seconds of the call's first span called ``name`` (under the round
    ``of_round`` where given), less its child called ``less``."""
    all_spans = spans(ctx)
    found = sorted((s for s in all_spans if s["name"] == name),
                   key=lambda s: s["t0"])
    if of_round is not None:
        roots = {s["args"]["span_id"] for s in all_spans
                 if s["name"] == "round"
                 and s["args"].get("round") == of_round}
        found = [s for s in found if s["args"].get("span_id") in roots
                 or s["args"].get("parent_id") in roots]
    if not found:
        return None
    first = found[0]
    inner = sum(s["t1"] - s["t0"] for s in all_spans if s["name"] == less
                and s["args"].get("parent_id") == first["args"]["span_id"])
    return first["t1"] - first["t0"] - inner


# -- readers: the profiler's trace, the traced rounds -----------------------------

def xplane_events(ctx) -> list:
    """Device ops and modules, the harness's cycles and the program's
    leaf spans as the host plane holds them; [] without a trace."""
    if "_xplane" not in ctx:
        leaves = sorted({s["name"] for s in spans(ctx) if s["leaf"]}
                        - {s["name"] for s in spans(ctx) if not s["leaf"]})
        try:
            path = trace_reduce.find_xplane(
                os.path.join(CACHE, "trace", ctx["cell"]))
            ctx["_xplane"] = trace_reduce.load_xplane(
                path, host_names=leaves + [ROUND_SPAN])
        except OSError:
            ctx["_xplane"] = []
    return ctx["_xplane"]


def _cycles_and_devices(events):
    """(t_lo, t_hi, number of cycles) of the annotated cycles, and per
    device plane its op and module intervals cut to them."""
    cycles = [(a, a + d) for _, line, name, a, d in events
              if line == "host" and name == ROUND_SPAN]
    if not cycles:
        return None
    lo, hi = min(a for a, _ in cycles), max(b for _, b in cycles)
    devs = defaultdict(lambda: {trace_reduce.OPS_LINE: [],
                                trace_reduce.MODULES_LINE: []})
    for plane, line, name, a, d in events:
        if line in (trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE) \
                and a + d > lo and a < hi:
            devs[plane][line].append((max(a, lo), min(a + d, hi), name))
    devs = {p: v for p, v in devs.items() if v[trace_reduce.OPS_LINE]}
    return (lo, hi, len(cycles), devs) if devs else None


def module_seconds(ctx, modules):
    """Device seconds a traced round of the programs whose module name
    starts with one of ``modules``: the union of the op intervals inside
    each of their runs, averaged over chips."""
    cut = _cycles_and_devices(xplane_events(ctx))
    if cut is None:
        return None
    _, _, n_cycles, devs = cut
    total, runs = 0.0, 0
    for v in devs.values():
        ops = [(a, b) for a, b, _ in v[trace_reduce.OPS_LINE]]
        for a, b, name in v[trace_reduce.MODULES_LINE]:
            if name.startswith(tuple(modules)):
                runs += 1
                total += _measure(_clip(ops, a, b))
    if not runs:
        return None
    return total / len(devs) / n_cycles / 1e9


def idle_unnamed_share(ctx):
    """Of the traced cycles' device-idle time (the complement of the
    union of op intervals, cut to the annotated cycles as
    ``trace_reduce.reduce`` cuts it), the share that no leaf span of the
    program covers.  Logs the idle seconds a traced round by leaf span."""
    events = xplane_events(ctx)
    cut = _cycles_and_devices(events)
    by_name = defaultdict(list)
    for _, line, name, a, d in events:
        if line == "host" and name != ROUND_SPAN:
            by_name[name].append((a, a + d))
    if cut is None or not by_name:
        return None
    lo, hi, n_cycles, devs = cut
    idle_ns = named_ns = 0.0
    per_name = defaultdict(float)
    for v in devs.values():
        busy = trace_reduce._union(
            (a, b) for a, b, _ in v[trace_reduce.OPS_LINE])
        marks = [[lo, lo]] + busy + [[hi, hi]]
        idle = [(p[1], q[0]) for p, q in zip(marks, marks[1:])
                if q[0] > p[1]]
        idle_ns += _measure(idle)
        named_ns += _overlap(
            idle, [iv for ivs in by_name.values() for iv in ivs])
        for name, ivs in by_name.items():
            per_name[name] += _overlap(idle, ivs)
    if not idle_ns:
        return None
    scale = len(devs) * n_cycles * 1e9
    say(f"idle: {idle_ns / scale:.6f} s a traced round, "
        f"{(idle_ns - named_ns) / scale:.6f} s of it under no leaf span")
    for name in sorted(per_name, key=lambda k: -per_name[k]):
        say(f"idle:   {name:<20} {per_name[name] / scale:.6f}")
    return 100.0 * (idle_ns - named_ns) / idle_ns
