"""From JAX's profiler trace to device metrics.

``load_xplane`` reads the ``.xplane.pb`` the profiler wrote into a plain
list of events ``(plane, line, name, start_ns, duration_ns)`` and keeps the
lines the reduction reads; ``reduce`` is pure arithmetic over such a list,
so the unit test runs it on a small recording (``tests/data``).

On a TPU v5e (looked at by hand, PR 26; see PERF.md section 3) each chip
is a plane ``/device:TPU:<n>``; its line ``XLA Ops`` holds one event per
executed HLO op (fusions, convolutions, copies, each named by its whole HLO
line; a ``while`` is one more event that spans the events of its body), its
line ``XLA Modules`` one event per executed program (``jit_<name>(<id>)``),
and ``Steps`` one per module run.  Host threads are lines of the plane
``/host:CPU``; a ``TraceAnnotation`` shows up there under its own name.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, str, str, float, float]

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def is_device_plane(name: str) -> bool:
    return (name.startswith(DEVICE_PREFIX)
            and name[len(DEVICE_PREFIX):].isdigit())


def load_xplane(path: str, host_names: Sequence[str] = ()) -> List[Event]:
    """Device ops and modules of every chip, and the host events whose
    name is in ``host_names`` (the harness's own annotations)."""
    from jax.profiler import ProfileData
    want = set(host_names)
    out: List[Event] = []
    for plane in ProfileData.from_file(path).planes:
        if is_device_plane(plane.name):
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    for e in line.events:
                        out.append((plane.name, line.name, e.name,
                                    float(e.start_ns), float(e.duration_ns)))
        elif plane.name == HOST_PLANE and want:
            for line in plane.lines:
                for e in line.events:
                    if e.name in want:
                        out.append((plane.name, "host", e.name,
                                    float(e.start_ns), float(e.duration_ns)))
    return out


def describe_xplane(path: str) -> List[str]:
    """Planes, lines and event counts: what a first look by hand needs."""
    from jax.profiler import ProfileData
    rows = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            evs = list(line.events)
            names = sorted({e.name for e in evs[:2000]})[:6]
            rows.append(f"{plane.name} | {line.name} | {len(evs)} events | "
                        f"{names}")
    return rows


CONTROL_FLOW = (" while(", " conditional(", " call(")


def short_name(hlo: str, width: int = 96) -> str:
    """``%fusion.281 = f32[20,26] fusion(...), kind=kOutput`` ->
    ``%fusion.281 f32[20,26] fusion kind=kOutput`` (the trace names an op
    by its whole HLO line, layouts and operands and all)."""
    head, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo[:width]
    shape, _, tail = rest.partition(" ")
    shape = shape.split("{", 1)[0]
    opcode = tail.split("(", 1)[0]
    kind = ""
    if "kind=" in tail:
        kind = " kind=" + tail.split("kind=", 1)[1].split(",", 1)[0]
    if shape.startswith("("):
        shape = "(tuple)"
        opcode = rest.rsplit(") ", 1)[-1].split("(", 1)[0] if ") " in rest \
            else opcode
    return f"{head} {shape} {opcode}{kind}"[:width]


def _union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1][1] = b
        else:
            merged.append([a, b])
    return merged


def reduce(events: Sequence[Event], program: Optional[str] = None,
           window: Optional[str] = None, top: int = 10) -> dict:
    """What the devices did inside the traced window.

    The window is the stretch the host annotations named ``window`` cover
    (the harness puts one around every traced round cycle, so it is whole
    cycles on the trace's own clock, without the profiler's start and
    stop); without such events it is the extent of the device ops.  Device
    events are cut to it.  Returns busy seconds (union of op intervals,
    averaged over chips), ``window_s`` and the ``rounds`` it holds, the ops
    that took most device time, the longest idle gaps named by the host
    annotation that covers most of each, and, for the program whose module
    name contains ``program``: its device seconds, its runs, and the idle
    stretch that ends at each run's first op.  Returns {} when no
    operation ran on a device inside the window."""
    by_dev: Dict[str, Dict[str, list]] = defaultdict(
        lambda: {OPS_LINE: [], MODULES_LINE: []})
    host, cycles = [], []
    for plane, line, name, start, dur in events:
        if line == "host":
            (cycles if name == window else host).append(
                (start, start + dur, name))
        elif line in (OPS_LINE, MODULES_LINE):
            by_dev[plane][line].append((start, start + dur, name))
    ops_all = [e for v in by_dev.values() for e in v[OPS_LINE]]
    if not ops_all:
        return {}
    ext_lo = min(a for a, _, _ in ops_all)
    ext_hi = max(b for _, b, _ in ops_all)
    if cycles:
        t_lo = min(a for a, _, _ in cycles)
        t_hi = max(b for _, b, _ in cycles)
    else:
        t_lo, t_hi = ext_lo, ext_hi

    def cut(rows):
        return [(max(a, t_lo), min(b, t_hi), name) for a, b, name in rows
                if b > t_lo and a < t_hi]

    devs = {p: {k: cut(v[k]) for k in v} for p, v in by_dev.items()}
    devs = {p: v for p, v in devs.items() if v[OPS_LINE]}
    if not devs:
        return {}
    busy_ns, op_time, gaps = 0.0, defaultdict(float), []
    prog_ns, prog_runs, prog_gap_ns = 0.0, 0, 0.0
    for plane, v in sorted(devs.items()):
        merged = _union((a, b) for a, b, _ in v[OPS_LINE])
        busy_ns += sum(b - a for a, b in merged)
        for a, b, name in v[OPS_LINE]:
            # a loop or a branch is an op too, and spans the ops it runs:
            # it counts towards busy time, not among the costliest ops
            if not any(c in name for c in CONTROL_FLOW):
                op_time[short_name(name)] += b - a
        # the window's own edges bound the first and the last gap
        marks = [[t_lo, t_lo]] + merged + [[t_hi, t_hi]]
        for (_, end_prev), (start_next, _) in zip(marks, marks[1:]):
            if start_next > end_prev:
                gaps.append((start_next - end_prev, end_prev, start_next))
        if program:
            ends = [t_lo] + [b for _, b in merged]
            for a, b, name in v[MODULES_LINE]:
                if program not in name:
                    continue
                prog_runs += 1
                inside = _union((max(x, a), min(y, b))
                                for x, y, _ in v[OPS_LINE]
                                if y > a and x < b)
                prog_ns += sum(y - x for x, y in inside)
                # the idle stretch that ends where this run's ops start
                first = inside[0][0] if inside else a
                prog_gap_ns += first - max(e for e in ends if e <= first)
    n = len(devs)
    gaps.sort(reverse=True)
    named = []
    for length, a, b in gaps[:top]:
        cover = defaultdict(float)
        for x, y, name in host:
            o = min(y, b) - max(x, a)
            if o > 0:
                cover[name] += o
        who = max(cover, key=cover.get) if cover else "unattributed"
        named.append([who, length / 1e9])
    out = {
        "devices": n,
        "busy_s": busy_ns / n / 1e9,
        "window_s": (t_hi - t_lo) / 1e9,
        "rounds": len(cycles),
        "extent_s": (ext_hi - ext_lo) / 1e9,
        "device_ops": [[k, v / n / 1e9] for k, v in sorted(
            op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": named,
    }
    if program and prog_runs:
        out.update(program_s=prog_ns / n / 1e9, program_runs=prog_runs / n,
                   program_gap_s=prog_gap_ns / n / 1e9)
    return out
