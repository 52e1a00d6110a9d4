"""Per-layer metrics of the cross-chip summary: how long the collectives of
a mesh program are in flight in a traced round, and what a chip sends in
them.

A collective is found by its opcode in the HLO line the profiler names a
device op by (``%all-reduce.7 = f32[2048,1536]{1,0} all-reduce(...)``), never
by its number, which changes with every compile: ``KINDS``, and each one's
asynchronous halves (``all-gather-start`` / ``all-gather-done``), wherever
they sit, in the wave program or in the fold.

- Seconds (`collective_seconds`): the time a collective is in flight on a
  chip.  A synchronous op's own time; an asynchronous one's from the start
  of its ``-start`` to the end of the ``-done`` that names it, the ops
  that run between them included (the transfer goes on under them).  A
  chip's collectives that overlap count once.
- Bytes (`collective_gb_per_s`): what a chip sends in each op by a ring's
  count over the ``n`` chips of its group, from the result the trace
  prints for a synchronous op or a ``-done`` (``R`` bytes): an all-reduce
  ``2 (n - 1) / n x R``, an all-gather or an all-to-all ``(n - 1) / n x R``,
  a reduce-scatter ``(n - 1) x R``, a collective-permute ``R``.  The rate
  is those bytes over those seconds: GB/s a chip, no share of a roofline.
  An algorithm other than a ring sends other bytes, and a collective that
  waits on its peers or on the ops it overlaps reads slower.

Seconds and bytes are a traced round's, averaged over the chips the trace
holds.  Every reader returns None where no collective ran in the traced
rounds (a one-chip program), and the harness then leaves the metric out.
"""

from __future__ import annotations

import re
from typing import Optional, Tuple

from benchmark import span_readers, trace_reduce

KINDS = ("all-reduce", "all-gather", "collective-permute", "all-to-all",
         "reduce-scatter")
DTYPE_BYTES = {"pred": 1, "s4": 0.5, "u4": 0.5, "s8": 1, "u8": 1,
               "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2, "s16": 2,
               "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
               "u64": 8, "c64": 8, "c128": 16}
_ARRAY = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")
_GROUPS = re.compile(r"replica_groups=(\{\{[0-9,]*\}|\[[0-9]+,([0-9]+)\])")
_START = re.compile(r"%((?:" + "|".join(KINDS) + r")-start[.0-9]*)\b")


def _result(hlo: str) -> Tuple[str, str]:
    """(the result's shape, the text after it) of an op's HLO line; two
    empty strings for a line that is no op's.  A tuple's shape runs to its
    matching parenthesis: a chip's layouts hold their own
    (``f32[8,128]{1,0:T(8,128)}``)."""
    _, sep, rest = hlo.partition(" = ")
    if not sep:
        return "", ""
    if not rest.startswith("("):
        shape, _, after = rest.partition(" ")
        return shape, after
    depth = 0
    for i, ch in enumerate(rest):
        depth += {"(": 1, ")": -1}.get(ch, 0)
        if depth == 0:
            return rest[:i + 1], rest[i + 1:]
    return rest, ""


def op_kind(hlo: str) -> Tuple[Optional[str], Optional[str]]:
    """(collective kind, ``start`` / ``done`` / ``None`` for a synchronous
    op) of an op's HLO line, or (None, None) for any other op."""
    opcode = _result(hlo)[1].split("(", 1)[0].strip()
    for kind in KINDS:
        if opcode == kind:
            return kind, None
        if opcode in (kind + "-start", kind + "-done"):
            return kind, opcode[len(kind) + 1:]
    return None, None


def shape_bytes(hlo: str) -> float:
    """Bytes of the result an op's HLO line prints: an array
    ``f32[2048,1536]{1,0}`` or a tuple of them; 0 where none is printed."""
    total = 0.0
    for dtype, dims in _ARRAY.findall(_result(hlo)[0]):
        if dtype not in DTYPE_BYTES:
            continue
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * DTYPE_BYTES[dtype]
    return total


def group_size(hlo: str, chips: int) -> int:
    """Chips in the op's group: the first of ``replica_groups={{0,1,..}}``
    or the width of ``replica_groups=[g,n]<=[..]``; ``chips`` (every chip
    the trace holds) where the line names none or an empty list."""
    found = _GROUPS.search(hlo)
    if found is None:
        return chips
    if found.group(2):
        return int(found.group(2))
    members = [m for m in found.group(1)[2:].split(",") if m]
    return len(members) or chips


def sent_bytes(hlo: str, chips: int) -> float:
    """Bytes a chip sends in a synchronous collective or the ``-done`` of
    an asynchronous one, by a ring's count (module docstring); 0 for a
    ``-start`` and for any other op."""
    kind, half = op_kind(hlo)
    if kind is None or half == "start":
        return 0.0
    result, n = shape_bytes(hlo), group_size(hlo, chips)
    return result * {"all-reduce": 2 * (n - 1) / n,
                     "all-gather": (n - 1) / n,
                     "all-to-all": (n - 1) / n,
                     "reduce-scatter": n - 1,
                     "collective-permute": 1}[kind]


def in_flight(ops) -> list:
    """A chip's ``(start, end)`` intervals in which a collective is in
    flight, from its ``(start, end, HLO line)`` ops: a synchronous op's
    own, an asynchronous one's from its ``-start`` to the ``-done`` that
    names it (either half alone where the trace cut off the other)."""
    spans, opened = [], {}
    for a, b, name in sorted(ops):
        kind, half = op_kind(name)
        if kind is None:
            continue
        if half == "start":
            opened[name.split(" = ", 1)[0].lstrip("%")] = (a, b)
        elif half == "done":
            started = _START.search(_result(name)[1])
            first = opened.pop(started.group(1), None) if started else None
            spans.append((first[0] if first else a, b))
        else:
            spans.append((a, b))
    return spans + list(opened.values())


def _per_round(ctx) -> Optional[dict]:
    """Collective seconds in flight (module docstring), bytes sent and ops,
    a traced round and a chip; read once."""
    if "_collectives" in ctx:
        return ctx["_collectives"]
    ctx["_collectives"] = None
    cut = span_readers._cycles_and_devices(span_readers.xplane_events(ctx))
    if cut is None:
        return None
    _, _, n_cycles, devs = cut
    seconds_ns, nbytes, ops = 0.0, 0.0, 0
    for v in devs.values():
        mine = v[trace_reduce.OPS_LINE]
        ops += sum(op_kind(name)[0] is not None for _, _, name in mine)
        nbytes += sum(sent_bytes(name, len(devs)) for _, _, name in mine)
        seconds_ns += span_readers._measure(in_flight(mine))
    if not ops:
        return None
    scale = len(devs) * n_cycles
    out = {"seconds": seconds_ns / scale / 1e9, "bytes": nbytes / scale,
           "ops": ops / scale}
    span_readers.say(f"collectives: {out['seconds']:.6f} s in flight, "
                     f"{out['bytes']:.0f} bytes sent, {out['ops']:.1f} ops "
                     f"a traced round a chip over {len(devs)} chips")
    ctx["_collectives"] = out
    return out


def collective_seconds(ctx):
    """Device seconds a traced round, a chip, with a cross-chip collective
    in flight."""
    found = _per_round(ctx)
    return None if found is None else found["seconds"]


def collective_gb_per_s(ctx):
    """The bytes a chip sends in those collectives, a traced round, by a
    ring's count, over their seconds in flight: GB/s."""
    found = _per_round(ctx)
    if found is None or not found["seconds"]:
        return None
    return found["bytes"] / found["seconds"] / 1e9
