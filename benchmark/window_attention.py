"""Per-layer metrics of a model whose layers differ by kind: a window of the
latest keys on some layers beside full causal attention on the others
(``layer_types`` and ``sliding_window`` in the cell's model keys).  What the
window layers' cores take of a traced round, what they are required to do,
and what share of the causal key tiles they visit.

One group of device operations is read from the traced rounds (the
profiler names an op by its whole HLO line; named scopes do not reach the
v5e trace, PERF.md section 3):

* ``window``: the window kernels of every sliding layer, forward and
  backward, found by their name (``window_attention_forward`` /
  ``_backward``, `models.fused_attention.WINDOW_KERNEL`), and nothing
  else.  The XLA ops beside them that carry the window heads' shapes are
  the layer's projections, not its core: the query projection's product
  (``[B, T, heads, head_dim]``, forward and recomputed), the output
  projection's input gradient with the kernels' row sums ``delta`` fused
  into it (a ``multiply_reduce_fusion`` of ``([heads, T], [B, heads, T,
  head_dim])``), the rotary and the copies of the layouts; by shape alone
  they cannot be told from what a core would make, so none is taken.

The function that gives the group's REQUIRED operations and bytes stands
here too (`window_attention_required`): every query head's score and mix
a (query, key) pair inside the window, three passes (forward, gradient by
the inputs, the recomputation a checkpoint adds not counted); bytes: q, k,
v read and the result written a pass.  A share of the roofline is the time
the required work takes at the chip's peaks (the slower of FLOPs / peak
FLOP/s and bytes / peak bytes/s) over the device time the group took.

Every reader returns None where it finds nothing to read (a model without
a window, a program without the kernels or the ops, as the parent of the
PR that brought this file is): the harness then leaves the metric out.
"""

from __future__ import annotations

import json
import os
from typing import Optional

from benchmark import span_readers, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
KERNEL = "window_attention_"    # the kernels' name, forward and backward
SLIDING = "sliding_attention"


def _model(ctx) -> Optional[dict]:
    """The cell's ``model`` keys with its batch, the sliding layers'
    query heads (``window_heads``) and their number (``window_layers``),
    or None for a configuration without a window."""
    if "_window_model" not in ctx:
        with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
            bench = json.load(f)
        found = None
        for w in bench["workloads"]:
            if w["name"] != ctx["cell"]:
                continue
            row = [c for c in bench["configs"] if c["name"] == w["config"]][0]
            with open(os.path.join(os.path.dirname(HERE), row["file"])) as f:
                cfg = json.load(f)
            m = cfg.get("model", {})
            if m.get("sliding_window") and SLIDING in m.get(
                    "layer_types", [])[:m["num_hidden_layers"]]:
                found = with_layers(m, int(cfg["cli"]["batch_size"]))
        ctx["_window_model"] = found
    return ctx["_window_model"]


def with_layers(m: dict, batch: int) -> dict:
    """``m`` with ``batch``, ``window_heads`` (the sliding layers' query
    heads) and ``window_layers`` over its first ``num_hidden_layers``
    layers."""
    n = m["num_hidden_layers"]
    kinds = m["layer_types"][:n]
    heads = m["num_attention_heads_per_layer"][:n]
    return dict(m, batch=batch,
                window_heads=max(h for k, h in zip(kinds, heads)
                                 if k == SLIDING),
                window_layers=kinds.count(SLIDING))


def group_of(hlo: str, m: dict) -> Optional[str]:
    """``"window"`` or None for one op of the trace: the window kernels, by
    name."""
    if any(c in hlo for c in trace_reduce.CONTROL_FLOW):
        return None
    return "window" if KERNEL in hlo.partition(" = ")[0] else None


def window_seconds(ctx) -> Optional[float]:
    """Device seconds a traced round of the group (union of its ops'
    intervals, averaged over chips)."""
    if "_window_seconds" in ctx:
        return ctx["_window_seconds"]
    ctx["_window_seconds"] = None
    m = _model(ctx)
    cut = span_readers._cycles_and_devices(span_readers.xplane_events(ctx)) \
        if m else None
    if cut is None:
        return None
    _, _, n_cycles, devs = cut
    total, labels, by_name = 0.0, {}, {}
    for v in devs.values():
        spans = []
        for a, b, name in v[trace_reduce.OPS_LINE]:
            if name not in labels:
                labels[name] = group_of(name, m)
            if labels[name]:
                spans.append((a, b))
                short = trace_reduce.short_name(name)
                by_name[short] = by_name.get(short, 0.0) + (b - a)
        total += span_readers._measure(spans)
    scale = len(devs) * n_cycles * 1e9
    span_readers.say(f"window: {total / scale:.6f} s a traced round in "
                     f"{len(by_name)} kinds of op; the costliest:")
    for short in sorted(by_name, key=lambda k: -by_name[k])[:8]:
        span_readers.say(f"window:   {by_name[short] / scale:.6f} {short}")
    ctx["_window_seconds"] = total / scale if total else None
    return ctx["_window_seconds"]


def window_pairs(t: int, window: int) -> int:
    """(query, key) pairs a sequence of ``t`` positions sees under a
    ``window`` of keys: ``min(i + 1, window)`` for the query at ``i``."""
    full = min(t, window)
    return full * (full + 1) // 2 + (t - full) * window


def window_attention_required(m: dict, pairs: float, layer_steps: float):
    """(FLOPs, bytes) the window cores require for ``pairs`` (query, key)
    pairs inside the window over ``layer_steps`` executions of a sliding
    layer on one sequence: every query head's score and mix a pair, three
    passes; bytes: q, k, v read and the result written a pass (the scores
    never leave the chip's registers in a fused kernel)."""
    t, heads, kv, hd = (m["seq_len"], m["window_heads"],
                        m["num_key_value_heads"], m["head_dim"])
    return (3 * 2.0 * heads * 2 * hd * pairs,
            3 * 4.0 * t * hd * (2 * heads + 2 * kv) * layer_steps)


def roofline_share(ctx):
    m = _model(ctx)
    seconds = window_seconds(ctx) if m else None
    if not seconds:
        return None
    sequences = float(sum(ctx["traced_samples"])) * ctx["epochs"]
    steps = sequences * m["window_layers"]
    flops, nbytes = window_attention_required(
        m, window_pairs(m["seq_len"], m["sliding_window"]) * steps, steps)
    floor_s = max(flops / ctx["peaks"]["bf16_flops_per_s"],
                  nbytes / ctx["peaks"]["hbm_bytes_per_s"]) / ctx["chips"]
    return 100.0 * floor_s / ctx["trace"]["rounds"] / seconds


def share_of_wave(ctx):
    """The group's share of the wave program's device time."""
    seconds = window_seconds(ctx)
    program = ctx["trace"].get("program_s")
    if not seconds or not program:
        return None
    return 100.0 * seconds * ctx["trace"]["rounds"] / program
