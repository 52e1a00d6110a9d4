"""Per-layer metrics of a model whose attention reads keys a learned indexer
selects (DeepSeek sparse attention under grouped-query heads): what the
selected core and the indexer take of a traced round, and what each is
required to do.

Two groups of device operations are read from the traced rounds, each op
found by the shapes its HLO line names (the profiler names an op by its
whole HLO line, result and operands; named scopes do not reach the v5e
trace, PERF.md section 3):

* ``attention``: the selected core of every block, forward, recomputed and
  backward: ops that produce or read a score block, ``[..., block, keys]``
  with ``B x heads`` rows of blocks before it (scores, their softmax,
  their gradients, the row reductions), or produce a mix, ``[B, rows, ...,
  head_dim]`` with the query or key/value heads between (the results, the
  gradients by q, k and v, the rotated heads they are made from);
* ``indexer``: index scores and the selection: ops that produce or read
  the indexer's per-head scores (``B x indexer heads`` rows of blocks) or
  a block of ``I`` itself, ``[B, block, keys]`` in any type but the
  selection's own (the mapped keys, the counts of the search, the
  running sums), and the ops that produce the selection ``pred[B, block,
  keys]`` / ``pred[B, T, T]``.  The core READS the selection, so an
  operand of that type decides nothing.

The functions that give each group's REQUIRED operations and bytes stand
here too, over the pairs the program's own counters report
(`wave.dispatch`'s ``attn_pairs_selected`` / ``attn_pairs_causal``, and
``attn_calls`` layer-steps): the core three passes' worth over the
SELECTED pairs (forward, gradient by the inputs, recomputation not
counted); the indexer ONE pass over the causal pairs, because no gradient
passes through a selection and it is made once a step.  A share of the
roofline is the time the required work takes at the chip's peaks (the
slower of FLOPs / peak FLOP/s and bytes / peak bytes/s) over the device
time the group took.

Every reader returns None where it finds nothing to read (another model,
a program without the counters): the harness then leaves the metric out.
"""

from __future__ import annotations

import json
import math
import os
import re
from typing import Dict, Optional

from benchmark import span_readers, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
COUNTS = ("attn_calls", "attn_pairs_causal", "attn_pairs_selected")
GROUPS = ("attention", "indexer")
_SHAPES = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]+)\]")


def _model(ctx) -> Optional[dict]:
    """The cell's ``model`` keys with its attention block and batch, or
    None for a configuration without an indexer."""
    if "_indexed_model" not in ctx:
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
            if "sa_config" in m and "num_key_value_heads" in m:
                found = dict(m, block=int(cfg["cli"].get(
                    "attn_block_size") or m["seq_len"]),
                    batch=int(cfg["cli"]["batch_size"]))
        ctx["_indexed_model"] = found
    return ctx["_indexed_model"]


def _shapes(hlo: str):
    """``(results, operands)`` of an HLO line, each a list of ``(type,
    dims)``."""
    _, sep, rest = hlo.partition(" = ")
    if not sep:
        return [], []
    depth, cut = 0, len(rest)
    for i, c in enumerate(rest):      # the result type ends at its space
        depth += c == "("
        depth -= c == ")"
        if c == " " and depth == 0:
            cut = i
            break

    def found(text):
        return [(t, tuple(int(d) for d in dims.split(",")))
                for t, dims in _SHAPES.findall(text)]
    return found(rest[:cut]), found(rest[cut:])


def _kind(shape, m) -> Optional[str]:
    """What one array of the trace is to the two groups: ``scores`` (a
    block of the core's), ``mix``, ``index_heads`` (a block of the
    indexer's per-head scores), ``index`` (a block of ``I`` in a type of
    its own), ``selection`` (the mask) or None."""
    kind, dims = shape
    b, t, block = m["batch"], m["seq_len"], m["block"]
    heads, kv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                     m["head_dim"])
    if len(dims) >= 2 and dims[-2] % block == 0 and dims[-1] % block == 0 \
            and dims[-1] <= t and dims[-2] <= heads // kv * block:
        rows = math.prod(dims[:-2]) * (dims[-2] // block)
        if len(dims) >= 3 and rows == b * heads:
            return "scores"
        if len(dims) >= 3 and rows == b * m["sa_config"][
                "indexer_num_heads"] and dims[-2] == block:
            return "index_heads"
        if rows == b and dims[-2] == block:
            return "selection" if kind == "pred" else "index"
    if kind == "pred" and dims in ((b, t, t), (t, t)):
        return "selection"
    if len(dims) >= 4 and dims[-1] == hd and dims[0] == b:
        # one axis of rows (a whole number of blocks), heads on the rest
        rows = [d for d in dims[1:-1] if d % block == 0]
        if len(rows) == 1 and math.prod(dims[1:-1]) // rows[0] in (heads, kv):
            return "mix"
    return None


def group_of(hlo: str, m: dict) -> Optional[str]:
    """``"attention"``, ``"indexer"`` or None for one op of the trace."""
    if any(c in hlo for c in trace_reduce.CONTROL_FLOW):
        return None
    results, operands = _shapes(hlo)
    made = {_kind(s, m) for s in results}
    read = {_kind(s, m) for s in operands}
    if made & {"scores", "mix"}:
        return "attention"
    if made & {"index_heads", "index", "selection"}:
        return "indexer"
    if "scores" in read:
        return "attention"
    if read & {"index_heads", "index"}:
        return "indexer"
    return None


def _group_seconds(ctx) -> Optional[Dict[str, float]]:
    """Device seconds a traced round of each group (union of its ops'
    intervals, averaged over chips)."""
    if "_indexed_groups" in ctx:
        return ctx["_indexed_groups"]
    ctx["_indexed_groups"] = None
    m = _model(ctx)
    cut = span_readers._cycles_and_devices(span_readers.xplane_events(ctx)) \
        if m else None
    if cut is None:
        return None
    _, _, n_cycles, devs = cut
    total = dict.fromkeys(GROUPS, 0.0)
    labels: Dict[str, Optional[str]] = {}
    by_name = {g: {} for g in GROUPS}
    for v in devs.values():
        spans = {g: [] for g in GROUPS}
        for a, b, name in v[trace_reduce.OPS_LINE]:
            if name not in labels:
                labels[name] = group_of(name, m)
            g = labels[name]
            if g:
                spans[g].append((a, b))
                short = trace_reduce.short_name(name)
                by_name[g][short] = by_name[g].get(short, 0.0) + (b - a)
        for g, ivs in spans.items():
            total[g] += span_readers._measure(ivs)
    scale = len(devs) * n_cycles * 1e9
    for g, names in by_name.items():
        span_readers.say(f"{g}: {total[g] / scale:.6f} s a traced round in "
                         f"{len(names)} kinds of op; the costliest:")
        for short in sorted(names, key=lambda k: -names[k])[:8]:
            span_readers.say(f"{g}:   {names[short] / scale:.6f} {short}")
    ctx["_indexed_groups"] = {g: s / scale for g, s in total.items() if s}
    return ctx["_indexed_groups"]


def group_seconds(ctx, key):
    """``key``: ``attention`` or ``indexer``."""
    return (_group_seconds(ctx) or {}).get(key)


def _counts(ctx) -> Optional[Dict[str, float]]:
    """Sums of `wave.dispatch`'s attention counts over the traced
    rounds."""
    ids = span_readers._rounds_by_kind(ctx)["traced"]
    found = [s for s in span_readers.spans(ctx)
             if s["name"] == "wave.dispatch" and COUNTS[1] in s["args"]
             and s["args"].get("trace_id") in ids]
    if not found:
        return None
    return {k: float(sum(s["args"][k] for s in found)) for k in COUNTS}


# -- what the two groups are required to do ---------------------------------------

def selected_attention_required(m: dict, pairs_selected: float,
                                layer_steps: float):
    """(FLOPs, bytes) the selected cores require for ``pairs_selected``
    (query, key) pairs over ``layer_steps`` executions of an attention
    layer on one sequence: every query head's score and mix a pair, three
    passes; bytes: q, k, v read and the result written a pass (the scores
    never leave the chip's registers in a fused kernel), and the
    selection read, a byte a causal pair."""
    t, heads, kv, hd = (m["seq_len"], m["num_attention_heads"],
                        m["num_key_value_heads"], m["head_dim"])
    values = t * hd * (2 * heads + 2 * kv)
    return (3 * 2.0 * heads * 2 * hd * pairs_selected,
            3 * (4.0 * values + t * (t + 1) / 2.0) * layer_steps)


def indexer_required(m: dict, pairs_causal: float, layer_steps: float):
    """(FLOPs, bytes) the index scores and the selection require for
    ``pairs_causal`` (query, key) pairs over ``layer_steps`` executions:
    every indexer head's product a pair, ONE pass (no gradient passes
    through a selection, and it is kept for the backward pass); bytes:
    the indexer's queries, key and head weights read, the selection
    written, a byte a pair."""
    sa = m["sa_config"]
    ih, idim = sa["indexer_num_heads"], sa["indexer_head_dim"]
    values = m["seq_len"] * (ih * idim + idim + ih)
    return (2.0 * ih * idim * pairs_causal,
            4.0 * values * layer_steps + pairs_causal)


def _roofline(ctx, key, required) -> Optional[float]:
    seconds = group_seconds(ctx, key)
    if not seconds:
        return None
    flops, nbytes = required
    rounds = ctx["trace"]["rounds"]
    floor_s = max(flops / ctx["peaks"]["bf16_flops_per_s"],
                  nbytes / ctx["peaks"]["hbm_bytes_per_s"]) / ctx["chips"]
    return 100.0 * floor_s / rounds / seconds


def attention_roofline_share(ctx):
    m = _model(ctx)
    counts = _counts(ctx) if m else None
    if counts is None:
        return None
    return _roofline(ctx, "attention", selected_attention_required(
        m, counts["attn_pairs_selected"], counts["attn_calls"]))


def indexer_roofline_share(ctx):
    m = _model(ctx)
    counts = _counts(ctx) if m else None
    if counts is None:
        return None
    return _roofline(ctx, "indexer", indexer_required(
        m, counts["attn_pairs_causal"], counts["attn_calls"]))


def share_of_wave(ctx):
    """Both groups' share of the wave program's device time."""
    groups = _group_seconds(ctx)
    program = ctx["trace"].get("program_s")
    if not groups or not program:
        return None
    return 100.0 * sum(groups.values()) * ctx["trace"]["rounds"] / program
