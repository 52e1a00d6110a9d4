"""Per-layer metrics of an expert model (with latent attention, or with
another attention whose group is read elsewhere): what its two distinctive
kernels take of a traced round, what they are required to do, and what the
router made of the tokens.

Two groups of device operations are read from the traced rounds (the
profiler names an op by its whole HLO line; named scopes do not reach the
v5e trace, PERF.md section 3, so a group is found by the shape the op
produces):

* ``attention``: the latent attention core of every block, forward,
  recomputed and backward: the ops whose result carries the heads beside a
  query block, ``[B, heads, block, keys]`` (scores, their softmax, their
  gradients) or ``[B, block, heads, head_dim]`` (the mix and the gradients
  by q, k and v) at the configuration's ``attn_block_size``.  Only for a
  model with latent attention (``LATENT_KEYS``); another attention has a
  reader of its own (``sparse_attention.py``);
* ``experts``: the grouped products over the experts held, a tile of rows
  against one expert's matrices inside the loop over the active tiles
  (``models/moe.grouped_gated_mlp``): results ``[tile, moe_width]``,
  ``[tile, hidden]`` and the weight gradients ``[hidden, moe_width]``,
  ``[moe_width, hidden]``.

The functions that give each group's REQUIRED operations and bytes stand
here too (`attention_required`, `experts_required`): three forward passes'
worth (forward, gradient by the inputs, gradient by the weights), the
recomputation a checkpoint adds not counted, over the causal half of the
scores and over the (token, expert) pairs that were really sent to a held
expert in the traced rounds (the program counts them: `wave.dispatch`'s
``expert_assignments_held``).  A share of the roofline is the time the
required work takes at the chip's peaks (the slower of FLOPs / peak FLOP/s
and bytes / peak bytes/s) over the device time the group took.

Every reader returns None where it finds nothing to read (a program
without the spans or the ops, as the parent of the PR that brought this
file is): the harness then leaves the metric out.
"""

from __future__ import annotations

import json
import os
import re
from typing import Dict, Optional

from benchmark import span_readers, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
TILE = 512      # rows a tile of the grouped product holds (models/moe.py)
COUNTS = ("tokens", "expert_assignments", "expert_assignments_held",
          "expert_load_max", "expert_load_mean")
_SHAPE = re.compile(r"= \(?[a-z0-9]+\[([0-9,]*)\]")
# the head widths of latent attention: the attention group is looked for
# only in a model file that has them
LATENT_KEYS = ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim")


def _model(ctx) -> Optional[dict]:
    """The cell's ``model`` keys and its attention block, or None for a
    configuration that is no expert model."""
    if "_expert_model" not in ctx:
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
            if "moe_intermediate_size" in m and "experts_held" in m:
                found = dict(m, block=int(cfg["cli"].get(
                    "attn_block_size") or m["seq_len"]),
                    batch=int(cfg["cli"]["batch_size"]))
        ctx["_expert_model"] = found
    return ctx["_expert_model"]


def _produced(hlo: str):
    """The dimensions of what an op produces (its first, for a tuple)."""
    hit = _SHAPE.search(hlo)
    if not hit or not hit.group(1):
        return ()
    return tuple(int(d) for d in hit.group(1).split(","))


def group_of(hlo: str, m: dict) -> Optional[str]:
    """``"attention"``, ``"experts"`` or None for one op of the trace."""
    if any(c in hlo for c in trace_reduce.CONTROL_FLOW):
        return None
    dims = _produced(hlo)
    heads, block = m["num_attention_heads"], m["block"]
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    if len(dims) == 4 and heads in dims[1:3] and all(
            k in m for k in LATENT_KEYS):
        head_dims = {m["qk_nope_head_dim"] + m["qk_rope_head_dim"],
                     m["v_head_dim"]}
        # [B, heads, block, keys] or [B, block, heads, head_dim] (or a
        # key range of k / v: a multiple of the block, heads, head_dim)
        rest = [x for i, x in enumerate(dims[1:], 1) if x != heads or i > 2]
        if any(x % block == 0 for x in rest) and (
                dims[3] in head_dims or dims[1] == heads):
            return "attention"
    if dims in ((TILE, f), (TILE, d), (d, f), (f, d), (1, d, f), (1, f, d)):
        return "experts"
    return None


def _group_seconds(ctx) -> Optional[Dict[str, float]]:
    """Device seconds a traced round of each group (union of its ops'
    intervals, averaged over chips)."""
    if "_expert_groups" in ctx:
        return ctx["_expert_groups"]
    ctx["_expert_groups"] = None
    m = _model(ctx)
    cut = span_readers._cycles_and_devices(span_readers.xplane_events(ctx)) \
        if m else None
    if cut is None:
        return None
    _, _, n_cycles, devs = cut
    total = {"attention": 0.0, "experts": 0.0}
    labels: Dict[str, Optional[str]] = {}
    by_name = {"attention": {}, "experts": {}}
    for v in devs.values():
        spans = {"attention": [], "experts": []}
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
        for short in sorted(names, key=lambda k: -names[k])[:6]:
            span_readers.say(f"{g}:   {names[short] / scale:.6f} {short}")
    ctx["_expert_groups"] = {g: s / scale for g, s in total.items() if s}
    return ctx["_expert_groups"]


def group_seconds(ctx, key):
    """``key``: ``attention`` or ``experts``."""
    return (_group_seconds(ctx) or {}).get(key)


def _counts(ctx, kind: str) -> Optional[Dict[str, float]]:
    """Sums of `wave.dispatch`'s expert counts over the rounds of
    ``kind`` (``window`` or ``traced``)."""
    ids = span_readers._rounds_by_kind(ctx)[kind]
    found = [s for s in span_readers.spans(ctx)
             if s["name"] == "wave.dispatch" and COUNTS[1] in s["args"]
             and s["args"].get("trace_id") in ids]
    if not found:
        return None
    return {k: float(sum(s["args"][k] for s in found)) for k in COUNTS}


# -- what the two groups are required to do ---------------------------------------

def attention_required(m: dict, sequences: float):
    """(FLOPs, bytes) the attention cores of ``sequences`` training steps
    of one sequence require: scores and mix over the causal half, every
    head of every block, three passes; bytes: q, k, v read and the
    result written a pass (the scores never leave the chip's registers in
    a fused kernel)."""
    t, heads = m["seq_len"], m["num_attention_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    layers = m["num_hidden_layers"]
    macs = heads * (qk + m["v_head_dim"]) * t * (t + 1) / 2.0
    values = t * heads * (2 * qk + 2 * m["v_head_dim"])
    return (3 * 2.0 * macs * layers * sequences,
            3 * 4.0 * values * layers * sequences)


def experts_required(m: dict, held_assignments: float, layer_steps: float):
    """(FLOPs, bytes) the grouped products require for
    ``held_assignments`` (token, held expert) pairs over ``layer_steps``
    executions of an expert layer: three matrices a pair, three passes;
    bytes: every held expert's three matrices read in the forward and in
    the backward pass and their gradients written, the pairs' rows read
    and written."""
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    weights = m["experts_held"] * 3 * d * f
    return (3 * 2.0 * held_assignments * 3 * d * f,
            4.0 * (3 * weights * layer_steps + 3 * 2 * d * held_assignments))


def _roofline(ctx, key, required) -> Optional[float]:
    seconds = group_seconds(ctx, key)
    if not seconds or required is None:
        return None
    flops, nbytes = required
    rounds = ctx["trace"]["rounds"]
    floor_s = max(flops / ctx["peaks"]["bf16_flops_per_s"],
                  nbytes / ctx["peaks"]["hbm_bytes_per_s"]) / ctx["chips"]
    return 100.0 * floor_s / rounds / seconds


def attention_roofline_share(ctx):
    m = _model(ctx)
    if m is None:
        return None
    return _roofline(ctx, "attention", attention_required(
        m, float(sum(ctx["traced_samples"])) * ctx["epochs"]))


def experts_roofline_share(ctx):
    m, counts = _model(ctx), _counts(ctx, "traced")
    if m is None or counts is None:
        return None
    steps = counts["tokens"] / (m["seq_len"] * m["batch"])   # layer-steps
    return _roofline(ctx, "experts", experts_required(
        m, counts["expert_assignments_held"], steps))


def experts_share_of_wave(ctx):
    """The grouped products' share of the wave program's device time."""
    seconds = group_seconds(ctx, "experts")
    program = ctx["trace"].get("program_s")
    if not seconds or not program:
        return None
    return 100.0 * seconds * ctx["trace"]["rounds"] / program


def held_assignment_share(ctx):
    """Of the window's (token, expert) pairs, the share whose expert this
    chip holds: 100 x held / all experts at even routing."""
    counts = _counts(ctx, "window")
    if counts is None or not counts["expert_assignments"]:
        return None
    return (100.0 * counts["expert_assignments_held"]
            / counts["expert_assignments"])


def max_expert_load(ctx):
    """The fullest held expert's tokens over the mean held expert's, over
    the window's layer-steps: 1 at even routing."""
    counts = _counts(ctx, "window")
    if counts is None or not counts["expert_load_mean"]:
        return None
    return counts["expert_load_max"] / counts["expert_load_mean"]
