"""Readers of where set-up's seconds go inside the compiler (ISSUE 41).

Since PR 41 the program records each stage of every compile as a span
(``jit.trace``, ``jit.lower``, ``jit.compile``, with the arg ``fun`` and,
on ``jit.compile``, ``cache_hit`` / ``cache_miss``) under the span that
paid it.  Set-up is everything before the harness's first edge.  A
program without those spans (the parent of PR 41) reads None here and the
metrics are left out of its line.
"""

from __future__ import annotations

import re
from collections import defaultdict

from benchmark.span_readers import _measure, say, spans

STAGES = ("jit.trace", "jit.lower", "jit.compile")


def before_window(ctx, name, part=None):
    """Seconds of the union of the spans called ``name`` that start before
    the window (with ``part``, of those whose arg ``part`` is truthy):
    nested stages count once.  None where no span is called ``name``."""
    found = [s for s in spans(ctx)
             if s["name"] == name and s["t0"] < ctx["edges"][0]]
    if not found:
        return None
    log_setup_funs(ctx)
    return _measure((s["t0"], s["t1"]) for s in found
                    if part is None or s["args"].get(part))


def log_setup_funs(ctx, top: int = 10) -> None:
    """Once a run: the ``top`` programs that cost set-up most, by their
    own seconds (a stage less the stages nested in it) of tracing,
    lowering and compiling, each with the persistent cache's verdicts."""
    if ctx.get("_setup_funs_logged"):
        return
    ctx["_setup_funs_logged"] = True
    before = [s for s in spans(ctx) if s["name"] in STAGES
              and s["t0"] < ctx["edges"][0]]
    nested = defaultdict(float)
    for s in before:
        nested[s["args"].get("parent_id")] += s["t1"] - s["t0"]
    cost = defaultdict(lambda: defaultdict(float))
    for s in before:
        # a lowered or compiled module is named `jit(<fun>)` or `jit_<fun>`
        fun = re.sub(r"^jit\((.*)\)$|^jit_(.*)$",
                     lambda m: m.group(1) or m.group(2),
                     s["args"].get("fun", ""))
        row = cost[fun]
        row[s["name"]] += (s["t1"] - s["t0"]
                           - nested[s["args"].get("span_id")])
        row["hit"] += s["args"].get("cache_hit", 0)
        row["miss"] += s["args"].get("cache_miss", 0)
        row["compiles"] += s["name"] == "jit.compile"
    own = {k: sum(row[k] for row in cost.values()) for k in STAGES}
    say(f"compile: set-up's own seconds traced | lowered | compiled: "
        f"{own['jit.trace']:.4f} | {own['jit.lower']:.4f} | "
        f"{own['jit.compile']:.4f}, "
        f"{_measure((s['t0'], s['t1']) for s in before):.4f} s in all")
    say(f"compile: set-up's {len(cost)} programs, own seconds traced | "
        f"lowered | compiled (persistent cache hits / misses of compiles)")
    for fun in sorted(cost, key=lambda f: -sum(
            cost[f][k] for k in STAGES))[:top]:
        row = cost[fun]
        say(f"compile:   {fun[:40]:<40} {row['jit.trace']:.4f} | "
            f"{row['jit.lower']:.4f} | {row['jit.compile']:.4f} "
            f"({int(row['hit'])} / {int(row['miss'])} of "
            f"{int(row['compiles'])})")
