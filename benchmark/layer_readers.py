"""Readers of the per-layer metrics.

Each ``layer_metrics/<name>.json`` names one of these (or a function a later
PR brings in a file of its own) as ``module:function`` with its arguments.
A reader takes the traced run's context and returns the metric's value, or
None where there is nothing to read: the harness then leaves the metric out
of the line.  It never returns 0 for a share of a peak.

The context: ``perf_lines`` (the program's ledger lines of the window's
rounds), ``edges`` (the harness's stamps), ``n_rounds``, ``window_s``,
``samples`` (real training rows of each round of the window),
``train_flops_per_sample``, ``epochs``, ``chips``, ``peaks``,
``memory_peak_bytes`` (what a chip held while the window was open),
``trace`` (what ``trace_reduce.reduce`` made of the profiler's trace: the
rounds traced after the window, ``rounds`` of them in ``window_s``
seconds) and ``traced_samples`` (their rows).  Seconds a round and the
whole step's share of the peak come from the window, which no profiler
slows; what only the trace shows comes from the traced rounds.
"""

from __future__ import annotations


def _phase_sum(ctx, phases):
    found, total = False, 0.0
    for line in ctx["perf_lines"]:
        for p in phases:
            if p in (line.get("phases") or {}):
                found = True
                total += float(line["phases"][p])
    return total if found else None


def phase_per_round(ctx, phases):
    """Seconds a round spent in the named ledger phases (program spans)."""
    total = _phase_sum(ctx, phases)
    return None if total is None else total / ctx["n_rounds"]


def round_other(ctx, phases):
    """Window wall per round minus the named phases: the round loop's own
    work (sampling, host copies of the global, CRC, ledger, publish)."""
    total = _phase_sum(ctx, phases)
    if total is None:
        return None
    return (ctx["window_s"] - total) / ctx["n_rounds"]


def gap_percentile(ctx, q):
    """The q-th percentile of the window's round cycles (the gaps between
    the harness's consecutive stamps), all of them: the tail beside the
    mean that ``round_s`` is."""
    gaps = sorted(b - a for a, b in zip(ctx["edges"], ctx["edges"][1:]))
    if not gaps:
        return None
    pos = (len(gaps) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(gaps) - 1)
    return gaps[lo] + (gaps[hi] - gaps[lo]) * (pos - lo)


def trace_per_round(ctx, key):
    """A quantity of the trace reduction, a traced round."""
    value = ctx["trace"].get(key)
    if value is None or not ctx["trace"].get("rounds"):
        return None
    return value / ctx["trace"]["rounds"]


def _required_flops(ctx, samples="samples"):
    return (ctx["train_flops_per_sample"] * ctx["epochs"]
            * float(sum(ctx[samples])))


def _peak(ctx):
    return ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"]


def mfu_of_window(ctx):
    """Model-required FLOPs of the window's rounds (real rows only,
    forward x 3) over the window's wall time, as a share of the chips'
    bf16 peak."""
    return 100.0 * _required_flops(ctx) / ctx["window_s"] / _peak(ctx)


def mfu_of_program(ctx):
    """The traced rounds' FLOPs, counted the same way, over the device
    time of the traced program's ops."""
    busy = ctx["trace"].get("program_s")
    if not busy:
        return None
    return (100.0 * _required_flops(ctx, "traced_samples") / busy
            / _peak(ctx))


def device_idle_share(ctx):
    """1 - busy time over the traced window, both on the trace's clock."""
    busy = ctx["trace"].get("busy_s")
    if busy is None:
        return None
    return 100.0 * (1.0 - busy / ctx["trace"]["window_s"])


def peak_hbm_share(ctx):
    if not ctx["memory_peak_bytes"]:
        return None
    return 100.0 * ctx["memory_peak_bytes"] / ctx["peaks"]["hbm_bytes"]
