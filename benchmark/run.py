#!/usr/bin/env python3
"""One run of one benchmark cell.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, in one process, on the machine that holds the
chips.  A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a
configuration (``benchmark/configs/<name>.json`` with its plain reference
``<name>.py`` beside it) under a traffic mix (``benchmark/traffic/<name>.json``,
which names in ``benchmark/hooks/`` where the harness taps the algorithm it
drives) with its limits (``benchmark/limits/<cell>.json``).  Nothing in this
file names a cell, a model or an algorithm; a later cell is new files and
new entries.

What a run does (PERF.md has the why):

1. set-up: the cell's data files from ``--seed`` in the program's real
   on-disk formats (kept in ``benchmark/.cache`` by seed); on the first run
   of the cell in a checkout, a short calibration call that compiles
   everything into the persistent cache and measures a warm round;
2. ONE call of ``fedml_tpu.experiments.main.main(argv)``, the CLI's own
   entry.  Its first ``KEEP`` rounds are set-up (trace, cache load, the
   round-0 evaluation) and are what the plain reference follows; the next
   N rounds are the window, timed by the harness's own clock on the
   program's round entry (``probe.py``), the chips' memory read all the
   while; with ``--trace 1`` a few more rounds follow under the profiler;
   one more round closes the call;
3. after the call: the program's ledger (phases, recompiles), the
   profiler's trace (``--trace 1``), then the plain reference on the same
   seed and the comparison that decides ``correct``.

The last line of stdout is one JSON object (see BENCHMARK.json's contract);
the numbers compared stand beside their limits in it and in the last lines
of stderr.
"""

from __future__ import annotations

T_PROCESS_START = __import__("time").time()

import argparse
import contextlib
import hashlib
import importlib
import json
import math
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE = os.path.join(HERE, ".cache")
KEEP = 3              # rounds of set-up the reference follows
MIN_ROUNDS = 8        # a window is never fewer rounds than this
TRACE_SECONDS = 3.0   # the rounds traced after the window: about so long,
TRACE_ROUNDS = (2, 4)  # and between this many
DATA_DIRS_KEPT = 6


def say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def call(spec: str):
    """``pkg.module:function`` -> the function."""
    mod, _, fn = spec.partition(":")
    return getattr(importlib.import_module(mod), fn)


def program_seed(seed: int, config: dict) -> int:
    """The seed the CLI is given.  A configuration may pin it (the CLI has
    one ``--seed`` for the initial weights and for the data split, and a
    split that changes with the seed changes the work); otherwise the
    run's seed, folded into what a signed 32-bit integer holds."""
    pinned = config["cli"].get("seed")
    return int(pinned) if pinned is not None else seed % (2 ** 31 - 1)


class Cell:
    """A workload entry of BENCHMARK.json with its files read."""

    def __init__(self, bench: dict, name: str, root: str = ROOT):
        rows = [w for w in bench["workloads"] if w["name"] == name]
        if not rows:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                             f"have {[w['name'] for w in bench['workloads']]}")
        self.name = name
        self.row = rows[0]
        self.chips = int(self.row["chips"])
        cfg_row = [c for c in bench["configs"]
                   if c["name"] == self.row["config"]][0]
        self.config = load_json(os.path.join(root, cfg_row["file"]))
        base = os.path.dirname(os.path.join(root, cfg_row["file"]))
        bench_dir = os.path.dirname(base)
        self.traffic = load_json(os.path.join(
            bench_dir, "traffic", self.row["traffic"] + ".json"))
        self.hooks = load_json(os.path.join(
            HERE, "hooks", self.traffic["hooks"] + ".json"))
        self.limits = load_json(os.path.join(
            bench_dir, "limits", name + ".json"))["limits"]
        self.reference = importlib.import_module(self.config["reference"])
        self.cli = {**self.config["cli"], **self.traffic["cli"]}
        self.bench = bench
        self.bench_dir = bench_dir

    def argv(self, seed: int, data_dir: str, run_dir: str, rounds: int,
             extra=()) -> list:
        args = dict(self.cli)
        args["seed"] = program_seed(seed, self.config)
        args.update({"data_dir": data_dir, "run_dir": run_dir,
                     "perf": "true", "log_stdout": "false",
                     "comm_round": rounds,
                     # evaluation at round 0 and at the last round only,
                     # both outside the window
                     "frequency_of_the_test": 10 ** 6})
        out = []
        for k, v in args.items():
            out += ["--" + k, str(v)]
        return out + list(extra)

    def metric_rows(self, kind: str) -> list:
        return [m for m in self.bench[kind]
                if self.name in m.get("workloads", [self.name])]


# ---------------------------------------------------------------------------
# set-up

def ensure_data(cell: Cell, seed: int):
    """The cell's data files for this seed; returns (dir, arrays or None).
    The arrays are made again after the window when the files were there
    already (the reference needs them, set-up does not)."""
    d = cell.config["data"]
    # the files are the generator's arguments as much as the seed's
    what = hashlib.sha256(json.dumps(d, sort_keys=True).encode()).hexdigest()
    tag = f"{cell.row['config']}-{what[:8]}-{seed}"
    root = os.path.join(CACHE, "data")
    out = os.path.join(root, tag)
    if os.path.exists(os.path.join(out, ".complete")):
        os.utime(out)
        return out, None
    t0 = time.time()
    arrays = make_arrays(cell, seed)
    shutil.rmtree(out, ignore_errors=True)
    call(d["writer"])(arrays, out)
    with open(os.path.join(out, ".complete"), "w") as f:
        f.write(tag)
    # the host keeps every block once written: cap what stays around
    kept = sorted((os.path.join(root, n) for n in os.listdir(root)),
                  key=os.path.getmtime)
    for old in kept[:-DATA_DIRS_KEPT]:
        shutil.rmtree(old, ignore_errors=True)
    say(f"data: wrote {tag} in {time.time() - t0:.1f} s")
    return out, arrays


def make_arrays(cell: Cell, seed: int):
    d = cell.config["data"]
    return call(d["generator"])(seed, **d.get("generator_args", {}))


def drive(cell: Cell, argv: list, probe):
    """One call of the CLI's entry with the round hook (and, traced, the
    host spans) in place.  The program's stdout goes to stderr: the last
    line of stdout is the result's."""
    from benchmark.probe import patched, span_wrapper
    from fedml_tpu.experiments.main import main
    hook = cell.hooks["round_hook"]
    with contextlib.ExitStack() as stack:
        stack.enter_context(patched(hook["target"], probe.wrap))
        if probe.trace_dir is not None:
            for name, target in cell.hooks.get("spans", {}).items():
                stack.enter_context(patched(target, span_wrapper(name)))
        stack.enter_context(contextlib.redirect_stdout(sys.stderr))
        # one run directory, one run: the program appends to metrics.jsonl
        shutil.rmtree(argv[argv.index("--run_dir") + 1], ignore_errors=True)
        return main(argv)


def calibrate(cell: Cell, seed: int, data_dir: str, watch) -> float:
    """Seconds of one warm round cycle, measured once per checkout: the
    CLI takes its number of rounds up front, so the window's has to be
    worked out before the call that holds it.  This call also compiles
    every program of the cell into the persistent cache."""
    path = os.path.join(CACHE, cell.name + ".calib.json")
    if os.path.exists(path):
        return float(load_json(path)["t_warm"])
    from benchmark.probe import RoundProbe
    from benchmark.compile_watch import diff
    before = watch.snapshot()
    t0 = time.time()
    probe = RoundProbe(cell.hooks["round_hook"], first=1, n_window=2,
                       keep=0)
    run_dir = os.path.join(CACHE, "runs", cell.name + ".calib")
    drive(cell, cell.argv(seed, data_dir, run_dir,
                          rounds=probe.rounds_needed), probe)
    edges = probe.window()["edges_mono"]
    t_warm = (edges[-1] - edges[0]) / 2
    os.makedirs(CACHE, exist_ok=True)
    with open(path, "w") as f:
        json.dump({"t_warm": t_warm, "seed": seed,
                   "call_s": time.time() - t0,
                   "compiles": diff(watch.snapshot(), before)}, f)
    say(f"calibration: warm round {t_warm:.4f} s "
        f"({json.dumps(diff(watch.snapshot(), before))})")
    return t_warm


# ---------------------------------------------------------------------------
# after the window

def read_jsonl(path: str) -> list:
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def window_metrics(edges, samples_per_round, setup_s: float) -> dict:
    """The end-to-end metrics, from the harness's stamps alone: over all
    the rounds and all the time of the window."""
    n = len(edges) - 1
    wall = edges[-1] - edges[0]
    return {"round_s": wall / n,
            "samples_per_s": sum(samples_per_round) / wall,
            "setup_s": setup_s}


def device_record(chips: int, memory) -> dict:
    """The device as JAX reports it, and what its fullest chip held while
    the window was open.  ``memory_stats()`` keeps ``peak_bytes_in_use``
    (buffers the process holds: arguments, results, data) and
    ``peak_bytes_reserved`` (scratch a program takes: one compiled with
    7.5 GB of temporaries moved ``reserved`` by 6.4 GB and ``in_use`` by
    nothing; my chip run, PR 26) for the life of the process, so they
    stand for set-up as much as for the window: cell 1's 11.9 GB were
    the round-0 evaluation's.  ``memory_peak_bytes`` is therefore the
    largest sum of the two CURRENT counters that ``probe.MemoryWatch``
    read on one chip inside the window; the process's peaks stand beside
    it in the record."""
    import jax
    devs = jax.devices()
    process = []
    for d in devs[:chips]:
        stats = d.memory_stats() or {}
        process.append({k: int(stats.get(k, 0)) for k in (
            "peak_bytes_in_use", "peak_bytes_reserved", "bytes_limit")})
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips, "memory_peak_bytes": memory.peak_bytes(),
            "window_memory": {"samples": memory.samples,
                              "peak": memory.peak, "at_open": memory.at_open,
                              "at_close": memory.at_close,
                              "reserved_steps": memory.steps},
            "process_memory": process}


def check_device(chips: int) -> dict:
    """The chip's peaks, or no run: a platform other than the TPU, fewer
    chips than the cell asks for, or a kind the table lacks is an error."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"benchmark: needs a TPU, JAX found "
                         f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"benchmark: the cell needs {chips} chips, JAX "
                         f"found {len(devs)}")
    table = load_json(os.path.join(HERE, "peaks.json"))
    kind = devs[0].device_kind
    if kind not in table["by_device_kind"]:
        raise SystemExit(f"benchmark: no peaks for device kind {kind!r} in "
                         f"benchmark/peaks.json")
    return table["by_device_kind"][kind]


def timed_call(cell: Cell, seed: int, data_dir: str, n: int, watch,
               trace_dir=None, n_traced: int = 0, extra=()) -> dict:
    """The one call of the CLI's entry that holds the window: ``KEEP``
    set-up rounds, ``n`` window rounds, ``n_traced`` rounds under the
    profiler where ``trace_dir`` is given, one more.  Returns the window's
    stamps and cohorts, the program's ledger lines of the window, what the
    call produced in its first ``KEEP`` rounds (the globals g0, g1 and
    gKEEP by round index, taken to the host in set-up) and the device's
    record."""
    from benchmark.compile_watch import diff
    from benchmark.probe import MemoryWatch, RoundProbe
    run_dir = os.path.join(CACHE, "runs", cell.name)
    if trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)
    memory = MemoryWatch(cell.chips)
    probe = RoundProbe(cell.hooks["round_hook"], first=KEEP, n_window=n,
                       keep=KEEP, trace_dir=trace_dir, n_traced=n_traced,
                       compile_snapshot=watch.snapshot, memory=memory)
    drive(cell, cell.argv(seed, data_dir, run_dir,
                          rounds=probe.rounds_needed, extra=extra), probe)
    win = probe.window()
    device = device_record(cell.chips, memory)
    states = {0: probe.state_in, **probe.states_out}  # host copies already
    probe.state_in, probe.states_out = None, {}
    lines = read_jsonl(os.path.join(run_dir, "perf.jsonl"))[KEEP:KEEP + n]
    evals = [r for r in read_jsonl(os.path.join(run_dir, "metrics.jsonl"))
             if r.get("round") == 0 and "train_loss" in r]
    return {"window": win, "lines": lines, "device": device,
            "states": states, "loss_r0": float(evals[0]["train_loss"]),
            "compiles_in_window": diff(probe.compiles_at["close"],
                                       probe.compiles_at["open"]),
            "recompiles": sum(int(ln.get("recompiles", 0)) for ln in lines)}


def follow_reference(cell: Cell, clients, pseed: int, **kw) -> dict:
    """The plain reference over the call's first ``KEEP`` rounds, under the
    task the configuration states, its products at the precision the
    configuration states.  What the reference does not follow (another
    optimizer, another server step, a task with no plain implementation,
    a configuration with no task) is an error here, before any round."""
    from benchmark.reference import fedavg, tasks
    a = cell.cli
    fedavg.refuse_unfollowed(a)
    kw.setdefault("precision", cell.config["model"]["matmul_precision"])
    return fedavg.run(cell.reference.build_model(cell.config), clients,
                      task=tasks.from_config(cell.config), seed=pseed,
                      rounds=KEEP, cohort=int(a["client_num_per_round"]),
                      batch_size=int(a["batch_size"]), lr=float(a["lr"]),
                      log=say, **kw)


def peak_rss_bytes() -> int:
    """The process's peak resident set so far (Linux counts it in KiB)."""
    import resource
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def run_cell(bench: dict, workload: str, seed: int, seconds: float,
             trace: bool, peaks: dict, root: str = ROOT, extra=()) -> dict:
    """Everything after the look for a chip.  Returns the result object."""
    import numpy as np
    from benchmark import flops, trace_reduce
    from benchmark.compile_watch import CompileWatch
    from benchmark.reference import fedavg

    cell = Cell(bench, workload, root)
    watch = CompileWatch()
    data_dir, arrays = ensure_data(cell, seed)
    t_warm = calibrate(cell, seed, data_dir, watch)
    n = max(MIN_ROUNDS, math.ceil(seconds / t_warm))
    n_traced = min(max(math.ceil(TRACE_SECONDS / t_warm), TRACE_ROUNDS[0]),
                   TRACE_ROUNDS[1]) if trace else 0
    trace_dir = os.path.join(CACHE, "trace", cell.name) if trace else None
    say(f"window: {n} rounds (warm round {t_warm:.4f} s), {KEEP} set-up "
        f"rounds before, {n_traced} traced rounds and one more after")
    timed = timed_call(cell, seed, data_dir, n, watch, trace_dir, n_traced,
                       extra)
    win, lines, device = timed["window"], timed["lines"], timed["device"]
    prog_states, prog_loss_r0 = timed["states"], timed["loss_r0"]
    in_window, recompiles = timed["compiles_in_window"], timed["recompiles"]
    edges = win["edges_mono"]
    failed = n if (recompiles or in_window["compiles"]) else 0

    # the reference's view of the data (made again if the files were kept)
    if arrays is None:
        arrays = make_arrays(cell, seed)
    pseed = program_seed(seed, cell.config)
    clients = cell.reference.train_clients(arrays, cell.config, pseed)
    counts = np.asarray([len(y) for _, y in clients])
    samples, traced_samples = (
        [int(counts[np.asarray(ids)].sum()) for ids in win[key]]
        for key in ("cohorts", "traced_cohorts"))
    e2e = window_metrics(edges, samples,
                         setup_s=win["start_wall"] - T_PROCESS_START)

    args = cell.cli
    ctx = {"cell": cell.name, "chips": cell.chips, "peaks": peaks,
           "edges": edges, "n_rounds": n, "perf_lines": lines,
           "window_s": edges[-1] - edges[0], "samples": samples,
           "traced_samples": traced_samples,
           "epochs": int(args.get("epochs", 1)),
           "train_flops_per_sample": flops.train_flops_per_sample(
               cell.reference, cell.config, clients[0][0].shape[1:],
               clients[0][0].dtype),
           "memory_peak_bytes": device["memory_peak_bytes"], "trace": {}}
    result = {"attempted": n, "failed": failed, "device": device}
    if trace:
        t0 = time.time()
        xplane = trace_reduce.find_xplane(trace_dir)
        from benchmark.probe import ROUND_SPAN
        events = trace_reduce.load_xplane(
            xplane, host_names=list(cell.hooks.get("spans", {}))
            + [ROUND_SPAN])
        ctx["trace"] = trace_reduce.reduce(
            events, program=cell.hooks.get("wave_program"),
            window=ROUND_SPAN)
        if ctx["trace"].get("rounds") != n_traced:
            for row in trace_reduce.describe_xplane(xplane):
                say("trace:", row)
            raise SystemExit(
                f"benchmark: the trace holds {ctx['trace'].get('rounds')} "
                f"round cycles with device operations, not {n_traced}")
        say(f"trace: {len(events)} events reduced in "
            f"{time.time() - t0:.1f} s: "
            f"{ {k: v for k, v in ctx['trace'].items() if not isinstance(v, list)} }")
        device["busy_s"] = ctx["trace"]["busy_s"]
        device["window_s"] = ctx["trace"]["window_s"]
        result["breakdown"] = {"device_ops": ctx["trace"]["device_ops"],
                               "idle_gaps": ctx["trace"]["idle_gaps"]}
        metrics = {}
        for row in cell.metric_rows("per_layer"):
            spec = load_json(os.path.join(cell.bench_dir, "layer_metrics",
                                          row["name"] + ".json"))
            value = call(spec["reader"])(ctx, **spec.get("args", {}))
            if value is not None:
                metrics[row["name"]] = {"value": value, "unit": row["unit"]}
    else:
        metrics = {row["name"]: {"value": e2e[row["name"]],
                                 "unit": row["unit"]}
                   for row in cell.metric_rows("end_to_end")}
    result["metrics"] = metrics

    # the plain reference, once the window has closed, the peak is read
    # and the program's state is gone
    t0 = time.time()
    ref = follow_reference(cell, clients, pseed)
    # the room the host has left: what the next, larger tree would add to
    # (a reading for PERF.md, no metric and no limit)
    peak_rss = {"after_reference": peak_rss_bytes()}
    numbers = fedavg.compare(prog_states, prog_loss_r0, ref)
    peak_rss["after_compare"] = peak_rss_bytes()
    ok, rows = fedavg.verdict(numbers, cell.limits)
    reference_s = time.time() - t0
    correct = bool(ok and not failed)
    say(f"peak resident set: {peak_rss['after_reference']} bytes after the "
        f"reference, {peak_rss['after_compare']} after the comparison")

    detail = {"workload": workload, "seed": seed, "program_seed": pseed,
              "rounds": n, "t_warm": t_warm, "round_gaps_s":
              [b - a for a, b in zip(edges, edges[1:])],
              "samples_per_round": samples, "end_to_end": e2e,
              "compiles_in_window": in_window, "recompiles": recompiles,
              "compiles_total": watch.snapshot(), "numbers": numbers,
              "train_flops_per_sample": ctx["train_flops_per_sample"],
              "program_loss_r0": prog_loss_r0,
              "reference_loss_r0": ref["loss_r0"],
              "reference_s": reference_s, "peak_rss_bytes": peak_rss,
              "total_s": time.time() - T_PROCESS_START, "trace": ctx["trace"],
              "phases": [ln.get("phases") for ln in lines],
              "global_crc": [ln.get("global_crc") for ln in lines]}
    os.makedirs(CACHE, exist_ok=True)
    with open(os.path.join(CACHE, "last_run.json"), "w") as f:
        json.dump(detail, f)
    print(json.dumps({k: detail[k] for k in (
        "rounds", "t_warm", "end_to_end", "compiles_in_window", "recompiles",
        "compiles_total", "numbers", "train_flops_per_sample", "reference_s",
        "peak_rss_bytes", "total_s")}))
    result = {"correct": correct, **result,
              "compared": {r["name"]: {"value": r["value"],
                                       "limit": r["limit"]} for r in rows}}
    if failed:
        result["compared"]["compiles_in_window"] = {
            "value": in_window["compiles"] + recompiles, "limit": 0}
    for name, row in result["compared"].items():
        say(f"compared {name}: {row['value']} (limit {row['limit']})")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    os.chdir(ROOT)
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    rows = [w for w in bench["workloads"] if w["name"] == a.workload]
    if not rows:
        raise SystemExit(f"no workload {a.workload!r} in BENCHMARK.json")
    import fedml_tpu  # noqa: F401  (a bare benchmark directory stops here)
    peaks = check_device(int(rows[0]["chips"]))
    result = run_cell(bench, a.workload, a.seed, a.seconds, bool(a.trace),
                      peaks)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
