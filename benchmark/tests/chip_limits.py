"""The readings a cell's limits are set from, taken on the chip at the
cell's own size, several seeds in one process (set-up is long):

    python3 benchmark/tests/chip_limits.py <workload> <seeds> <control seeds> <fault seeds> [<name>:<seeds>:<flag,value,...> ...]

each list comma-separated (``-`` for none).  For every seed it drives the
cell's own timed call (``run.timed_call``: the CLI's entry, the cell's
files, a window of one round: these readings need no measured window) and
follows the plain reference, and prints the numbers compared.  For a
control seed it drives the program again with its own lower-precision path
switched on (``--compute_dtype bfloat16``: the control of a float32
configuration) against the same reference.  For a fault seed it puts the
reference with a planted fault in the program's place (a state left
unchanged reads 1 on every norm and needs no run; ``half_tokens`` only
where the task leaves out a pad id).  A further argument
drives the program once more on its seeds with the given CLI flags (another
path of the program, as a witness).  Every reading goes through
``fedavg.verdict`` with the cell's committed limits, as a run's does: a
sound seed has to come out correct, a control or a fault not.  One JSON
line per reading goes to stdout and to
``chiprun_out/limits.<workload>.jsonl``, and a summary by kind to stderr.
The process holds one program set of globals and one reference set at a
time: each set compared is let go before the next is made, so a language
model's readings fit the host as a run's do.  The benchmark's own runs
never run this.
"""

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

CONTROL = ("--compute_dtype", "bfloat16")
FAULTS = ("half_batch", "half_tokens")


def seeds_of(arg: str):
    return [] if arg == "-" else [int(s) for s in arg.split(",")]


def main(workload: str, seeds, control_seeds, fault_seeds, variants=(),
         bench=None) -> None:
    """``bench``: BENCHMARK.json's contents, or another's (a test's tiny
    cell, whose caller has put the look for a chip aside)."""
    from benchmark import run
    from benchmark.compile_watch import CompileWatch
    from benchmark.reference import fedavg, tasks
    if bench is None:
        bench = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = run.Cell(bench, workload)
    run.check_device(cell.chips)
    watch = CompileWatch()
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out = open(os.path.join(ROOT, "chiprun_out",
                            f"limits.{workload}.jsonl"), "a")

    readings = []

    def emit(row):
        ok, rows = fedavg.verdict(row["numbers"], cell.limits)
        row["correct"] = ok
        row["failed_on"] = [r["name"] for r in rows if not r["ok"]]
        readings.append(row)
        out.write(json.dumps(row) + "\n")
        if "device" in row:   # the file has all of it, the screen the sum
            row = {**row, "device": {
                "memory_peak_bytes": row["device"]["memory_peak_bytes"]}}
        print(json.dumps(row), flush=True)
        out.flush()

    faults = [f for f in FAULTS
              if f != "half_tokens" or tasks.from_config(cell.config).pad_id
              is not None]

    for seed in seeds:
        t0 = time.time()
        data_dir, arrays = run.ensure_data(cell, seed)
        if arrays is None:
            arrays = run.make_arrays(cell, seed)
        pseed = run.program_seed(seed, cell.config)
        clients = cell.reference.train_clients(arrays, cell.config, pseed)
        del arrays
        t1 = time.time()
        prog = run.timed_call(cell, seed, data_dir, 1, watch)
        t2 = time.time()
        ref = run.follow_reference(cell, clients, pseed)
        t3 = time.time()
        emit({"workload": workload, "seed": seed, "kind": "program",
              "numbers": fedavg.compare(prog["states"], prog["loss_r0"], ref),
              "loss_r0": [prog["loss_r0"], ref["loss_r0"]],
              "data_s": t1 - t0, "program_s": t2 - t1,
              "reference_s": t3 - t2, "device": prog["device"],
              "peak_rss_bytes": run.peak_rss_bytes()})
        del prog
        others = [("control", CONTROL)] if seed in control_seeds else []
        others += [(name, flags) for name, v_seeds, flags in variants
                   if seed in v_seeds]
        for name, flags in others:
            alt = run.timed_call(cell, seed, data_dir, 1, watch,
                                 extra=flags)
            emit({"workload": workload, "seed": seed, "kind": name,
                  "flags": list(flags),
                  "numbers": fedavg.compare(alt["states"], alt["loss_r0"],
                                            ref),
                  "peak_rss_bytes": run.peak_rss_bytes()})
            del alt
        for fault in faults if seed in fault_seeds else ():
            bad = run.follow_reference(cell, clients, pseed, fault=fault)
            emit({"workload": workload, "seed": seed,
                  "kind": "fault:" + fault,
                  "numbers": fedavg.compare(bad["states"], bad["loss_r0"],
                                            ref),
                  "peak_rss_bytes": run.peak_rss_bytes()})
            del bad
        del clients, ref
    out.close()
    for kind in sorted({r["kind"] for r in readings}):
        rows = [r for r in readings if r["kind"] == kind]
        run.say(f"{kind}: {len(rows)} readings, "
                f"{sum(r['correct'] for r in rows)} correct under "
                f"{cell.limits}")
        for name in rows[0]["numbers"]:
            vals = [r["numbers"][name] for r in rows]
            run.say(f"  {name}: {min(vals):.3g} .. {max(vals):.3g}")


if __name__ == "__main__":
    extra = [a.split(":") for a in sys.argv[5:]]
    main(sys.argv[1], seeds_of(sys.argv[2]), seeds_of(sys.argv[3]),
         seeds_of(sys.argv[4]),
         [(n, seeds_of(s), f.split(",")) for n, s, f in extra])
