"""Test-only entry: a run's control flow end to end on the CPU at a tiny size.

    JAX_PLATFORMS=cpu python benchmark/tests/rehearse.py [femnist|resnet56|shakespeare] [seed]

It skips the harness's look for a chip (and nothing else): the tiny
configuration and traffic files under ``tests/tiny`` go through the same
``run_cell`` as a cell.  Not an option of ``run.py``; what it prints is no
measurement.
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

FAKE_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
              "hbm_bytes": 16e9}
CELLS = {"femnist": ("femnist_cnn", "cohort8_wave4", "femnist_cnn.cohort8"),
         "resnet56": ("resnet56_cifar10", "silos3_wave3",
                      "resnet56_cifar10.silos3"),
         "shakespeare": ("fed_shakespeare_moe", "cohort4_wave2",
                         "fed_shakespeare_moe.cohort4")}


def tiny_bench(which: str) -> dict:
    config, traffic, cell = CELLS[which]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [{"name": config, "file":
                         f"benchmark/tests/tiny/configs/{config}.json"}]
    bench["workloads"] = [{"name": cell, "config": config,
                           "traffic": traffic, "chips": 1}]
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            m.pop("workloads", None)
    return bench


def rehearse(which: str, seed: int, seconds: float = 1.0, extra=()) -> dict:
    from benchmark import run
    return run.run_cell(tiny_bench(which), CELLS[which][2], seed, seconds,
                        trace=False, peaks=FAKE_PEAKS, extra=extra)


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "femnist"
    seed = int(sys.argv[2]) if len(sys.argv) > 2 else 2147483659
    print(json.dumps(rehearse(which, seed)))
