"""On-chip reproduction of a published benchmark row.

benchmark/README.md:12 row: logistic regression on MNIST — 1000 clients,
10 per round, B=10, SGD lr=0.03, E=1, target >75 train accuracy past 100
rounds.  The CPU tier already proves this config learns
(tests/test_convergence.py::test_mnist_lr_to_75 on the hermetic learnable
twin); this script runs the SAME config end-to-end on the attached TPU
and writes the full accuracy curve + wall-clock to MNIST_LR_TPU.json —
the committed artifact closing the loop from SURVEY §6 on the chip side.

Every eval lands incrementally in MNIST_LR_TPU.json.partial so a kill
mid-run still leaves the curve measured so far on disk (the same
hardening as scripts/flagship_accuracy.py).

Usage: `python scripts/mnist_lr_tpu.py` (TPU; minutes at measured round
rates).  `--platform cpu --rounds 8` is the wiring sanity run.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", default="tpu", choices=["cpu", "tpu"])
    ap.add_argument("--rounds", type=int, default=120)
    ap.add_argument("--clients", type=int, default=1000)
    ap.add_argument("--eval_every", type=int, default=10)
    ap.add_argument("--json_out", default="MNIST_LR_TPU.json")
    args = ap.parse_args()

    import jax
    if args.platform != "tpu":
        # pin before any backend query
        jax.config.update("jax_platforms", args.platform)

    from fedml_tpu.algorithms import FedAvg, FedAvgConfig
    from fedml_tpu.data.synthetic import mnist_learnable_twin
    from fedml_tpu.models import LogisticRegression
    from fedml_tpu.trainer.workload import ClassificationWorkload

    config = {"model": "lr", "dataset": "mnist_learnable_twin",
              "clients": args.clients, "clients_per_round": 10,
              "batch_size": 10, "lr": 0.03, "epochs": 1,
              "rounds": args.rounds,
              "reference_row": "benchmark/README.md:12 — >75 train acc "
                               "past 100 rounds"}
    data = mnist_learnable_twin(num_clients=args.clients, batch_size=10,
                                seed=0)
    wl = ClassificationWorkload(
        LogisticRegression(input_dim=784, output_dim=10), num_classes=10,
        grad_clip_norm=None)
    curve = []

    class Sink:
        """Append every eval to <out>.partial as it lands — a wedge
        mid-run still leaves the curve measured so far on disk."""

        def log(self, metrics, step=None):
            if "train_acc" not in metrics:
                return
            curve.append({"round": step,
                          "train_acc": metrics.get("train_acc"),
                          "test_acc": metrics.get("test_acc")})
            with open(args.json_out + ".partial", "w") as f:
                json.dump({"partial": True, "config": config,
                           "curve_so_far": curve}, f, indent=1)

    cfg = FedAvgConfig(comm_round=args.rounds, client_num_per_round=10,
                       epochs=1, batch_size=10, lr=0.03,
                       frequency_of_the_test=args.eval_every, seed=0)
    algo = FedAvg(wl, data, cfg, sink=Sink())
    dev = jax.devices()[0]
    t0 = time.time()
    params = algo.run()
    wall_s = time.time() - t0
    final = algo.evaluate_global(params)
    out = {"platform": dev.platform,
           "device_kind": str(getattr(dev, "device_kind", "unknown")),
           "captured_at": time.time(), "config": config,
           "wall_clock_s": wall_s,
           "final_train_acc": float(final["train_acc"]),
           "final_test_acc": float(final["test_acc"]),
           "target_met": bool(final["train_acc"] > 0.75),
           "curve": curve}
    with open(args.json_out, "w") as f:
        json.dump(out, f, indent=2)
    try:
        os.remove(args.json_out + ".partial")
    except OSError:
        pass
    print(json.dumps({"final_train_acc": out["final_train_acc"],
                      "target_met": out["target_met"],
                      "wall_clock_s": round(wall_s, 1)}))
    # the >75 target is published for a >100-round budget
    # (benchmark/README.md:12); a short --rounds wiring sanity run is
    # EXPECTED to miss it on the calibrated twin (0.54 at round 30) and
    # must not read as a failure
    if not out["target_met"] and args.rounds >= 100:
        sys.exit(4)


if __name__ == "__main__":
    main()
