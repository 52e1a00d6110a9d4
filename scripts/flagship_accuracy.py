"""Flagship accuracy run: the benchmark/README.md:105
CIFAR10 ResNet-56 config — 10 clients, LDA(0.5) non-IID, B=64, SGD
lr=0.001 wd=0.001, E=20 local epochs, 100 rounds — executed end-to-end,
with the centralized twin trained at the same budget for the published
centralized-vs-federated comparison (93.19 vs 87.12).

Real CIFAR10 is not downloadable on this host, so by default the run uses
the LDA-partitioned learnable CIFAR twin (data/synthetic.py
cifar_learnable_twin); pass --data_dir to run on a real CIFAR-10 pickle
tree instead.  Writes FLAGSHIP_CURVE.json:

* the full federated accuracy curve (eval every ``--eval_every`` rounds),
* the centralized curve at the same number of gradient steps,
* the retention ratio fed/centralized — the hermetic proxy for the
  published 87.12/93.19 = 0.935,
* the reference's published trajectory (normalized round fraction) when
  the pretrained curve files parse, for shape comparison.

TPU: `python scripts/flagship_accuracy.py` (full config, ~100 rounds).
CPU sanity: `--preset cpu_small` shrinks rounds/epochs/samples to
minutes while keeping model, partition, and optimizer real.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REF_CURVES = "/root/reference/fedml_api/model/cv/pretrained/CIFAR10/resnet56"


class PartialSink:
    """MetricsSink that appends every eval to <json_out>.partial as it
    lands: a timeout kill mid-run must still leave the curve measured so
    far on disk."""

    def __init__(self, path, meta):
        self.path, self.meta, self.curve = path, meta, []

    def log(self, metrics, step=None):
        self.curve.append({"round": step,
                           "train_acc": metrics.get("train_acc"),
                           "test_acc": metrics.get("test_acc")})
        with open(self.path, "w") as f:
            json.dump({"partial": True, "config": self.meta,
                       "federated_curve_so_far": self.curve}, f,
                      indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", default="tpu", choices=["cpu", "tpu"])
    ap.add_argument("--preset", default="full",
                    choices=["full", "cpu_small"],
                    help="full = published config; cpu_small = scaled "
                         "minutes-long sanity run (same model/partition)")
    ap.add_argument("--data_dir", default=None,
                    help="real CIFAR-10 pickle tree; default = learnable twin")
    ap.add_argument("--rounds", type=int, default=None)
    ap.add_argument("--epochs", type=int, default=None)
    ap.add_argument("--samples_per_client", type=int, default=None)
    ap.add_argument("--eval_every", type=int, default=5)
    ap.add_argument("--json_out", default="FLAGSHIP_CURVE.json")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    if args.platform != "tpu":
        # pin before any backend query
        jax.config.update("jax_platforms", args.platform)

    full = args.preset == "full"
    rounds = args.rounds or (100 if full else 8)
    epochs = args.epochs or (20 if full else 2)
    samples = args.samples_per_client or (5000 if full else 192)

    from fedml_tpu.algorithms.centralized import CentralizedTrainer
    from fedml_tpu.algorithms.fedavg import FedAvg, FedAvgConfig
    from fedml_tpu.models import resnet56
    from fedml_tpu.trainer.workload import ClassificationWorkload

    if args.data_dir:
        from fedml_tpu.data import load_data
        data = load_data("cifar10", data_dir=args.data_dir, batch_size=64,
                         client_num=10, partition_method="hetero",
                         partition_alpha=0.5, seed=args.seed)
        source = f"real:{args.data_dir}"
    else:
        from fedml_tpu.data.synthetic import (FLAGSHIP_TWIN_KWARGS,
                                              cifar_learnable_twin)
        # the multi-mode twin whose non-IID gap is REAL (the single-
        # prototype default saturates at fed == cent == 1.0 — a retention
        # ratio that probes nothing); difficulty shared with the CI
        # retention proxy via FLAGSHIP_TWIN_KWARGS so both measure the
        # same task
        data = cifar_learnable_twin(num_clients=10,
                                    samples_per_client=samples,
                                    partition_alpha=0.5, batch_size=64,
                                    seed=args.seed,
                                    **FLAGSHIP_TWIN_KWARGS)
        source = (f"learnable_twin(spc={samples}, lda=0.5, "
                  f"{FLAGSHIP_TWIN_KWARGS})")

    wl = ClassificationWorkload(resnet56(10), num_classes=10)
    cfg = FedAvgConfig(comm_round=rounds, client_num_per_round=10,
                       epochs=epochs, batch_size=64, lr=0.001, wd=0.001,
                       frequency_of_the_test=args.eval_every,
                       seed=args.seed)
    sink = PartialSink(args.json_out + ".partial",
                       {"rounds": rounds, "epochs": epochs,
                        "samples_per_client": samples, "source": source,
                        "preset": args.preset})
    algo = FedAvg(wl, data, cfg, sink=sink)
    t0 = time.time()
    algo.run()
    fed_wall = time.time() - t0
    fed_curve = [{"round": h["round"],
                  "train_acc": h.get("train_acc"),
                  "test_acc": h.get("test_acc")} for h in algo.history]
    fed_final = fed_curve[-1]["test_acc"]
    fed_final_split = "test"
    if fed_final is None:  # dataset without a per-client test split
        fed_final = fed_curve[-1]["train_acc"]
        fed_final_split = "train"

    # centralized twin at the same gradient-step budget (the reference's
    # 93.19 column): all clients' data pooled; each FedAvg round did
    # ``epochs`` local epochs per client in parallel, so the pooled twin
    # trains rounds * epochs epochs over the pooled set
    import jax as _jax
    import jax.numpy as jnp
    cent_epochs = rounds * epochs
    trainer = CentralizedTrainer(wl, lr=0.001, wd=0.001, epochs_per_call=1)
    pooled = {k: jnp.asarray(v) for k, v in data.train_global.items()}
    cent_eval_split = "test" if data.test_global is not None else "train"
    test_g = {k: jnp.asarray(v) for k, v in data.test_global.items()} \
        if data.test_global is not None else pooled
    params_c = wl.init(_jax.random.key(args.seed),
                       _jax.tree.map(lambda v: v[0], pooled))
    cent_curve = []
    t0 = time.time()
    rng_c = _jax.random.key(args.seed + 1)
    eval_stride = max(1, cent_epochs // 20)
    for e in range(cent_epochs):
        rng_c, r = _jax.random.split(rng_c)
        params_c, _ = trainer.local_train(params_c, pooled, r)
        if (e + 1) % eval_stride == 0 or e == cent_epochs - 1:
            st = trainer.metrics(params_c, test_g)
            cent_curve.append({"epoch": e + 1, "acc": st.get("acc"),
                               "split": cent_eval_split})
            with open(args.json_out + ".partial", "w") as f:
                json.dump({"partial": True, "config": sink.meta,
                           "federated_curve": sink.curve,
                           "centralized_curve_so_far": cent_curve}, f,
                          indent=1)
    cent_wall = time.time() - t0
    cent_final = cent_curve[-1]["acc"]

    report = {
        "config": {"model": "resnet56", "clients": 10, "lda_alpha": 0.5,
                   "batch_size": 64, "lr": 0.001, "wd": 0.001,
                   "epochs": epochs, "rounds": rounds, "source": source,
                   "platform": jax.default_backend(), "preset": args.preset},
        "published_reference": {"centralized": 93.19, "federated": 87.12,
                                "retention": 87.12 / 93.19,
                                "anchor": "benchmark/README.md:105"},
        "federated": {"curve": fed_curve, "final_acc": fed_final,
                      "final_acc_split": fed_final_split,
                      "wall_s": round(fed_wall, 1)},
        "centralized": {"final_acc": cent_final,
                        "eval_split": cent_eval_split,
                        "wall_s": round(cent_wall, 1),
                        "curve": cent_curve},
        "retention": (fed_final / cent_final
                      if fed_final is not None and cent_final else None),
    }
    try:
        from fedml_tpu.utils.reference_curves import load_reference_curve
        ref = load_reference_curve(os.path.join(REF_CURVES, "train_metrics"))
        report["published_trajectory_top1"] = [
            e["train_accTop1"] for e in ref]
    except Exception as e:  # torch unpickle may be unavailable
        report["published_trajectory_top1"] = f"unavailable: {e}"
    with open(args.json_out, "w") as f:
        json.dump(report, f, indent=1)
    try:  # clean completion supersedes the incremental checkpoint
        os.remove(args.json_out + ".partial")
    except OSError:
        pass
    print(json.dumps({k: report[k] for k in
                      ("config", "retention")}, default=str))
    print("federated final:", fed_final, "centralized final:", cent_final)


if __name__ == "__main__":
    main()
