"""342k-client scale proof (SURVEY hard part (f)).

Generates a stackoverflow_nwp-shaped synthetic corpus (vocab 10000 + 3
special + 1 oov, seq 20 — mirroring the reference layout in
fedml_api/data_preprocessing/stackoverflow_nwp/data_loader.py) at the
reference's FULL client count (342,477 train clients), staged directly
into the memmap format (data/stacking.py save/load_stacked_memmap) in
client chunks so host RAM never holds the corpus, then runs federated
rounds of the standard FedAvg engine with cohort sampling — the cohort
gather fancy-indexes the memmap, so per-round RAM is one cohort.

Writes SCALE_PROOF.json: corpus size on disk, staging wall time, peak
host RSS, per-round wall times.  Run on an idle machine:

    python scripts/scale_proof.py --clients 342477 --rounds 10 \
        --per_round 50 [--out_dir /tmp/so_scale] [--small_model]
"""

import argparse
import json
import math
import os
import resource
import sys
import time

import numpy as np
from numpy.lib.format import open_memmap

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SEQ = 20            # reference nwp sequence length
VOCAB = 10000 + 3 + 1  # vocab + pad/bos/eos + oov (RNNStackOverflow)
PAD, BOS, EOS = 0, 1, 2


def rss_gb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6


def generate(out_dir: str, n_clients: int, batch_size: int,
             max_samples: int, seed: int, chunk: int = 8192) -> dict:
    """Stream the corpus into memmapped .npy files, ``chunk`` clients at
    a time — peak RAM is O(chunk), not O(n_clients)."""
    os.makedirs(out_dir, exist_ok=True)
    steps = math.ceil(max_samples / batch_size)
    cap = steps * batch_size
    shapes = {
        "x": ((n_clients, steps, batch_size, SEQ), np.int32),
        "y": ((n_clients, steps, batch_size, SEQ), np.int32),
        "mask": ((n_clients, steps, batch_size), np.float32),
        "num_samples": ((n_clients,), np.float32),
    }
    mm = {k: open_memmap(os.path.join(out_dir, f"{k}.npy"), mode="w+",
                         dtype=dt, shape=sh)
          for k, (sh, dt) in shapes.items()}
    t0 = time.time()
    for lo in range(0, n_clients, chunk):
        hi = min(lo + chunk, n_clients)
        c = hi - lo
        rng = np.random.RandomState(seed + lo)
        # long-tail per-client example counts (the reference SO corpus is
        # heavily skewed); clip to the padded capacity
        counts = np.clip(rng.lognormal(2.5, 1.0, c).astype(np.int64),
                         1, cap)
        toks = rng.randint(3, VOCAB, size=(c, cap, SEQ)).astype(np.int32)
        toks[:, :, 0] = BOS
        sample_idx = np.arange(cap)[None, :]
        live = (sample_idx < counts[:, None])  # [c, cap]
        toks *= live[:, :, None]
        ys = np.concatenate(
            [toks[:, :, 1:], np.full((c, cap, 1), EOS, np.int32)], axis=2)
        ys *= live[:, :, None]
        mm["x"][lo:hi] = toks.reshape(c, steps, batch_size, SEQ)
        mm["y"][lo:hi] = ys.reshape(c, steps, batch_size, SEQ)
        mm["mask"][lo:hi] = live.astype(np.float32).reshape(
            c, steps, batch_size)
        mm["num_samples"][lo:hi] = counts.astype(np.float32)
    for v in mm.values():
        v.flush()
    staging_s = time.time() - t0
    disk_gb = sum(os.path.getsize(os.path.join(out_dir, f"{k}.npy"))
                  for k in shapes) / 1e9
    return {"staging_wall_s": round(staging_s, 1),
            "corpus_disk_gb": round(disk_gb, 2),
            "rss_after_staging_gb": round(rss_gb(), 2),
            "steps_per_client": steps, "batch_size": batch_size}


def train(out_dir: str, n_clients: int, rounds: int, per_round: int,
          batch_size: int, small_model: bool, platform: str) -> dict:
    import jax
    # pin the platform before any backend query
    if platform != "tpu":
        jax.config.update("jax_platforms", platform)
    from fedml_tpu.algorithms.fedavg import FedAvg, FedAvgConfig
    from fedml_tpu.data.stacking import FederatedData, load_stacked_memmap
    from fedml_tpu.models.rnn import RNNStackOverflow
    from fedml_tpu.trainer.workload import NWPWorkload

    stacked = load_stacked_memmap(out_dir)
    assert stacked["x"].shape[0] == n_clients
    data = FederatedData(client_num=n_clients, class_num=VOCAB,
                         train=stacked)
    model = (RNNStackOverflow(embedding_size=32, latent_size=64)
             if small_model else RNNStackOverflow())
    wl = NWPWorkload(model)
    algo = FedAvg(wl, data, FedAvgConfig(
        comm_round=rounds, client_num_per_round=per_round,
        batch_size=batch_size, epochs=1, lr=0.3,
        frequency_of_the_test=10**9))
    # throughput/staging proof: skip the metrics sweep entirely (round 0
    # always evals; a full-corpus LSTM eval would dominate the timing —
    # chunked eval exists for real runs, FedAvgConfig.eval_chunk_clients)
    algo.evaluate_global = lambda p: {}

    round_times = []
    t_last = time.time()
    orig_step = algo.cohort_step

    def timed_step(*a, **kw):
        nonlocal t_last
        out = orig_step(*a, **kw)
        jax.block_until_ready(out[0])
        now = time.time()
        round_times.append(now - t_last)
        t_last = now
        return out

    algo.cohort_step = timed_step
    t0 = time.time()
    algo.run()
    total = time.time() - t0
    rts = np.asarray(round_times[1:] or round_times)  # drop compile round
    return {"rounds": rounds, "clients_per_round": per_round,
            "model": "RNNStackOverflow" + ("(small)" if small_model else ""),
            "platform": jax.default_backend(),
            "total_wall_s": round(total, 1),
            "round_wall_s_median": round(float(np.median(rts)), 3),
            "round_wall_s_max": round(float(rts.max()), 3),
            "first_round_incl_compile_s": round(round_times[0], 1),
            "peak_rss_gb": round(rss_gb(), 2)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, default=342477)
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--per_round", type=int, default=50)
    ap.add_argument("--batch_size", type=int, default=16)
    ap.add_argument("--max_samples", type=int, default=48)
    ap.add_argument("--out_dir", default="/tmp/so_scale")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--small_model", action="store_true",
                    help="reduced embed/latent for CPU-bound hosts")
    ap.add_argument("--platform", default="cpu", choices=["cpu", "tpu"],
                    help="tpu runs on the chip; the default stays on the "
                         "CPU (this is a host-memory proof)")
    ap.add_argument("--skip_generate", action="store_true",
                    help="reuse an existing staged corpus in out_dir")
    ap.add_argument("--json_out", default="SCALE_PROOF.json")
    args = ap.parse_args()

    report = {"n_clients": args.clients,
              "reference_anchor":
                  "stackoverflow_nwp 342,477 train clients "
                  "(fedml_api/data_preprocessing/stackoverflow_nwp/)"}
    if not args.skip_generate:
        report["staging"] = generate(args.out_dir, args.clients,
                                     args.batch_size, args.max_samples,
                                     args.seed)
        print("staged:", json.dumps(report["staging"]))
    report["training"] = train(args.out_dir, args.clients, args.rounds,
                               args.per_round, args.batch_size,
                               args.small_model, args.platform)
    with open(args.json_out, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report))


if __name__ == "__main__":
    main()
