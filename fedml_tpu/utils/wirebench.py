"""Wire-path microbench: the measured proof behind the encode-once /
zero-copy round hot path (scripts/wire_bench.py is the CLI).

Three measurements, all CPU-container wall clock (``time.perf_counter``
on the host — no accelerator in the loop, so the timing trust contract's
device-sync concerns do not apply; every number is labeled
``backend: "cpu"``):

a. **broadcast serialize cost vs cohort size** — N per-silo full encodes
   (the seed path) vs ONE shared-payload encode + N small headers
   (``send_many``).  The encode-once cost is ~flat in N; the per-silo
   cost is linear.  gRPC's additional per-receiver memcpy of the shared
   block (unary RPCs need one contiguous buffer) is measured separately
   and honestly — it is a memcpy, not a re-serialization.
b. **encode/decode copies per leaf** — counted by the codec's own spy
   (`message.CODEC_COUNTS`), not estimated: one copy per contiguous leaf
   on encode, zero on decode (read-only views into the frame).
c. **end-to-end round time** — a real federation (server + N silo actors
   over the codec-roundtrip LocalHub) timed with the seed wire path
   (per-silo encode + stack-at-barrier) vs the new one (send_many +
   incremental staging), same model, same rounds, same results.
"""

from __future__ import annotations

import json
import time
from typing import Dict, List, Optional

import numpy as np

from fedml_tpu.comm.message import CODEC_COUNTS, Message, build_fanout

_NOTE = ("CPU-container wall-clock microbench (host perf_counter; no "
         "accelerator in the loop) — wire/serialization cost only, not a "
         "training-throughput claim")


def make_model_tree(target_mb: float = 10.0, seed: int = 0) -> dict:
    """A dense-layer-shaped pytree of ~``target_mb`` MB of float32."""
    rng = np.random.RandomState(seed)
    layers: Dict[str, dict] = {}
    per_layer = 512 * 512 * 4 + 512 * 4
    n_layers = max(1, int(target_mb * 1e6 / per_layer))
    for i in range(n_layers):
        layers[f"dense_{i}"] = {
            "kernel": rng.randn(512, 512).astype(np.float32),
            "bias": rng.randn(512).astype(np.float32)}
    return layers


def tree_mb(tree) -> float:
    import jax
    return sum(np.asarray(l).nbytes for l in jax.tree.leaves(tree)) / 1e6


def _median_time(fn, repeats: int = 3) -> float:
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def bench_broadcast_serialize(tree, cohort_sizes=(1, 2, 4, 8),
                              repeats: int = 3) -> dict:
    """Measurement (a): serialize cost of one broadcast, by cohort size."""
    round_tag = {Message.ARG_ROUND: 3}

    def per_silo(n):
        for silo in range(1, n + 1):
            msg = Message(1, 0, silo)
            msg.add(Message.ARG_MODEL_PARAMS, tree)
            msg.add(Message.ARG_CLIENT_INDEX, silo)
            msg.params.update(round_tag)
            msg.to_bytes()

    def encode_once(n):
        msgs = build_fanout(
            1, 0, range(1, n + 1), {Message.ARG_MODEL_PARAMS: tree,
                                    **round_tag},
            {s: {Message.ARG_CLIENT_INDEX: s} for s in range(1, n + 1)})
        for msg in msgs:
            msg.frame_parts()   # what the in-process/scatter wire pays

    def encode_once_contiguous(n):
        msgs = build_fanout(
            1, 0, range(1, n + 1), {Message.ARG_MODEL_PARAMS: tree,
                                    **round_tag},
            {s: {Message.ARG_CLIENT_INDEX: s} for s in range(1, n + 1)})
        for msg in msgs:
            msg.to_bytes()      # + one block memcpy per receiver (gRPC)

    out = {"cohort_sizes": list(cohort_sizes), "per_silo_encode_s": {},
           "encode_once_s": {}, "encode_once_grpc_assembly_s": {}}
    for n in cohort_sizes:
        out["per_silo_encode_s"][str(n)] = _median_time(
            lambda: per_silo(n), repeats)
        out["encode_once_s"][str(n)] = _median_time(
            lambda: encode_once(n), repeats)
        out["encode_once_grpc_assembly_s"][str(n)] = _median_time(
            lambda: encode_once_contiguous(n), repeats)
    n_max = str(max(cohort_sizes))
    out["speedup_at_n%s" % n_max] = (
        out["per_silo_encode_s"][n_max] / out["encode_once_s"][n_max])
    out["grpc_assembly_speedup_at_n%s" % n_max] = (
        out["per_silo_encode_s"][n_max]
        / out["encode_once_grpc_assembly_s"][n_max])
    return out


def measure_codec_copies(tree) -> dict:
    """Measurement (b): encode copies from the codec spy; decode
    zero-copy verified structurally — every decoded leaf must be a
    READ-ONLY view sharing memory with the frame buffer (a regression to
    buffer-slicing would flip the share fraction to 0, unlike a spy
    counter the decode path never increments)."""
    import jax
    n_leaves = len(jax.tree.leaves(tree))
    msg = Message(1, 0, 1).add(Message.ARG_MODEL_PARAMS, tree)
    before = CODEC_COUNTS["leaf_copies"]
    frame = msg.to_bytes()
    enc_copies = CODEC_COUNTS["leaf_copies"] - before
    decoded = Message.from_bytes(frame)
    frame_arr = np.frombuffer(frame, np.uint8)
    leaves = jax.tree.leaves(decoded.get(Message.ARG_MODEL_PARAMS))
    sharing = sum(1 for l in leaves
                  if l.size == 0 or np.shares_memory(l, frame_arr))
    readonly = sum(1 for l in leaves if not l.flags.writeable)
    return {"leaves": n_leaves,
            "encode_copies_per_leaf": enc_copies / n_leaves,
            "decode_leaves_sharing_frame_memory": sharing / len(leaves),
            "decode_leaves_readonly": readonly / len(leaves)}


def _delta_train_fn(delta: float):
    import jax

    def fn(params, client_idx, round_idx):
        return (jax.tree.map(lambda v: np.asarray(v) + np.float32(delta),
                             params), 10)
    return fn


def bench_round_e2e(tree, n_silos: int = 8, rounds: int = 3,
                    encode_once: bool = True, staging: bool = True,
                    chaos: bool = False, seed: int = 0) -> dict:
    """Measurement (c): wall time per round of a real federation over the
    codec-roundtrip hub (every frame encodes + decodes like a wire
    transport), seed path vs encode-once + incremental staging."""
    from fedml_tpu.algorithms.cross_silo import (FedAvgClientActor,
                                                 FedAvgServerActor, MsgType)
    from fedml_tpu.comm.local import LocalHub
    from fedml_tpu.robust.defense import make_defended_aggregate

    hub = LocalHub(codec_roundtrip=True)
    wrap = lambda t: t  # noqa: E731
    admission = None
    if chaos:
        from fedml_tpu.comm.chaos import ChaosPlan, ChaosTransport, LinkChaos
        from fedml_tpu.robust.admission import AdmissionPipeline
        plan = ChaosPlan(seed=seed,
                         default=LinkChaos(dup_prob=0.1, reorder_prob=0.1,
                                           corrupt_prob=0.1,
                                           max_delay_s=0.01),
                         immune_types=(MsgType.S2C_FINISH,))
        wrap = lambda t: ChaosTransport(t, plan)  # noqa: E731
        admission = AdmissionPipeline(tree, norm_min_history=10_000)
    server = FedAvgServerActor(
        wrap(hub.transport(0)), tree, client_num_in_total=n_silos,
        client_num_per_round=n_silos, num_rounds=rounds,
        admission=admission,
        aggregate_fn=make_defended_aggregate("mean"),
        encode_once=encode_once, incremental_staging=staging)
    server.register_handlers()
    silos = [FedAvgClientActor(i, wrap(hub.transport(i)),
                               _delta_train_fn(0.001))
             for i in range(1, n_silos + 1)]
    for s in silos:
        s.register_handlers()
    t0 = time.perf_counter()
    if chaos:
        # chaos releases reordered/delayed frames on wall-clock timers the
        # synchronous pump cannot wait for — drive each actor on its own
        # thread like a real deployment (the main.py chaos drive)
        import threading
        threads = [threading.Thread(target=s.run, daemon=True,
                                    name=f"wirebench-silo-{s.node_id}")
                   for s in silos]
        for th in threads:
            th.start()
        server.start()
        server.transport.run()  # blocks until the final round's FINISH
        for th in threads:
            th.join(timeout=10)
    else:
        server.start()
        hub.pump()
    elapsed = time.perf_counter() - t0
    assert server.round_idx == rounds, (
        f"federation did not complete ({server.round_idx}/{rounds})")
    return {"rounds": rounds, "n_silos": n_silos,
            "round_s": elapsed / rounds,
            "encode_once": encode_once, "incremental_staging": staging,
            "chaos": chaos,
            "final_param_checksum": float(sum(
                np.asarray(l, np.float64).sum()
                for l in __import__("jax").tree.leaves(server.params)))}


def run(out_path: Optional[str] = "BENCH_wire.json",
        smoke: bool = False) -> dict:
    """The full wire bench: measurements (a)-(c) + wire telemetry, written
    to ``out_path``."""
    from fedml_tpu.obs import telemetry

    # the serialize/copy measurements always run at the ~10MB model the
    # acceptance criterion names (a handful of encodes — cheap even in
    # smoke); only the e2e federations shrink for the smoke tier
    cohorts = (2, 8) if smoke else (1, 2, 4, 8)
    rounds = 2 if smoke else 4
    reg = telemetry.enable()
    tree = make_model_tree(10.0)
    details = {
        "backend": "cpu", "note": _NOTE, "smoke": smoke,
        "model_mb": round(tree_mb(tree), 2),
        "broadcast_serialize": bench_broadcast_serialize(tree, cohorts),
        "codec_copies": measure_codec_copies(tree),
    }
    e2e_tree = make_model_tree(1.0 if smoke else 4.0)
    details["round_e2e"] = {
        "model_mb": round(tree_mb(e2e_tree), 2),
        "seed_path": bench_round_e2e(e2e_tree, rounds=rounds,
                                     encode_once=False, staging=False),
        "encode_once_staged": bench_round_e2e(e2e_tree, rounds=rounds,
                                              encode_once=True,
                                              staging=True),
    }
    s, n = (details["round_e2e"]["seed_path"],
            details["round_e2e"]["encode_once_staged"])
    details["round_e2e"]["round_speedup"] = s["round_s"] / n["round_s"]
    details["round_e2e"]["results_identical"] = (
        s["final_param_checksum"] == n["final_param_checksum"])
    # the chaos arm (run_chaos.sh --smoke): encode-once frames through
    # dup/reorder/corrupt faults with the admission screen armed — proves
    # the shared-payload path survives a hostile wire, not just a clean one
    details["round_e2e"]["encode_once_under_chaos"] = bench_round_e2e(
        e2e_tree, rounds=rounds, encode_once=True, staging=True, chaos=True)
    snap = reg.snapshot()
    details["wire_telemetry"] = {
        k: v for bucket in ("counters", "gauges") for k, v in
        snap.get(bucket, {}).items() if k.startswith("fedml_wire")}
    enc = snap.get("histograms", {}).get("fedml_wire_encode_seconds")
    if enc:
        details["wire_telemetry"]["fedml_wire_encode_seconds"] = {
            "count": enc["count"], "mean_s": enc["mean"]}
    details["captured_at"] = time.time()
    if out_path:
        with open(out_path, "w") as f:
            json.dump(details, f, indent=2)
    return details
