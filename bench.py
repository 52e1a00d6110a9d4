"""Benchmark suite: honest rounds/sec + step-time + FLOPs + MFU.

Configs (BASELINE.md):
* femnist_cnn  — the cross-device headline (2-conv CNN, 10 clients/round,
  B=20, E=1, benchmark/README.md:54).  Comparable with BENCH_r01.
* resnet56_cifar10 — the flagship cross-silo config (10 clients, B=64,
  benchmark/README.md:105; the published config trains E=20 local epochs —
  we measure one epoch-round and report per-epoch numbers).
* cohort scaling — femnist_cnn at 10/32/64/128 clients per round: does the
  chip saturate as the cohort grows?
* multi-device — the same cohort step sharded over a mesh when >1 device
  is visible (skipped on single-chip hosts).

FLOPs come from XLA cost analysis of TWIN compiled programs
(``_honest_flops``): cost analysis counts a ``lax.scan`` body ONCE
regardless of trip count (verified empirically; the round-2 artifact
under-reported the scanned-dispatch MFU by exactly its trip count this
way), so per-round FLOPs are extrapolated from two rounds differing only
in local-step count, with recurrent cells unrolled in the cost twin.
MFU = achieved FLOP/s ÷ peak; peak comes from the detected device kind
(bf16 peak — the computation runs f32 unless BENCH_DTYPE=bfloat16, so
reported MFU is conservative), overridable via BENCH_PEAK_TFLOPS, and
raised to the measured bf16 matmul throughput when that exceeds the
table value (``bench_matmul_peak``).  Cost twins compile on the host CPU
backend (``_twin_device_ctx``): they are never executed, so they do not
need the accelerator.

stdout carries ONE JSON line (driver contract): the femnist_cnn rounds/s
with vs_baseline = measured sequential-torch-CPU round time ratio (the
reference's standalone simulator loop, fedavg_api.py:52-66 — an
architectural baseline, not a hardware-parity one; see BENCH_DETAILS.json
for the honest per-config breakdown, which is also written per-run).
With no TPU the bench FAILS (non-zero exit, nothing printed as a
result): no skipped line, no carried number, no CPU microbench in its
place.  An explicit ``BENCH_PLATFORM=cpu`` developer run is the one
exception and writes BENCH_DETAILS_cpu.json.  A kernel or timing-gate
failure propagates as an exception.

Env knobs: BENCH_ROUNDS (default 20), BENCH_MODE=quick|full,
BENCH_SCALING=0 to skip the curve, BENCH_PLATFORM to force a jax platform.
"""

import json
import os
import sys
import time

import numpy as np

# The bf16 peak table (matched as a substring of jax's device_kind —
# the round-2 cohort-scaling numbers exceeded the blanket v5e assumption
# at 128 clients, so the attached chip's kind must be recorded, not
# assumed) and the XLA cost-analysis probe now LIVE in the device
# observatory (fedml_tpu/obs/device.py) and are aliased here: the
# offline bench and the live per-round fedml_dev_*/mfu gauges read ONE
# table and ONE accounting, so they can never disagree — the same
# drift-proofing as the _max_mfu -> trend.max_mfu delegation below
# (tests/test_device_obs.py pins all three by identity).
from fedml_tpu.obs.device import PEAK_TFLOPS_BY_KIND as _PEAK_BY_KIND
from fedml_tpu.obs.device import compiled_flops as _compiled_flops
from fedml_tpu.obs.device import peak_tflops_for_device as _peak_for_device

# resolved in main() from the attached chip's device_kind (an unknown
# kind raises there; the explicit-CPU run has no peak and reports mfu 0)
PEAK_TFLOPS = None


def _compute_dtype():
    """BENCH_DTYPE=bfloat16 runs model compute in bf16 (mixed precision:
    f32 master params/opt, bf16 conv/matmul), the MXU-native mode."""
    name = os.environ.get("BENCH_DTYPE")
    if not name:
        return None
    import jax.numpy as jnp
    return jnp.dtype(name)


def _now():
    return time.time()


def _twin_device_ctx():
    """Context that places the FLOPs cost twins on the host CPU backend.

    Twins are only COMPILED (cost analysis), never executed, so they do
    not need the accelerator at all.  FLOP counts are a property of the
    HLO, not the backend, and the twin subtraction (F2-F1) cancels
    residual backend-specific overhead.
    BENCH_TWIN_DEVICE=default restores on-device twins; falls back to the
    default backend when no CPU backend is registered."""
    import contextlib
    import jax
    if os.environ.get("BENCH_TWIN_DEVICE", "cpu") != "cpu":
        return contextlib.nullcontext()
    try:
        return jax.default_device(jax.local_devices(backend="cpu")[0])
    except Exception:
        return contextlib.nullcontext()


def _honest_flops(model, classes, lr, epochs, batch_size, xs, ys,
                  clients_per_round, workload=None):
    """Per-round FLOPs that count every local step: (flops, total_steps).

    XLA cost analysis counts a `lax.scan`/while body ONCE regardless of
    trip count, and the local trainer runs its whole epochs*S-step run as
    one scan (local_sgd.py) — so the full program's own number misses the
    steps loop entirely.  Instead compile two TWIN rounds whose step scan
    is fully UNROLLED (scan_unroll=S, so every step is present in the HLO
    that cost analysis sees) at S=1 and S=2 batches, and extrapolate:

        F(round) = F1 + (epochs*S - 1) * (F2 - F1)

    F2 - F1 is exactly one step body (batch gather + fwd/bwd + optimizer);
    F1 carries the per-round overhead (aggregation, weighing) once.  A
    model whose SINGLE step hides another scan (the LSTM recurrence) needs
    _rnn_round_flops instead — unrolling 80 cells makes a twin that takes
    minutes to compile, so the recurrent cost is extrapolated over
    sequence length too.  Twins always use the plain vmap cohort step:
    mesh collectives add negligible FLOPs.
    """
    import jax
    from fedml_tpu.data.stacking import gather_cohort

    def f_for(nb):
        need = nb * batch_size
        xs_t, ys_t = [], []
        for x, y in zip(xs[:clients_per_round], ys[:clients_per_round]):
            reps = max(1, -(-need // len(x)))
            xs_t.append(np.concatenate([x] * reps)[:need])
            ys_t.append(np.concatenate([y] * reps)[:need])
        with _twin_device_ctx():
            step, params, stacked = _build_step(
                model, classes, lr, 1, batch_size, xs_t, ys_t,
                workload=workload, scan_unroll=nb)
            cohort = gather_cohort(stacked, np.arange(clients_per_round),
                                   pad_to=clients_per_round)
            return _compiled_flops(step, params, cohort, jax.random.key(0))

    f1, f2 = f_for(1), f_for(2)
    total_steps = epochs * max(1, -(-max(len(x) for x in xs) // batch_size))
    flops = f1 + (total_steps - 1) * max(f2 - f1, 0.0)
    return flops, total_steps


def _rnn_round_flops(dtype, clients_per_round, n_steps, seq_len=80,
                     batch=4, vocab=90, t_lo=4, t_hi=8):
    """Exact per-round FLOPs for the LSTM config: (flops, n_steps).

    The recurrence is a second scan INSIDE the training step, so the
    _honest_flops twins alone still count the T-step cell chain once.
    Unrolling all ``seq_len`` cells makes a twin that takes minutes to
    compile; instead, per-step cost is affine in T (embed + cell + logits
    + loss are all per-position; the optimizer update is T-independent),
    so three SMALL fully-unrolled twins pin both lines:

        A = (S=1, T=t_lo)   B = (S=2, T=t_lo)   C = (S=1, T=t_hi)
        per_token = (C - A) / (t_hi - t_lo)
        step(T)   = (B - A) + (T - t_lo) * per_token
        round     = (2A - B) + n_steps * step(seq_len)

    where 2A - B is the per-round overhead (aggregation) and B - A one
    t_lo-length step.  All scans (steps and cells) are unrolled in the
    twins so cost analysis sees every body."""
    import jax
    from fedml_tpu.data.stacking import gather_cohort
    from fedml_tpu.models import RNNOriginalFedAvg
    from fedml_tpu.trainer.workload import NWPWorkload

    def f_at(nb, t):
        rng = np.random.RandomState(0)
        xs = [rng.randint(1, vocab, (nb * batch, t)).astype(np.int32)
              for _ in range(clients_per_round)]
        ys = [np.concatenate([x[:, 1:], x[:, :1]], axis=1) for x in xs]
        wl = NWPWorkload(
            RNNOriginalFedAvg(vocab_size=vocab, dtype=dtype, unroll=t),
            compute_dtype=dtype)
        with _twin_device_ctx():
            step, params, stacked = _build_step(
                None, vocab, 0.8, 1, batch, xs, ys, workload=wl,
                scan_unroll=nb)
            cohort = gather_cohort(stacked, np.arange(clients_per_round),
                                   pad_to=clients_per_round)
            return _compiled_flops(step, params, cohort, jax.random.key(0))

    a, b, c = f_at(1, t_lo), f_at(2, t_lo), f_at(1, t_hi)
    per_token = max(c - a, 0.0) / (t_hi - t_lo)
    step_t = max(b - a, 0.0) + (seq_len - t_lo) * per_token
    return max(2 * a - b, 0.0) + n_steps * step_t, n_steps


def _synth_clients(n_clients, samples, shape, classes, seed=0):
    rng = np.random.RandomState(seed)
    xs = [rng.randn(samples, *shape).astype(np.float32)
          for _ in range(n_clients)]
    ys = [rng.randint(0, classes, samples).astype(np.int32)
          for _ in range(n_clients)]
    return xs, ys


def _build_step(model, classes, lr, epochs, batch_size, xs, ys, mesh=None,
                workload=None, scan_unroll=1, client_axis="vmap"):
    import jax
    import jax.numpy as jnp
    from fedml_tpu.data.stacking import stack_client_data, gather_cohort
    from fedml_tpu.parallel.cohort import make_cohort_step
    from fedml_tpu.trainer.local_sgd import make_local_trainer
    from fedml_tpu.trainer.workload import (ClassificationWorkload,
                                            make_client_optimizer)

    stacked = stack_client_data(xs, ys, batch_size)
    if workload is None:
        workload = ClassificationWorkload(model, num_classes=classes,
                                          compute_dtype=_compute_dtype())
    local = make_local_trainer(workload,
                               make_client_optimizer("sgd", lr), epochs,
                               scan_unroll=scan_unroll)
    step = make_cohort_step(local, mesh=mesh, client_axis=client_axis)
    params = workload.init(jax.random.key(0), jax.tree.map(
        lambda v: jnp.asarray(v[0, 0]),
        {k: stacked[k] for k in ("x", "y", "mask")}))
    return step, params, stacked


_SPREAD_MIN_ROUND_S = 0.02  # per-round blocking is noise below this


def _round_spread(run_round, params, rounds):
    """Per-round BLOCKED wall times -> {median, p10, p90, max} seconds.

    The amortized loop hides run-to-run jitter (the round-2 artifact showed
    an unexplained 2x step-time spread on resnet56); blocking per round costs one host sync each, negligible once a round is
    >= _SPREAD_MIN_ROUND_S, and pins whether an outlier mean comes from a
    fat tail or a level shift."""
    import jax
    times = []
    for i in range(rounds):
        t0 = _now()
        params, _ = run_round(params, i)
        jax.block_until_ready(params)
        times.append(_now() - t0)
    ts = np.asarray(times)
    return {"mean": float(ts.mean()), "median": float(np.median(ts)),
            "p10": float(np.percentile(ts, 10)),
            "p90": float(np.percentile(ts, 90)),
            "max": float(ts.max()), "n": len(ts)}


def _measure(step, params, stacked, clients_per_round, total_clients,
             rounds, spread=False):
    """Compile once, then time `rounds` rounds; returns round_s (amortized)
    or (round_s, spread_stats) when ``spread``.  (FLOPs come separately
    from _honest_flops — the full program's cost analysis counts its scan
    bodies once and is NOT a per-round number.)"""
    import jax
    from fedml_tpu.core.sampling import sample_clients
    from fedml_tpu.data.stacking import gather_cohort

    def round_args(i):
        ids = sample_clients(i, total_clients, clients_per_round)
        return (gather_cohort(stacked, ids, pad_to=clients_per_round),
                jax.random.key(i))

    cohort, rng = round_args(0)
    params, _ = step(params, cohort, rng)          # warmup/compile
    jax.block_until_ready(params)
    probe_s = 0.0
    if spread:  # one POST-compile round estimates the per-round cost
        cohort, rng = round_args(0)
        t0 = _now()
        params, _ = step(params, cohort, rng)
        jax.block_until_ready(params)
        probe_s = _now() - t0
    if spread and probe_s >= _SPREAD_MIN_ROUND_S:
        # slow config: ONE blocked loop yields both the amortized mean and
        # the per-round spread (blocking costs a host sync per round —
        # negligible at this scale, and no duplicated measurement)
        def run_round(p, i):
            cohort, rng = round_args(1 + i)
            return step(p, cohort, rng)
        stats = _round_spread(run_round, params, max(rounds, 5))
        return stats["mean"], stats
    t0 = _now()
    for i in range(1, rounds + 1):
        cohort, rng = round_args(i)
        params, _ = step(params, cohort, rng)
    jax.block_until_ready(params)
    round_s = (_now() - t0) / rounds
    return (round_s, None) if spread else round_s


# the FEMNIST headline config, shared by the dispatch and scanned benches so
# the two rounds/s numbers always measure the same workload
# (benchmark/README.md:54: 2-conv CNN, B=20, E=1, sgd lr=0.1, 62 classes)
FEMNIST_CLASSES = 62
FEMNIST_LR = 0.1
FEMNIST_EPOCHS = 1
FEMNIST_BATCH = 20


def _femnist_data(clients_per_round):
    samples = int(os.environ.get("BENCH_FEMNIST_SAMPLES", "200"))
    return _synth_clients(max(128, clients_per_round), samples,
                          (28, 28, 1), FEMNIST_CLASSES)


def bench_femnist_cnn(rounds, clients_per_round=10, mesh=None,
                      on_device=True, flops_base=None):
    """benchmark/README.md:54 config on synthetic FEMNIST-shaped data.
    Returns (round_s, flops_per_round, steps_per_round).

    ``on_device`` (single-chip only): HBM-resident dataset + in-jit cohort
    gather (make_device_round) — the production fast path; False measures
    the host-gather + re-upload path for comparison.  ``flops_base`` is an
    optional (flops, steps, base_clients) from a previous call — per-round
    FLOPs are linear in cohort size (per-client training and aggregation
    both scale with clients), so the scaling curve reuses one twin
    measurement instead of recompiling twins per cohort size."""
    from fedml_tpu.models import CNNOriginalFedAvg
    xs, ys = _femnist_data(clients_per_round)
    model = CNNOriginalFedAvg(only_digits=False)
    if flops_base is None:
        flops, steps = _honest_flops(
            model, FEMNIST_CLASSES, FEMNIST_LR, FEMNIST_EPOCHS,
            FEMNIST_BATCH, xs, ys, clients_per_round)
    else:
        f0, steps, base_clients = flops_base
        flops = f0 * clients_per_round / base_clients
    if on_device and mesh is None:
        round_s = _measure_device(
            model, FEMNIST_CLASSES, FEMNIST_LR, FEMNIST_EPOCHS,
            FEMNIST_BATCH, xs, ys, clients_per_round, rounds)
        return round_s, flops, steps
    step, params, stacked = _build_step(
        model, FEMNIST_CLASSES, lr=FEMNIST_LR, epochs=FEMNIST_EPOCHS,
        batch_size=FEMNIST_BATCH, xs=xs, ys=ys, mesh=mesh)
    round_s = _measure(step, params, stacked, clients_per_round, len(xs),
                       rounds)
    return round_s, flops, steps


def _device_setup(model, classes, lr, epochs, batch_size, xs, ys):
    """Shared HBM-resident staging for the device-round / scanned benches:
    (local_train, params, stacked_dev)."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.data.stacking import stack_client_data
    from fedml_tpu.trainer.local_sgd import make_local_trainer
    from fedml_tpu.trainer.workload import (ClassificationWorkload,
                                            make_client_optimizer)

    stacked = stack_client_data(xs, ys, batch_size)
    workload = ClassificationWorkload(model, num_classes=classes,
                                      compute_dtype=_compute_dtype())
    local = make_local_trainer(workload,
                               make_client_optimizer("sgd", lr), epochs)
    params = workload.init(jax.random.key(0), jax.tree.map(
        lambda v: jnp.asarray(v[0, 0]),
        {k: stacked[k] for k in ("x", "y", "mask")}))
    stacked_dev = {k: jnp.asarray(v) for k, v in stacked.items()}
    return local, params, stacked_dev


def _measure_device(model, classes, lr, epochs, batch_size, xs, ys,
                    clients_per_round, rounds):
    import jax
    import jax.numpy as jnp
    from fedml_tpu.core.sampling import sample_clients
    from fedml_tpu.parallel.cohort import make_device_round

    local, params, stacked_dev = _device_setup(
        model, classes, lr, epochs, batch_size, xs, ys)
    round_fn = make_device_round(local, clients_per_round)
    live = jnp.ones(clients_per_round, jnp.float32)

    def ids_for(i):
        ids = sample_clients(i, len(xs), clients_per_round)
        return jnp.asarray(ids.astype(np.int32))

    args0 = (params, stacked_dev, ids_for(0), live, jax.random.key(0))
    params, _ = round_fn(*args0)
    jax.block_until_ready(params)
    t0 = _now()
    for i in range(1, rounds + 1):
        params, _ = round_fn(params, stacked_dev, ids_for(i), live,
                             jax.random.key(i))
    jax.block_until_ready(params)
    return (_now() - t0) / rounds


def bench_femnist_cnn_scanned(rounds, clients_per_round=10, k=20):
    """The dispatch-amortised fast path: lax.scan over K rounds per device
    dispatch (make_scanned_rounds).  At sub-ms round times the host loop is
    latency-bound — this measures the true on-chip round rate.  Returns
    round_s only; per-round FLOPs are the dispatch config's (identical
    hyperparameters by construction — shared FEMNIST_* constants)."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.core.sampling import sample_clients
    from fedml_tpu.models import CNNOriginalFedAvg
    from fedml_tpu.parallel.cohort import make_scanned_rounds

    xs, ys = _femnist_data(clients_per_round)
    # identical workload/hparams to the dispatch headline (shared FEMNIST_*
    # constants) so the two numbers compare only the dispatch model
    local, params, stacked_dev = _device_setup(
        CNNOriginalFedAvg(only_digits=False), FEMNIST_CLASSES, FEMNIST_LR,
        FEMNIST_EPOCHS, FEMNIST_BATCH, xs, ys)
    rounds_fn = make_scanned_rounds(local, clients_per_round)

    def ids_for(chunk):
        ids = np.stack([sample_clients(chunk * k + i, len(xs),
                                       clients_per_round)
                        for i in range(k)]).astype(np.int32)
        return jnp.asarray(ids), jnp.ones((k, clients_per_round), jnp.float32)

    ids, live = ids_for(0)
    args0 = (params, stacked_dev, ids, live, jax.random.key(0))
    params, _ = rounds_fn(*args0)     # warmup/compile
    jax.block_until_ready(params)
    n_chunks = max(1, rounds // k)
    t0 = _now()
    for c in range(1, n_chunks + 1):
        ids, live = ids_for(c)
        params, _ = rounds_fn(params, stacked_dev, ids, live,
                              jax.random.key(c))
    jax.block_until_ready(params)
    return (_now() - t0) / (n_chunks * k)


def bench_resnet56_cifar10(rounds, mesh=None, samples=512, epochs=1,
                           client_axis=None):
    """Flagship cross-silo config (benchmark/README.md:105): 10 clients,
    B=64; ``epochs`` local epochs measured (published runs use E=20 of
    5000 samples — pass epochs=20 for the exact config).  Returns
    (round_s, flops, steps).

    ``client_axis`` ("vmap" | "scan", env BENCH_R56_CLIENT_AXIS):
    concurrent clients lower per-client conv kernels to GROUPED convs —
    at 16/32/64 channels each group fills a sliver of the 128-wide MXU
    tile; "scan" trains clients sequentially with dense convs.
    """
    from fedml_tpu.models import resnet56
    client_axis = client_axis or os.environ.get(
        "BENCH_R56_CLIENT_AXIS", "vmap")
    xs, ys = _synth_clients(10, samples, (32, 32, 3), 10)
    flops, steps = _honest_flops(resnet56(10), 10, 0.001, epochs, 64,
                                 xs, ys, 10)
    step, params, stacked = _build_step(
        resnet56(10), 10, lr=0.001, epochs=epochs, batch_size=64, xs=xs,
        ys=ys, mesh=mesh, client_axis=client_axis)
    round_s, spread = _measure(step, params, stacked, 10, 10, rounds,
                               spread=True)
    return round_s, flops, steps, spread


def bench_shakespeare_rnn(rounds, clients_per_round=10):
    """The NLP family config (benchmark/README.md shakespeare row): 2-layer
    LSTM(256) char LM, B=4, seq 80 — recurrence compiles to lax.scan.
    Returns (round_s, flops, steps).

    The FLOPs come from _rnn_round_flops (cell scan extrapolated over
    sequence length): without it, cost analysis counts the 80-step cell
    scan once and the honest per-step cost is off by ~T (the round-2
    artifact's 0.14% "MFU" was this accounting artifact, not a slow
    kernel)."""
    from fedml_tpu.experiments.models import create_workload

    rng = np.random.RandomState(0)
    samples = int(os.environ.get("BENCH_RNN_SAMPLES", "32"))
    xs = [rng.randint(1, 90, (samples, 80)).astype(np.int32)
          for _ in range(max(32, clients_per_round))]
    ys = [np.concatenate([x[:, 1:], x[:, :1]], axis=1) for x in xs]
    # create_workload owns the model-dtype/workload-dtype coupling
    wl = create_workload("rnn", "shakespeare", 90, (80,),
                         compute_dtype=os.environ.get("BENCH_DTYPE", ""))
    n_steps = max(1, -(-samples // 4))
    flops, steps = _rnn_round_flops(_compute_dtype(), clients_per_round,
                                    n_steps)
    step, params, stacked = _build_step(
        None, 90, lr=0.8, epochs=1, batch_size=4, xs=xs, ys=ys, workload=wl)
    round_s = _measure(step, params, stacked, clients_per_round, len(xs),
                       rounds)
    return round_s, flops, steps


def bench_longcontext_transformer(steps=10, seq_len=2048, batch=2,
                                  block=256, use_flash=False,
                                  moe_experts=0):
    """Long-context single-chip training step (the capability the
    reference's LSTM zoo caps at 80 tokens): TransformerLM grad step at
    ``seq_len`` with flash-style kv blocking (or the pallas flash kernel
    when ``use_flash``).  ``moe_experts`` swaps the FFN for the Switch
    MoE layer (models/moe.py) — the routed-capacity timing point.
    Returns (step_s, tokens_per_s)."""
    import jax
    import jax.numpy as jnp
    import optax
    from fedml_tpu.models import TransformerLM

    model = TransformerLM(vocab_size=256, d_model=256, n_heads=8,
                          n_layers=2, d_ff=1024, max_len=seq_len,
                          block_size=None if use_flash else block,
                          use_flash=use_flash,
                          moe_experts=moe_experts,
                          dtype=_compute_dtype())
    toks = jnp.asarray(np.random.RandomState(0).randint(
        0, 256, (batch, seq_len)), jnp.int32)
    params = model.init(jax.random.key(0), toks)["params"]

    def loss_fn(p, x):
        logits = model.apply({"params": p}, x).astype(jnp.float32)
        y = jnp.concatenate([x[:, 1:], x[:, :1]], axis=1)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()

    grad = jax.jit(jax.grad(loss_fn))
    g = grad(params, toks)
    jax.block_until_ready(g)
    t0 = _now()
    for _ in range(steps):
        g = grad(params, toks)
    jax.block_until_ready(g)
    step_s = (_now() - t0) / steps
    return step_s, batch * seq_len / step_s


def bench_robust_backends(rounds, clients_per_round=10):
    """Defended FedAvg round (clip + weak-DP), XLA transform hook vs the
    fused Pallas aggregation kernel (core/pallas_agg.py) — same model and
    hparams as the femnist headline so the delta is the defense path."""
    import jax
    from fedml_tpu.core.pallas_agg import (make_fused_robust_aggregate,
                                           pallas_interpret)
    from fedml_tpu.core.robust import add_gaussian_noise, clip_update
    from fedml_tpu.models import CNNOriginalFedAvg
    from fedml_tpu.parallel.cohort import make_cohort_step
    from fedml_tpu.trainer.local_sgd import make_local_trainer
    from fedml_tpu.trainer.workload import (ClassificationWorkload,
                                            make_client_optimizer)

    xs, ys = _femnist_data(clients_per_round)
    workload = ClassificationWorkload(CNNOriginalFedAvg(only_digits=False),
                                      num_classes=FEMNIST_CLASSES,
                                      compute_dtype=_compute_dtype())
    local = make_local_trainer(
        workload, make_client_optimizer("sgd", FEMNIST_LR), FEMNIST_EPOCHS)

    def transform(client_params, global_params, rng):
        p = clip_update(client_params, global_params, 5.0)
        return add_gaussian_noise(p, rng, 0.025)

    fused = make_fused_robust_aggregate(
        norm_bound=5.0, noise_std=0.025,
        interpret=pallas_interpret("robust_aggregate"))
    from fedml_tpu.data.stacking import stack_client_data
    import jax.numpy as jnp
    stacked = stack_client_data(xs, ys, FEMNIST_BATCH)
    params = workload.init(jax.random.key(0), jax.tree.map(
        lambda v: jnp.asarray(v[0, 0]),
        {k: stacked[k] for k in ("x", "y", "mask")}))
    out = {}
    for name, step in (
            ("xla", make_cohort_step(local, transform_update=transform)),
            ("pallas", make_cohort_step(local, aggregate=fused))):
        out[name] = _measure(step, params, stacked, clients_per_round,
                             len(xs), rounds)
    return out


def bench_matmul_peak(n=4096, iters=24):
    """Empirical MXU throughput floor: achieved TF/s on a chained dense
    [n,n]x[n,n] matmul, bf16 and f32.

    Round-4 motivation: with the honest per-trip FLOPs accounting in
    place, the femnist configs still read MFU > 1.0 against the
    device_kind table peak ("TPU v5 lite" -> 197 TF/s bf16), and a hand
    count of the CNN's conv/fc MACs CONFIRMS the per-round FLOPs number
    — so the peak assumption, not the accounting, is what's broken.  A
    plain matmul can't exceed the chip's real peak,
    so its achieved rate is a hard lower bound; when it beats the table
    value, MFU is quoted against it instead."""
    import jax
    import jax.numpy as jnp

    out = {}
    rng = np.random.RandomState(0)
    # ~N(0,1) columns keep the chained product's scale stable (no
    # overflow-to-inf values in the timing loop)
    b0 = (rng.randn(n, n) / np.sqrt(n)).astype(np.float32)
    for name, dt in (("bf16", jnp.bfloat16), ("f32", jnp.float32)):
        a = jnp.asarray(rng.randn(n, n).astype(np.float32), dtype=dt)
        b = jnp.asarray(b0, dtype=dt)
        f = jax.jit(lambda x, y: x @ y)
        r = f(a, b)
        jax.block_until_ready(r)
        t0 = _now()
        for _ in range(iters):
            r = f(r, b)
        jax.block_until_ready(r)
        out[name] = 2.0 * n ** 3 * iters / (_now() - t0) / 1e12
    return out


_LINEARITY_BAND = (1.7, 2.3)
# no announced TPU exceeds 918 TF/s bf16 dense (v6e); a measured "peak"
# beyond 2x that is timer failure, not silicon
_PEAK_SANITY_CAP_TFLOPS = 1836.0


def bench_timing_sanity(n=4096, iters=16):
    """Host-timing trust gate: evidence that timed loops measure real device
    execution.  An earlier round read femnist MFU 1.14/3.08 — physically
    impossible — which would follow from a ``block_until_ready`` that
    does not synchronize; every headline number hangs on that primitive,
    so prove it before measuring anything.

    Three checks on a chained [n,n] matmul (bf16 on accelerators; the
    multiplier's spectral radius is ~1/2, so the chain neither overflows
    nor folds to a constant):

    * sync:      t_block(R) vs t_sync(R), where t_sync ends at a host
                 ``float()`` readback of a scalar REDUCED FROM THE RESULT —
                 a synchronization that cannot be faked (the scalar depends
                 on every chained matmul).  A broken block_until_ready
                 shows t_block << t_sync.
    * linearity: t_sync(2R)/t_sync(R) ~ 2 within _LINEARITY_BAND — a timer
                 blind to device work reads near-constant instead.  The
                 iteration count auto-grows until the timed work dwarfs
                 the measured constant readback/dispatch overhead, so a
                 REAL backend with a slow control path cannot fail the
                 band spuriously.
    * checksum:  the readback scalar must be finite, and its existence
                 means XLA could not dead-code the timed work.

    All three must hold for ``trusted``; main() fails the run when they
    don't.  Returns the evidence dict either way.
    """
    import jax
    import jax.numpy as jnp

    dt = jnp.float32 if jax.default_backend() == "cpu" else jnp.bfloat16
    rng = np.random.RandomState(0)
    b = jnp.asarray((rng.randn(n, n) / (2.0 * np.sqrt(n))).astype(
        np.float32), dt)
    a = jnp.asarray(rng.randn(n, n).astype(np.float32), dt)
    f = jax.jit(lambda x, y: x @ y)
    summ = jax.jit(lambda x: jnp.sum(x.astype(jnp.float32)))

    def chain(k):
        x = a
        for _ in range(k):
            x = f(x, b)
        return x

    float(summ(chain(2)))  # compile both programs outside the timings

    def t_block(k):
        t0 = _now()
        jax.block_until_ready(chain(k))
        return _now() - t0

    def t_sync(k):
        t0 = _now()
        s = float(summ(chain(k)))
        return _now() - t0, s

    # constant per-call overhead estimate (dispatch + readback RTT): one
    # near-zero-work readback.  The linearity test compares t(2R)/t(R); with constant
    # overhead r it reads (2W+r)/(W+r), so W must dwarf r or a REAL
    # backend fails the band — grow iters until the timed work does.
    t0 = _now()
    float(summ(a))
    rtt = _now() - t0
    target = max(0.05, 20.0 * rtt)

    def measured(k, reps=3):
        # min-of-N before ANY decision: load spikes are strictly
        # additive noise, so min estimates the true time; a single
        # inflated sample must neither end growth early nor skew the
        # band ratio (observed on a 1-core host: min-of-2 left the ratio
        # brushing the band edges under background load)
        t1, c = t_sync(k)
        for _ in range(reps - 1):
            t1 = min(t1, t_sync(k)[0])
        return t1, c

    ts1, checksum = measured(iters, reps=2)
    while ts1 < target and iters < 1024:
        # jump straight to the projected count (step-doubling would
        # re-time the chain log-many times, each paying the RTT)
        est = max(ts1 - rtt, 1e-6) / iters
        need = max((target - rtt) / est, 2.0 * iters)
        iters = int(min(1024, 2.0 ** np.ceil(np.log2(need))))
        ts1, checksum = measured(iters, reps=2)
    ts1 = min(ts1, measured(iters, reps=1)[0])  # 3rd sample at final size
    growth_capped = ts1 < target
    tb = min(t_block(iters), t_block(iters))
    ts2, _ = measured(2 * iters, reps=3)
    ratio = ts2 / max(ts1, 1e-9)
    sync_ratio = ts1 / max(tb, 1e-9)
    failures = []
    if not (_LINEARITY_BAND[0] <= ratio <= _LINEARITY_BAND[1]):
        msg = (f"linearity: t_sync(2R)/t_sync(R)={ratio:.2f} outside "
               f"{list(_LINEARITY_BAND)} — the timer is not measuring "
               "the device work")
        if growth_capped:
            msg += (f" [iters capped at {iters} before timed work "
                    f"dwarfed the {rtt * 1e3:.0f} ms per-call overhead; "
                    "this failure may be overhead-domination, not a "
                    "broken timer]")
        failures.append(msg)
    if sync_ratio > 1.5:
        failures.append(
            f"sync: readback-synced loop is {sync_ratio:.2f}x the "
            "block_until_ready loop — block_until_ready does not "
            "synchronize on this backend")
    if not np.isfinite(checksum):
        failures.append(f"checksum not finite ({checksum})")
    return {"n": n, "iters_R": iters, "t_block_R_s": tb, "t_sync_R_s": ts1,
            "t_sync_2R_s": ts2, "linearity_ratio": ratio,
            "sync_ratio": sync_ratio, "checksum": checksum,
            "readback_rtt_s": rtt, "growth_capped": growth_capped,
            "band": list(_LINEARITY_BAND), "trusted": not failures,
            "failures": failures,
            "tflops_readback_verified": 2.0 * n ** 3 * iters / ts1 / 1e12}


def run_timing_gate(on_cpu: bool = False):
    """THE timing-trust gate: sanity probe with one retry (a transient
    host-load spike must not fail a run), then the matmul-peak
    plausibility cap.  Returns ``(sanity, mm, failures)``; ``failures``
    empty means the run may proceed, ``mm`` is None on explicit-CPU
    runs."""
    kw = {"n": 512, "iters": 4} if on_cpu else {}
    sanity = bench_timing_sanity(**kw)
    if not sanity["trusted"]:
        sanity = bench_timing_sanity(**kw)
        sanity["retried"] = True
    failures = list(sanity["failures"])
    mm = None
    if not on_cpu:
        mm = bench_matmul_peak()
        if mm["bf16"] > _PEAK_SANITY_CAP_TFLOPS:
            failures.append(
                f"measured bf16 matmul {mm['bf16']:.0f} TF/s exceeds any "
                f"announced TPU peak (cap {_PEAK_SANITY_CAP_TFLOPS:.0f}) — "
                "timer failure, not silicon")
    return sanity, mm, failures


def bench_agg_kernels_flagship(iters=30, clients=10, workload=None,
                               sample_shape=(8, 32, 32, 3)):
    """Do the Pallas kernels earn their keep at flagship sizes?  (The one
    femnist-size reading was 1.05x — decide with flagship-size bf16
    measurements, then justify or demote.)

    Aggregation-only microbenches at resnet56 parameter size (~0.85M
    params x 10 clients, the published CIFAR10 cross-silo shape):

    * robust aggregate (clip + weak-DP + weighted mean): fused Pallas
      kernel (core/pallas_agg.py) vs the XLA compose
      ``tree_weighted_mean(vmap(clip+noise))`` — f32 and bf16 stacked
      updates (bf16 halves the HBM traffic the kernel exists to save).
    * SecAgg quantize+mask: ``SecureCohortAggregator.mask_update`` with
      backend="pallas" (secure/pallas_mask.py) vs "xla" — f32, the
      quantization domain.

    Returns {row: {xla_ms, pallas_ms, speedup}}.  TPU-only in main():
    the interpreter path is not a perf number — but ``workload``/
    ``sample_shape`` are injectable so the wiring (tree shapes, fused
    kernel API, SecureCohortAggregator surface) is unit-testable on CPU
    at toy size (tests/test_bench_unit.py).
    """
    import jax
    import jax.numpy as jnp
    from fedml_tpu.core.pallas_agg import (make_fused_robust_aggregate,
                                           pallas_interpret)
    from fedml_tpu.core.pytree import tree_weighted_mean
    from fedml_tpu.core.robust import add_gaussian_noise, clip_update
    from fedml_tpu.models import resnet56
    from fedml_tpu.secure.secagg import SecureCohortAggregator
    from fedml_tpu.trainer.workload import ClassificationWorkload

    wl = workload or ClassificationWorkload(resnet56(10), num_classes=10)
    batch = {"x": jnp.zeros(sample_shape, jnp.float32),
             "y": jnp.zeros((sample_shape[0],), jnp.int32),
             "mask": jnp.ones((sample_shape[0],), jnp.float32)}
    params = wl.init(jax.random.key(0), batch)
    weights = jnp.ones((clients,), jnp.float32)
    fused = make_fused_robust_aggregate(
        5.0, 0.025, interpret=pallas_interpret("robust_aggregate"))

    def stack(dt):
        # distinct per-client offsets so nothing collapses to a broadcast
        return jax.tree.map(
            lambda p: (p[None].astype(dt)
                       + (jnp.arange(1, clients + 1, dtype=jnp.float32)
                          * 1e-3).astype(dt).reshape(
                              (clients,) + (1,) * p.ndim)),
            params)

    def xla_agg(stacked, g, rng):
        def per_client(c, k):
            return add_gaussian_noise(clip_update(c, g, 5.0), k, 0.025)
        return tree_weighted_mean(
            jax.vmap(per_client)(stacked, jax.random.split(rng, clients)),
            weights)

    def timed_ms(fn, *args):
        out = fn(*args)
        jax.block_until_ready(out)
        t0 = _now()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        return 1e3 * (_now() - t0) / iters

    rows = {}
    rng = jax.random.key(0)
    for name, dt in (("robust_agg_r56_f32", jnp.float32),
                     ("robust_agg_r56_bf16", jnp.bfloat16)):
        stacked = stack(dt)
        g = jax.tree.map(lambda p: p.astype(dt), params)
        xla_ms = timed_ms(jax.jit(xla_agg), stacked, g, rng)
        pal_ms = timed_ms(
            jax.jit(lambda s, gg, r: fused(s, weights, gg, r)),
            stacked, g, rng)
        rows[name] = {"xla_ms": xla_ms, "pallas_ms": pal_ms,
                      "speedup": xla_ms / pal_ms}

    stacked32 = stack(jnp.float32)
    one_update = jax.tree.map(lambda v: v[0], stacked32)
    for name, backend in (("secagg_mask_r56_f32", "pallas"),):
        agg_x = SecureCohortAggregator(clients, backend="xla")
        agg_p = SecureCohortAggregator(clients, backend=backend)
        xla_ms = timed_ms(
            jax.jit(lambda u, k: agg_x.mask_update(u, 1.0, 0, k)),
            one_update, rng)
        pal_ms = timed_ms(
            jax.jit(lambda u, k: agg_p.mask_update(u, 1.0, 0, k)),
            one_update, rng)
        rows[name] = {"xla_ms": xla_ms, "pallas_ms": pal_ms,
                      "speedup": xla_ms / pal_ms}
    return rows


def bench_twin_backend_delta(cpu_flops, clients_per_round=10):
    """Cost-analysis FLOPs are a
    property of the post-optimization HLO, which is backend-specific —
    compile the femnist twins on the DEVICE backend too and record the
    relative per-round delta vs the CPU-twin number the headline already
    uses (``cpu_flops``, from bench_femnist_cnn's identical
    model/constants/data), so a divergence is detectable instead of
    silent.  Returns {cpu_flops, device_flops, rel_delta}."""
    from fedml_tpu.models import CNNOriginalFedAvg

    xs, ys = _femnist_data(clients_per_round)
    model = CNNOriginalFedAvg(only_digits=False)
    old = os.environ.get("BENCH_TWIN_DEVICE")
    os.environ["BENCH_TWIN_DEVICE"] = "default"
    try:
        dev_f, _ = _honest_flops(
            model, FEMNIST_CLASSES, FEMNIST_LR, FEMNIST_EPOCHS,
            FEMNIST_BATCH, xs, ys, clients_per_round)
    finally:
        if old is None:
            os.environ.pop("BENCH_TWIN_DEVICE", None)
        else:
            os.environ["BENCH_TWIN_DEVICE"] = old
    return {"cpu_flops": cpu_flops, "device_flops": dev_f,
            "rel_delta": abs(dev_f - cpu_flops) / max(cpu_flops, 1.0)}


def bench_torch_baseline(clients_per_round=10, batch_size=20):
    """The reference's standalone simulator loop (sequential clients,
    fedavg_api.py:52-66) in torch on this host's CPU — an architectural
    comparison point, not a hardware-parity baseline."""
    try:
        import torch
        import torch.nn as nn
    except Exception:
        return None

    class CNN(nn.Module):
        def __init__(self):
            super().__init__()
            self.c1 = nn.Conv2d(1, 32, 5, padding=2)
            self.c2 = nn.Conv2d(32, 64, 5, padding=2)
            self.f1 = nn.Linear(3136, 512)
            self.f2 = nn.Linear(512, 62)
            self.pool = nn.MaxPool2d(2, 2)

        def forward(self, x):
            x = self.pool(torch.relu(self.c1(x)))
            x = self.pool(torch.relu(self.c2(x)))
            return self.f2(torch.relu(self.f1(x.flatten(1))))

    torch.manual_seed(0)
    model = CNN()
    crit = nn.CrossEntropyLoss()
    # same samples/client as the jax side (BENCH_FEMNIST_SAMPLES) so the
    # vs_baseline ratio always compares identical workloads
    samples = int(os.environ.get("BENCH_FEMNIST_SAMPLES", "200"))
    xs, ys = _synth_clients(clients_per_round, samples, (28, 28, 1), 62)
    t0 = _now()
    for c in range(clients_per_round):
        opt = torch.optim.SGD(model.parameters(), lr=0.1)
        x = torch.from_numpy(xs[c]).permute(0, 3, 1, 2)
        y = torch.from_numpy(ys[c]).long()
        for s in range(0, len(x), batch_size):
            opt.zero_grad()
            loss = crit(model(x[s:s + batch_size]), y[s:s + batch_size])
            loss.backward()
            opt.step()
    return _now() - t0


def _mfu(flops, seconds):
    if not flops or not seconds or not PEAK_TFLOPS:
        return 0.0
    return (flops / seconds) / (PEAK_TFLOPS * 1e12)


def _max_mfu(details) -> float:
    """Largest MFU anywhere in a details artifact: mfu > 1.0 is
    physically impossible, so such a run documents a timing failure, not
    performance, and main() fails it.

    Delegates to `fedml_tpu.obs.trend.max_mfu` — the same recursive scan
    `scripts/perf_trend.py --lint_mfu` runs over committed artifacts — so
    the bench and the CI lint can never disagree about what an artifact
    claims (a nested scaling-curve cell counts in both or neither)."""
    from fedml_tpu.obs.trend import max_mfu
    return max_mfu(details)


def _repo_path(name):
    return os.path.join(os.path.dirname(os.path.abspath(__file__)), name)


def main():
    import jax
    forced = os.environ.get("BENCH_PLATFORM")
    if forced:
        jax.config.update("jax_platforms", forced)
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not forced:
        # no chip => fail: no skipped line, no carried number, no CPU
        # number under a device metric's name
        sys.exit(f"bench.py: jax found no TPU (platform "
                 f"{dev.platform!r}, device_kind {dev.device_kind!r}); "
                 f"nothing measured.  BENCH_PLATFORM=cpu asks for the "
                 f"explicit CPU developer run.")

    # persistent compilation cache (the CLI's helper; gates itself on the
    # resolved backend): warm compiles don't change any measured number
    # (warmup dispatch is excluded from timing loops)
    from fedml_tpu.experiments.main import enable_compile_cache
    enable_compile_cache()

    on_cpu = dev.platform == "cpu"
    if on_cpu:
        # explicit BENCH_PLATFORM=cpu developer run: shrink so it terminates
        # (a CNN round is ~7-14 s on CPU) — results go to
        # BENCH_DETAILS_cpu.json, never over the TPU artifact
        os.environ.setdefault("BENCH_FEMNIST_SAMPLES", "20")
        os.environ.setdefault("BENCH_SCALING", "0")
    global PEAK_TFLOPS
    PEAK_TFLOPS = _peak_for_device(dev)

    rounds = int(os.environ.get("BENCH_ROUNDS", "20"))
    full = os.environ.get("BENCH_MODE", "quick") == "full"
    details = {"platform": dev.platform,
               "captured_at": time.time(),
               "device_kind": str(getattr(dev, "device_kind", "unknown")),
               "n_devices": len(jax.devices()),
               "peak_tflops_assumed": PEAK_TFLOPS,
               "femnist_samples_per_client": int(os.environ.get(
                   "BENCH_FEMNIST_SAMPLES", "200")),
               "flops_accounting": (
                   "twin-program extrapolation (_honest_flops): scan "
                   "bodies counted per trip, LSTM recurrence unrolled in "
                   "the cost twin"),
               "configs": {}}
    out_name = os.environ.get(
        "BENCH_OUT",
        "BENCH_DETAILS_cpu.json" if on_cpu else "BENCH_DETAILS.json")

    # 0) torch CPU baseline (needs no accelerator)
    torch_s = bench_torch_baseline()
    details["torch_cpu_sequential_round_s"] = torch_s

    # 0a/0b) timing trust gate FIRST: linearity + readback-sync +
    # checksum, then the matmul-peak plausibility cap.  A failed gate
    # fails the whole run — without it, a non-synchronizing
    # block_until_ready turns every number below into dispatch-rate
    # fiction.  The peak measurement doubles as the empirical MFU
    # denominator floor: a plain matmul bounds the real chip peak from
    # below, so when it exceeds the device_kind table value, MFU is quoted
    # against it.
    sanity, mm, gate_failures = run_timing_gate(on_cpu)
    details["timing_sanity"] = sanity
    peak_src = ("BENCH_PEAK_TFLOPS env override"
                if os.environ.get("BENCH_PEAK_TFLOPS")
                else "device_kind table")
    if mm is not None:
        details["measured_matmul_tflops"] = mm
    if gate_failures:
        raise RuntimeError("timing self-check failed; nothing measured "
                           "this run is trustworthy: "
                           + "; ".join(gate_failures))
    if mm is not None:
        # an explicit BENCH_PEAK_TFLOPS pins the MFU denominator; only the
        # device_kind table value gets raised by measurement
        if (mm["bf16"] > PEAK_TFLOPS
                and not os.environ.get("BENCH_PEAK_TFLOPS")):
            PEAK_TFLOPS = mm["bf16"]
            peak_src = ("measured bf16 matmul throughput (exceeds the "
                        "device_kind table peak)")
    details["peak_tflops_used"] = PEAK_TFLOPS
    details["peak_tflops_source"] = peak_src
    # which backend compiled the FLOPs cost twins (recorded so
    # a backend-dependent cost-analysis divergence is attributable)
    details["twin_backend"] = (
        "cpu" if os.environ.get("BENCH_TWIN_DEVICE", "cpu") == "cpu"
        else dev.platform)

    # 1) cross-device headline
    round_s, flops, steps = bench_femnist_cnn(rounds)
    details["configs"]["femnist_cnn_c10"] = {
        "round_s": round_s, "rounds_per_s": 1.0 / round_s,
        "steps_per_round": steps,
        "flops_per_round": flops, "mfu": _mfu(flops, round_s)}

    # 1b) dispatch-amortised headline (scan K rounds per dispatch);
    # identical hyperparameters to 1), so per-round FLOPs are shared
    scan_round_s = bench_femnist_cnn_scanned(
        4 if on_cpu else max(rounds, 20), k=2 if on_cpu else 20)
    details["configs"]["femnist_cnn_c10_scan20"] = {
        "round_s": scan_round_s, "rounds_per_s": 1.0 / scan_round_s,
        "steps_per_round": steps,
        "flops_per_round": flops, "mfu": _mfu(flops, scan_round_s)}

    # 1c) twin backend cross-check: femnist twins compiled on the device
    # backend vs the CPU twins the headline used
    if not on_cpu and os.environ.get("BENCH_TWIN_XCHECK", "1") != "0":
        details["twin_backend_delta"] = bench_twin_backend_delta(flops)

    # 2) NLP family: shakespeare char-LM (skipped on explicit-CPU runs)
    if not on_cpu:
        rnn_s, rnn_fl, rnn_steps = bench_shakespeare_rnn(
            max(3, rounds // 4))
        details["configs"]["shakespeare_rnn_c10_b4"] = {
            "round_s": rnn_s, "rounds_per_s": 1.0 / rnn_s,
            "steps_per_round": rnn_steps,
            "flops_per_round": rnn_fl, "mfu": _mfu(rnn_fl, rnn_s)}

    # 2c) defended aggregation: XLA transform hook vs fused Pallas kernel
    # (skipped on CPU: the interpreter path is not a perf number)
    if not on_cpu:
        rb = bench_robust_backends(max(3, rounds // 4))
        details["configs"]["fedavg_robust_weakdp_c10"] = {
            "round_s_xla": rb["xla"], "round_s_pallas": rb["pallas"],
            "pallas_speedup": rb["xla"] / rb["pallas"]}

    # 2d) pallas kernels at flagship size in bf16 (measure, then justify
    # or demote) — aggregation-only programs
    if not on_cpu:
        details["configs"]["pallas_kernels_flagship"] = \
            bench_agg_kernels_flagship()

    # 3) cohort scaling curve (FLOPs scale linearly from the c=10 twins)
    if os.environ.get("BENCH_SCALING", "1") != "0":
        curve = {}
        details["cohort_scaling"] = curve
        for c in (10, 32, 64, 128):
            rs, fl, _ = bench_femnist_cnn(max(3, rounds // 4),
                                          clients_per_round=c,
                                          flops_base=(flops, steps, 10))
            curve[str(c)] = {"rounds_per_s": 1.0 / rs,
                             "mfu": _mfu(fl, rs)}

    # 4) flagship cross-silo (skipped on explicit-CPU runs: resnet56
    # training steps take tens of seconds per round there)
    if not on_cpu:
        r56_rounds = max(3, rounds // 4)
        samples = int(os.environ.get("BENCH_R56_SAMPLES",
                                     "5000" if full else "512"))
        round_s56, flops56, steps56, spread56 = bench_resnet56_cifar10(
            r56_rounds, samples=samples)
        cfg56 = {
            "round_s": round_s56, "samples_per_client": samples,
            "steps_per_round": steps56,
            # per vmapped step (10 clients' B=64 batches advance together)
            "step_time_ms": 1e3 * round_s56 / max(steps56, 1),
            "flops_per_round": flops56, "mfu": _mfu(flops56, round_s56)}
        if spread56 is not None:
            # per-round blocked medians: a tight p10..p90 around the
            # median with a fat max = host spikes, not a real level shift
            cfg56["round_s_spread"] = spread56
            cfg56["step_time_ms_median"] = (
                1e3 * spread56["median"] / max(steps56, 1))
        details["configs"]["resnet56_cifar10_c10_b64"] = cfg56
    else:
        details["configs"]["resnet56_cifar10_c10_b64"] = {"mfu": 0.0,
                                                          "skipped": "cpu"}

    # 5) long-context transformer grad step (blockwise kv scan; the
    # reference has no comparable capability).  CPU: skipped.  The
    # flash/moe variants only run in BENCH_MODE=full (each a second
    # multi-minute XLA compile).
    if not on_cpu:
        lc_s, lc_tok = bench_longcontext_transformer()
        details["configs"]["transformer_T2048_blockwise"] = {
            "step_s": lc_s, "tokens_per_s": lc_tok}
        if full:
            # a flash-kernel failure propagates: it is a bug, not a skip
            fl_s, fl_tok = bench_longcontext_transformer(use_flash=True)
            details["configs"]["transformer_T2048_flash"] = {
                "step_s": fl_s, "tokens_per_s": fl_tok}
            # routed-FFN capability point: the SAME T=2048 config with a
            # Switch MoE FFN (8 experts) — directly comparable tokens/s
            # against transformer_T2048_blockwise (grouped routing keeps
            # dispatch linear in T)
            moe_s, moe_tok = bench_longcontext_transformer(moe_experts=8)
            details["configs"]["transformer_T2048_moe8"] = {
                "step_s": moe_s, "tokens_per_s": moe_tok}

    # 6) multi-device (skipped on 1-chip hosts)
    if len(jax.devices()) >= 2:
        from fedml_tpu.parallel.mesh import make_mesh
        n = len(jax.devices())
        mesh = make_mesh(client_axis=n)
        rs, fl, _ = bench_femnist_cnn(max(3, rounds // 4),
                                      clients_per_round=max(16, n),
                                      mesh=mesh,
                                      flops_base=(flops, steps, 10))
        details["configs"][f"femnist_cnn_mesh{n}"] = {
            "rounds_per_s": 1.0 / rs, "mfu": _mfu(fl, rs)}

    # sanity: MFU needs achieved-flops <= peak; XLA cost_analysis can
    # overcount (it models the unfused HLO), so flag near/over-peak values
    # instead of reporting them as utilization
    suspect = []
    for name, c in list(details["configs"].items()) + [
            (f"scaling_{k}", v)
            for k, v in details.get("cohort_scaling", {}).items()]:
        if c.get("mfu", 0.0) > 0.95:
            suspect.append(name)
    if suspect:
        details["mfu_warning"] = (
            "mfu > 0.95 for " + ", ".join(suspect) + " — XLA cost-analysis "
            "flops likely overcount vs the fused executable; treat these "
            "as upper bounds, trust round_s/step_time_ms")

    # primary line.  Explicit-CPU runs write a separate details file;
    # their vs_baseline is still honest — torch CPU vs jax CPU on the same
    # host is a same-platform comparison.
    details["vs_baseline_meaning"] = (
        "ratio vs the reference's SEQUENTIAL standalone simulator loop "
        "(fedavg_api.py:52-66) in torch on THIS HOST'S CPU — an "
        "architectural comparison (one-program cohort vs per-client "
        "Python loop), NOT a GPU-hardware claim; the 8xV100 wall-clock "
        "north star (BASELINE.md) remains unmeasured from both sides")
    # an artifact whose best MFU exceeds 1.0 documents a timing failure,
    # not performance: fail instead of writing it
    if _max_mfu(details) > 1.0:
        raise RuntimeError(
            f"max mfu {_max_mfu(details):.2f} > 1.0 — achieved FLOP/s "
            "above the measured peak is physically impossible; timing "
            "untrusted, nothing written")
    with open(_repo_path(out_name), "w") as f:
        json.dump(details, f, indent=2)
    best_round_s = min(round_s, scan_round_s)
    line = {
        "metric": "fedavg_round_time_femnist_cnn",
        "value": round(1.0 / best_round_s, 3),
        "unit": "rounds/sec",
        "platform": details["platform"],
        "device_kind": details["device_kind"],
        "vs_baseline": round((torch_s or best_round_s) / best_round_s, 3),
        "rounds_per_s_dispatch": round(1.0 / round_s, 3),
        "rounds_per_s_scan20": round(1.0 / scan_round_s, 3),
        "mfu_femnist": round(details["configs"]["femnist_cnn_c10"]["mfu"], 4),
        "mfu_resnet56": round(
            details["configs"]["resnet56_cifar10_c10_b64"]["mfu"], 4),
    }
    if on_cpu:
        line["note"] = ("explicit BENCH_PLATFORM=cpu run; vs_baseline is a "
                        "same-host torch-vs-jax CPU comparison, not a TPU "
                        "number")
    print(json.dumps(line))


if __name__ == "__main__":
    main()
