#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Run as ``python chip_smoke.py`` from the root of a checkout: no arguments,
no network, one process (a chip belongs to one process at a time).  It
drives the main path — a federated round, as ``python -m fedml_tpu`` runs
it — ONCE on the TPU at the full width of the one model the repo has at a
published width, ResNet-56 on CIFAR-10 (depth 56, widths 16/32/64,
32x32x3 input, 10 silos at B=64; only samples per silo, local epochs and
rounds are cut, and the data is the hermetic synthetic twin), then the
parts of the round the first invocation's config gates keep apart:

  round    --algo cross_silo --agg_mode stream with admission, a defended
           mean (--norm_clip), --server_opt momentum, a checkpoint every
           round, --perf/--device_obs: staging -> local training ->
           wire/ingest -> admission -> fold/finalize -> server step ->
           journal/checkpoint
  kernels  each in-repo Pallas kernel reached through its CLI selector at
           ResNet-56 parameter size, COMPILED by Mosaic (never
           interpreted), then checked against its XLA compose to the
           tolerance its own test uses; the fused attention core
           (`models/fused_attention.py`), which has no selector, through
           `causal_blocked_attention` at a shape it admits
  selected the indexed grouped-query attention of
           benchmark/models/keye_vl2_30b_a3b.json at its published widths
           and 8,192 positions (`models/indexed_attention.py`: the fused
           kernels with the selection and grouped key heads) against the
           plain reference's attention on the same weights, how many
           (query, key) pairs the two select differently, and the
           selected kernels' result and gradients against the XLA blocks
           on one selection
  flash    --attn_flash at the CLI's default shapes is refused at config
           time with the reason; the kernel itself runs at a sequence it
           accepts and matches dense attention
  decode   a DecodeScheduler answers a few requests from a published
           TransformerLM at the CLI's default width (the donated-cache
           step compiles and runs on the device)
  mesh     only with >= 4 devices: cross-device waves over a 4-chip
           clients mesh and the ResNet-56 fold state over 4 model shards,
           each equal to its single-chip run

Any failed assertion is an uncaught exception.  There is no fallback: no
platform is set, no ``--platform cpu`` is passed, nothing is interpreted,
and nothing is skipped except ``mesh`` on a host with fewer than 4 chips.

What it prints are set-up facts, not speeds: the device as JAX reports it,
each phase's wall seconds, seconds spent tracing, lowering and compiling
and number of compilations (every compile of the process, from the
program's own account, `fedml_tpu.obs.trace.compile_totals`; for the
round phase also the named compile ledger of `fedml_tpu.obs.device`), and
the persistent compile cache's hits and misses.  The last line of stdout is one JSON object, ``{"ok": true,
"device": {...}}``; the exit code is 0 only then.
"""

import contextlib
import json
import logging
import os
import sys
import tempfile
import time

# the one platform this script accepts; every placement assertion below
# compares against it
ON = "tpu"

# the flagship cross-silo config (BASELINE.md): 10 silos, B=64, E=1
R56 = ["--model", "resnet56", "--dataset", "cifar10",
       "--client_num_in_total", "10", "--client_num_per_round", "10",
       "--batch_size", "64", "--epochs", "1", "--log_stdout", "false",
       "--frequency_of_the_test", "1"]


def say(*parts) -> None:
    print(*parts, flush=True)


class KernelPaths(logging.Handler):
    """Collects `core.pallas_agg.pallas_interpret`'s decisions: which
    path (compiled / interpreted) each in-repo kernel took."""

    def __init__(self):
        super().__init__(level=logging.INFO)
        self.paths = {}
        log = logging.getLogger("fedml_tpu.core.pallas_agg")
        log.setLevel(logging.INFO)
        log.addHandler(self)

    def emit(self, record) -> None:
        if record.msg == "pallas kernel %s: %s":
            kernel, path = record.args
            self.paths.setdefault(kernel, set()).add(path)

    def assert_compiled(self, kernel: str) -> None:
        assert self.paths.get(kernel) == {"compiled"}, (
            f"pallas kernel {kernel} took {self.paths.get(kernel)}, not "
            f"the compiled path")


class Phases:
    """Per-phase wall / compile accounting over the program's own
    compile totals (`fedml_tpu.obs.trace.compile_totals`: a persistent
    cache hit still counts as a near-zero-second compile, and JAX
    records a miss only when it WRITES the entry)."""

    KEYS = ("compiles", "trace_s", "lower_s", "compile_s", "cache_hits",
            "cache_misses")

    def __init__(self):
        from fedml_tpu.obs.trace import compile_totals
        self.totals = compile_totals
        self.rows = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        say(f"== phase {name}")
        before, t0 = self.totals(), time.perf_counter()
        yield
        wall, after = time.perf_counter() - t0, self.totals()
        row = {"phase": name, "wall_s": round(wall, 1),
               **{k: after[k] - before[k] for k in self.KEYS}}
        for k in ("trace_s", "lower_s", "compile_s"):
            row[k] = round(row[k], 1)
        self.rows.append(row)
        say(f"== phase {name}: wall {row['wall_s']} s, tracing "
            f"{row['trace_s']} s, lowering {row['lower_s']} s, compiling "
            f"{row['compile_s']} s in {row['compiles']} compilations, "
            f"persistent cache {row['cache_hits']} hits / "
            f"{row['cache_misses']} misses (written)")


def read_jsonl(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def all_finite(values) -> bool:
    import math
    return all(isinstance(v, (int, float)) and math.isfinite(v)
               for v in values)


def run_cli(argv):
    from fedml_tpu.experiments.main import main as cli_main
    say("   $ python -m fedml_tpu " + " ".join(argv))
    return cli_main(argv)


def assert_ledger(run_dir, rounds):
    """The flight recorder's account of a run: one line per round, zero
    recompilations after the first (the sentry's count, and nothing new
    in the named compile ledger), and a global that moved each round."""
    rows = read_jsonl(os.path.join(run_dir, "perf.jsonl"))
    assert len(rows) == rounds, (len(rows), rounds)
    later = sum(r["recompiles"] for r in rows[1:])
    assert later == 0 and not any(r["device"]["compiles"]
                                  for r in rows[1:]), (
        f"{later} recompilations after the first round: "
        f"{[(r.get('recompiled'), r['device']['compiles']) for r in rows[1:]]}")
    crcs = [r["global_crc"] for r in rows]
    assert len(set(crcs)) == rounds, f"the global did not change: {crcs}"
    return rows


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_round(tmp):
    run, ckpt = os.path.join(tmp, "round"), os.path.join(tmp, "round_ckpt")
    rounds = 3
    summary = run_cli(
        ["--algo", "cross_silo", "--silo_backend", "local",
         "--agg_mode", "stream", *R56, "--comm_round", str(rounds),
         "--admission", "on", "--norm_clip", "5.0",
         "--server_opt", "momentum",
         "--checkpoint_dir", ckpt, "--checkpoint_every", "1",
         "--perf", "true", "--device_obs", "true", "--run_dir", run])
    metrics = [m for m in read_jsonl(os.path.join(run, "metrics.jsonl"))
               if "train_loss" in m]
    assert len(metrics) == rounds, metrics
    assert all_finite([m[k] for m in metrics
                       for k in ("train_loss", "test_loss")]), metrics
    assert summary["global_platform"] == ON, summary
    assert summary["global_devices"] == 1, summary
    assert os.listdir(ckpt), "no checkpoint was written"
    rows = assert_ledger(run, rounds)
    dev = rows[-1]["device"]
    assert dev["backend"] == ON and dev["peak_tflops"], dev
    ledger = [c for r in rows for c in r["device"]["compiles"]]
    say(f"   losses {[round(m['train_loss'], 4) for m in metrics]}; "
        f"global on {summary['global_platform']}; peak from "
        f"{dev['peak_source']!r}; obs/device compile ledger: "
        f"{len(ledger)} named compiles, "
        f"{sum(c['wall_s'] for c in ledger):.1f} s, all in round 0: "
        f"{sorted(c['fn'] for c in rows[0]['device']['compiles'])}")


def r56_tree(seed=0):
    """A ResNet-56 parameter tree, random from a seed — the size every
    kernel check below runs at."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.experiments.models import create_workload
    wl = create_workload("resnet56", "cifar10", 10, (32, 32, 3))
    batch = {"x": jnp.zeros((2, 32, 32, 3), jnp.float32),
             "y": jnp.zeros((2,), jnp.int32),
             "mask": jnp.ones((2,), jnp.float32)}
    return wl.init(jax.random.key(seed), batch)


def perturbed(tree, n, scale, seed):
    """``n`` client copies of ``tree``, each moved by N(0, scale)."""
    import jax
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    return jax.tree.unflatten(treedef, [
        leaf[None] + scale * jax.random.normal(k, (n,) + leaf.shape,
                                               leaf.dtype)
        for leaf, k in zip(leaves, keys)])


def max_abs_diff(a, b) -> float:
    import jax
    import numpy as np
    return max(float(np.max(np.abs(np.asarray(x, np.float64)
                                   - np.asarray(y, np.float64))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def check_robust_aggregate(tree):
    """_agg_kernel == vmap(clip_update) + tree_weighted_mean
    (tests/test_pallas_agg.py: atol 2e-5; noise std within 5 %)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from fedml_tpu.core.pallas_agg import (make_fused_robust_aggregate,
                                           pallas_interpret)
    from fedml_tpu.core.pytree import tree_weighted_mean
    from fedml_tpu.core.robust import clip_update
    n, bound, sigma = 10, 5.0, 0.025
    stacked = perturbed(tree, n, 1e-2, seed=1)
    w = jnp.arange(1.0, n + 1.0)
    key = jax.random.key(2)
    interpret = pallas_interpret("robust_aggregate")
    got = jax.jit(make_fused_robust_aggregate(
        norm_bound=bound, noise_std=0.0, interpret=interpret))(
            stacked, w, tree, key)
    want = jax.jit(lambda s, g: tree_weighted_mean(
        jax.vmap(clip_update, in_axes=(0, None, None))(s, g, bound), w))(
            stacked, tree)
    diff = max_abs_diff(got, want)
    assert diff <= 2e-5, f"robust_aggregate vs XLA compose: {diff}"
    noised = jax.jit(make_fused_robust_aggregate(
        norm_bound=bound, noise_std=sigma, interpret=interpret))(
            stacked, w, tree, key)
    delta = np.concatenate([
        (np.asarray(a) - np.asarray(b)).ravel() for a, b in
        zip(jax.tree.leaves(noised), jax.tree.leaves(got))])
    ratios = np.asarray(w / w.sum())
    want_std = sigma * float(np.sqrt((ratios ** 2).sum()))
    assert np.isfinite(delta).all() and abs(delta.mean()) < 1e-3
    np.testing.assert_allclose(delta.std(), want_std, rtol=0.05)
    say(f"   robust_aggregate: max |kernel - xla| {diff:.2e}; in-kernel "
        f"noise std {delta.std():.5f} (want {want_std:.5f})")


def check_shard_finalize(tree):
    """_finalize_kernel == the XLA finalize (tests/test_shard_spine.py:
    sigma=0 bit-equal; noise std within 10 %)."""
    import jax
    import numpy as np
    from fedml_tpu.shard_spine.agg import ShardedStreamingAggregator
    from fedml_tpu.shard_spine.plan import build_shard_plan
    host = jax.tree.map(np.asarray, tree)
    stacked = perturbed(tree, 3, 1e-2, seed=3)
    ups = [jax.tree.map(lambda v: np.asarray(v[i]), stacked)
           for i in range(3)]
    plan = build_shard_plan(host, 2)

    def run(fused, noise):
        agg = ShardedStreamingAggregator(plan, host, fused=fused,
                                         noise_std=noise, seed=9)
        agg.reset(host)
        for i, u in enumerate(ups):
            agg.fold(u, float(i + 1))
        return agg.finalize(1)

    xla, fused = run(False, 0.0), run(True, 0.0)
    diff = max_abs_diff(xla, fused)
    assert diff == 0.0, f"shard_finalize sigma=0 vs XLA compose: {diff}"
    sigma = 0.5
    delta = np.concatenate([
        (np.asarray(a) - np.asarray(b)).ravel() for a, b in
        zip(jax.tree.leaves(run(True, sigma)), jax.tree.leaves(fused))])
    assert np.isfinite(delta).all() and abs(delta.mean()) < 0.02
    np.testing.assert_allclose(delta.std(), sigma, rtol=0.1)
    say(f"   shard_finalize: kernel bit-equal to xla at sigma=0; "
        f"in-kernel noise std {delta.std():.4f} (want {sigma})")


def check_secagg_mask(tree):
    """_mask_kernel: the cohort's ring sum == the sum of the quantized
    updates EXACTLY, and dequantizes to the weighted mean
    (tests/test_pallas_mask.py: atol 2N/scale)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from fedml_tpu.core.pallas_agg import pallas_interpret
    from fedml_tpu.secure.pallas_mask import fused_quantize_mask
    from fedml_tpu.secure.secagg import dequantize, quantize
    n, scale, clip = 4, 2.0 ** 16, 2.0 ** 14
    stacked = perturbed(tree, n, 1e-2, seed=4)
    ups = [jax.tree.map(lambda v: v[i], stacked) for i in range(n)]
    weights = np.random.RandomState(9).dirichlet(np.ones(n))
    key = jax.random.key(5)
    interpret = pallas_interpret("secagg_mask")
    masked = [fused_quantize_mask(ups[i], weights[i], i, key, n, scale,
                                  clip, interpret=interpret)
              for i in range(n)]
    ring = jax.tree.map(lambda *xs: sum(xs[1:], xs[0]), *masked)
    plain = jax.tree.map(
        lambda *xs: sum(xs[1:], xs[0]),
        *[quantize(jax.tree.map(lambda x: x * jnp.float32(weights[i]),
                                ups[i]), scale, clip) for i in range(n)])
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), ring, plain)
    q0 = quantize(jax.tree.map(lambda x: x * jnp.float32(weights[0]),
                               ups[0]), scale, clip)
    same = np.mean(np.concatenate([
        (np.asarray(a) == np.asarray(b)).ravel() for a, b in
        zip(jax.tree.leaves(masked[0]), jax.tree.leaves(q0))]))
    assert same < 0.01, f"{same:.3f} of one masked upload is unmasked"
    want = jax.tree.map(lambda *xs: sum(w * np.asarray(x) for w, x in
                                        zip(weights, xs)), *ups)
    diff = max_abs_diff(dequantize(ring, scale), want)
    assert diff <= n / scale * 2, f"secagg_mask dequantized sum: {diff}"
    say(f"   secagg_mask: ring sum exact; max |dequantized - mean| "
        f"{diff:.2e}; {same:.4f} of a single upload left unmasked")


def check_latent_attention(kernels):
    """`causal_blocked_attention` at a shape the fused core admits: it
    must hand over to the kernels, Mosaic must compile them, and result
    and gradients must be the XLA blocks' to the default precision's
    rounding (both round a product's operands to bfloat16)."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.models import transformer as tr
    keys = jax.random.split(jax.random.key(0), 4)
    q, k = (jax.random.normal(x, (1, 1024, 2, 256)) for x in keys[:2])
    v, w = (jax.random.normal(x, (1, 1024, 2, 128)) for x in keys[2:])
    assert tr.fused_core_fits(q, k, v)

    def all_of(core):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(core(q, k, v) * w), (0, 1, 2)))(q, k, v)
    (lf, gf), (lx, gx) = (all_of(tr.causal_blocked_attention),
                          all_of(lambda q, k, v: tr._xla_blocked_attention(
                              q, k, v, 512)))
    kernels.assert_compiled("latent_attention")
    gaps = [float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
            for a, b in zip(gf, gx)]
    assert all_finite([float(lf), *gaps]) and max(gaps) < 1e-2, gaps
    assert abs(float(lf) - float(lx)) < 1e-2 * float(
        jnp.linalg.norm(w)), (lf, lx)
    say(f"   latent_attention: fused vs XLA blocks at T=1024, widths "
        f"256/128: |dq|, |dk|, |dv| gaps {gaps[0]:.2e} {gaps[1]:.2e} "
        f"{gaps[2]:.2e} of the norm")


def phase_kernels(tmp, kernels):
    tree = r56_tree()
    # _agg_kernel: clip + in-kernel weak-DP noise + weighted mean
    s = run_cli(["--algo", "fedavg_robust", "--defense_backend", "pallas",
                 "--defense", "weak_dp", *R56, "--comm_round", "1"])
    assert all_finite([s["train_loss"], s["test_loss"]]), s
    kernels.assert_compiled("robust_aggregate")
    check_robust_aggregate(tree)
    # _finalize_kernel: --fused_finalize auto picks it on a TPU
    run = os.path.join(tmp, "shard2")
    s = run_cli(["--algo", "cross_silo", "--silo_backend", "local",
                 "--agg_mode", "stream", "--model_shards", "2", *R56,
                 "--comm_round", "2", "--perf", "true",
                 "--device_obs", "true", "--run_dir", run])
    assert all_finite([s["train_loss"], s["test_loss"]]), s
    assert s["shard_state_devices"] >= 1, s
    rows = assert_ledger(run, 2)
    fns = {c["fn"] for r in rows for c in r["device"]["compiles"]}
    assert {"fused_finalize[s0]", "fused_finalize[s1]"} <= fns, fns
    kernels.assert_compiled("shard_finalize")
    say(f"   shard state on {s['shard_state_devices']} device(s) for "
        f"2 shards")
    check_shard_finalize(tree)
    # _mask_kernel: quantize + pairwise masks
    s = run_cli(["--algo", "turboaggregate", "--secagg_backend", "pallas",
                 *R56, "--client_num_per_round", "4", "--group_num", "2",
                 "--comm_round", "1"])
    assert all_finite([s["train_loss"], s["test_loss"]]), s
    kernels.assert_compiled("secagg_mask")
    check_secagg_mask(tree)
    # the attention core's forward and backward kernels
    check_latent_attention(kernels)


def check_selected_kernels(kernels, selected, heads, kv_heads, width,
                           block):
    """`causal_blocked_attention` with ``selected`` [1, T, T] and
    ``kv_heads`` key heads under ``heads`` query heads: it must hand over
    to the selected kernels, Mosaic must compile them, and result and
    gradients must be the XLA blocks' on the same selection to the
    default precision's rounding."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.models import transformer as tr
    t = selected.shape[-1]
    keys = jax.random.split(jax.random.key(3), 4)
    q, w = (jax.random.normal(x, (1, t, heads, width)) for x in keys[:2])
    k, v = (jax.random.normal(x, (1, t, kv_heads, width))
            for x in keys[2:])
    assert tr.fused_core_fits(q, k, v, selected)

    def all_of(core):
        def weighted(q, k, v):
            out = core(q, k, v)
            return jnp.sum(out * w), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            weighted, (0, 1, 2), has_aux=True))(q, k, v)
        return (out,) + grads
    fused = all_of(lambda q, k, v: tr.causal_blocked_attention(
        q, k, v, block, selected))
    kernels.assert_compiled("selected_attention")
    plain = all_of(lambda q, k, v: tr._xla_blocked_attention(
        q, k, v, block, selected))
    gaps = [float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
            for a, b in zip(fused, plain)]
    assert all_finite(gaps) and max(gaps) < 1e-2, gaps
    say(f"   selected_attention: kernels vs XLA blocks at T={t}, "
        f"{heads}/{kv_heads} heads of {width}, one selection: out, dq, dk, "
        f"dv {' '.join(f'{x:.2e}' for x in gaps)} of the norm")


def phase_selected(kernels, config="benchmark/models/keye_vl2_30b_a3b.json",
                   t=8192, block=1024):
    """One attention layer of the Keye-VL-2.0 configuration as the cell
    runs it (published widths, 8,192 positions, blocks of 1,024) against
    the plain reference's on the same weights, both at the default
    precision: the result, and the selection itself (the same indexer
    inputs through `index_selection` and through the reference's head by
    head scores and `top_k`), which has to differ in a vanishing share of
    its pairs for the cell's comparison to mean anything; then the
    selected kernels against the XLA blocks on that selection."""
    import jax
    import jax.numpy as jnp
    from benchmark.configs import keye_vl2_30b_a3b as ref
    from fedml_tpu.experiments.models import arch_of
    from fedml_tpu.models.indexed_attention import (IndexedAttention,
                                                    index_selection)
    from fedml_tpu.models.transformer import rotary
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), config)
    arch = arch_of(path)
    with open(path) as f:
        m = ref._Frozen({"initializer_range": 0.02, **json.load(f)})
    x = jax.random.normal(jax.random.key(0), (1, t, arch.hidden_size))
    pos = jnp.broadcast_to(jnp.arange(t), (3, t))
    layer, plain = IndexedAttention(arch, block_size=block), ref._Attention(m)
    params = jax.jit(layer.init)(jax.random.key(1), x, pos)["params"]
    got = jax.jit(lambda p: layer.apply({"params": p}, x, pos))(params)
    want = jax.jit(lambda p: plain.apply({"params": p}, x, pos))(params)
    gap = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert all_finite([gap]) and gap < 1e-2, gap
    say(f"   selected core at T={t}, {arch.num_attention_heads}/"
        f"{arch.num_key_value_heads} heads of {arch.head_dim}, top-"
        f"{arch.index_topk}: program vs plain reference {gap:.2e} of the "
        f"norm")

    ih, idim = arch.indexer_num_heads, arch.indexer_head_dim
    keys = jax.random.split(jax.random.key(2), 3)
    q_i = rotary(jax.random.normal(keys[0], (1, t, ih, idim)), pos[0],
                 arch.rope_theta)
    k_i = rotary(jax.random.normal(keys[1], (1, t, 1, idim)), pos[0],
                 arch.rope_theta)[:, :, 0]
    w_i = jax.random.normal(keys[2], (1, t, ih)) * (ih * idim) ** -0.5

    @jax.jit
    def plainly(q_i, k_i, w_i):
        index = jnp.zeros((t, t), jnp.float32)
        for j in range(ih):
            index = index + w_i[0, :, j, None] * jax.nn.relu(
                q_i[0, :, j] @ k_i[0].T)
        return ref._select(index, arch.index_topk)
    for precision in ("default", "highest"):
        with jax.default_matmul_precision(precision):
            ours = jax.jit(lambda *a: index_selection(
                *a, arch.index_topk, block))(q_i, k_i, w_i)[0]
            theirs = plainly(q_i, k_i, w_i)
        chosen = int(jnp.sum(theirs))
        assert int(jnp.sum(ours)) == chosen == ref.selected_pairs(
            t, arch.index_topk)
        apart = int(jnp.sum(ours != theirs)) // 2
        assert apart < 1e-3 * chosen, (precision, apart, chosen)
        say(f"   selection at {precision} precision: {apart} of {chosen} "
            f"pairs chosen by one and not the other")
    check_selected_kernels(kernels, ours[None], arch.num_attention_heads,
                           arch.num_key_value_heads, arch.head_dim, block)


def phase_flash():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from fedml_tpu.models import TransformerLM
    from fedml_tpu.models.transformer import FLASH_BLOCK
    # the CLI's default shapes: d_model 128 / 4 heads, Shakespeare
    # windows of 80 tokens — refused before anything is traced
    argv = ["--algo", "fedavg", "--model", "transformer", "--dataset",
            "shakespeare", "--attn_flash", "true", "--comm_round", "1",
            "--client_num_in_total", "4", "--client_num_per_round", "2",
            "--log_stdout", "false"]
    try:
        run_cli(argv)
    except ValueError as e:
        assert "--attn_flash" in str(e) and str(FLASH_BLOCK) in str(e), e
        say(f"   refused at config time: {e}")
    else:
        raise AssertionError("--attn_flash at 80-token windows was not "
                             "refused at config time")
    # the kernel itself, at the default width and a sequence it accepts
    toks = jnp.asarray(np.random.RandomState(0).randint(
        1, 90, (2, FLASH_BLOCK)), jnp.int32)
    dense = TransformerLM(vocab_size=90, max_len=FLASH_BLOCK)
    flash = TransformerLM(vocab_size=90, max_len=FLASH_BLOCK,
                          use_flash=True)
    params = dense.init(jax.random.key(0), toks)

    def loss(model):
        def f(p):
            logits = model.apply(p, toks).astype(jnp.float32)
            return jnp.mean(jax.nn.logsumexp(logits, -1)), logits
        return jax.jit(jax.value_and_grad(f, has_aux=True))

    (ld, yd), gd = loss(dense)(params)
    (lf, yf), gf = loss(flash)(params)
    assert all_finite([float(ld), float(lf)])
    fwd, bwd = max_abs_diff(yd, yf), max_abs_diff(gd, gf)
    assert fwd < 5e-2 and bwd < 5e-2, (fwd, bwd)
    say(f"   flash vs dense at T={FLASH_BLOCK}, head size 32: max |logit "
        f"diff| {fwd:.2e}, max |grad diff| {bwd:.2e}")


def phase_decode():
    import jax
    import jax.numpy as jnp
    from fedml_tpu.models import TransformerLM
    from fedml_tpu.serve.decode import DecodeScheduler
    from fedml_tpu.serve.registry import ModelRegistry
    model = TransformerLM(vocab_size=90)    # the CLI's default width
    params = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    registry = ModelRegistry(lambda p, x: x)
    registry.publish(params, 1)
    sched = DecodeScheduler(registry, model, slots=4, cache_len=64,
                            max_new=16).start()
    try:
        asks = [([5, 6, 7], 4), ([11], 9), ([3, 1, 4, 1, 5], 16),
                ([2, 7], 1), ([8, 8, 8, 8], 12), ([42], 6)]
        futures = [sched.submit(p, max_new=n) for p, n in asks]
        results = [f.result(timeout=600) for f in futures]
    finally:
        sched.stop()
    for (prompt, want), got in zip(asks, results):
        assert len(got.tokens) == want and got.version == 1 \
            and not got.truncated, (prompt, want, got.tokens)
        assert all(0 <= t < 90 for t in got.tokens), got.tokens
    assert sched._cache_size() == 1, sched._cache_size()
    where = {d.platform for leaf in jax.tree.leaves(sched._cache)
             for d in leaf.devices()}
    assert where == {ON}, where
    say(f"   {len(asks)} requests answered with "
        f"{[len(r.tokens) for r in results]} tokens over {sched.steps} "
        f"steps of one compiled decode step; cache on {where}")


def device_bytes(rows):
    """Per-device round-peak bytes in use, from the ledger's device
    section (memory_stats on a TPU)."""
    peak = {}
    for r in rows:
        for e in r["device"]["memory"] or []:
            b = e.get("round_peak_bytes", e.get("bytes_in_use")) or 0
            peak[e["id"]] = max(peak.get(e["id"], 0), b)
    return peak


def against_one_chip(tmp, name, argv, spread, rounds=2):
    """Run ``argv`` as it is and again with ``spread`` appended.  The CPU
    tier pins the two bit-identical (tests/test_cross_device.py,
    tests/test_shard_spine.py).  On the chip a client that trains in a
    differently shaped program (8 per device instead of 32 in one) runs
    its default-precision convolutions differently: the wave losses
    differed by 7e-5 relative, and by 1e-7 at full matmul precision
    (PERF.md, PR 21).  So the bound here is the final loss to 1e-3
    relative, and bit-identity is printed as a fact."""
    one, four = os.path.join(tmp, name + "1"), os.path.join(tmp, name + "4")
    s1 = run_cli([*argv, "--run_dir", one])
    s4 = run_cli([*argv, *spread, "--run_dir", four])
    r1, r4 = assert_ledger(one, rounds), assert_ledger(four, rounds)
    held = device_bytes(r4)
    assert len(held) >= 4 and all(held.values()), held
    rel = abs(s1["train_loss"] - s4["train_loss"]) / abs(s1["train_loss"])
    assert rel <= 1e-3, (s1, s4)
    same = [a["global_crc"] == b["global_crc"] for a, b in zip(r1, r4)]
    return s4, (f"bytes in use per device {held}; global bit-identical to "
                f"the one-chip run per round: {same}; relative train_loss "
                f"difference {rel:.1e}")


def phase_mesh(tmp):
    common = ["--comm_round", "2", "--perf", "true", "--device_obs", "true"]
    # (a) cross-device waves over a 4-chip clients mesh: cohort 64, two
    # waves of 32 per round
    s4, facts = against_one_chip(
        tmp, "waves",
        ["--algo", "cross_device", "--model", "cnn", "--dataset", "femnist",
         "--client_num_in_total", "128", "--client_num_per_round", "64",
         "--wave_size", "32", "--batch_size", "20", "--log_stdout", "false",
         "--frequency_of_the_test", "1", *common],
        ["--mesh_clients", "4"])
    assert s4["wave_devices"] == 4, s4
    assert s4["global_platform"] == ON and s4["global_devices"] == 4, s4
    say(f"   waves: cohort batch over {s4['wave_devices']} devices, global "
        f"on {s4['global_devices']}; {facts}")
    # (b) the ResNet-56 fold state over 4 model shards, one per chip,
    # against the replicated single-device fold
    s4, facts = against_one_chip(
        tmp, "fold",
        ["--algo", "cross_silo", "--silo_backend", "local", "--agg_mode",
         "stream", "--admission", "on", *R56, *common],
        ["--model_shards", "4"])
    assert s4["shard_state_devices"] == 4, s4
    say(f"   shards: fold state on {s4['shard_state_devices']} devices; "
        f"{facts}")


# ---------------------------------------------------------------------------

def main() -> int:
    import jax
    devices = jax.devices()
    dev = devices[0]
    say(f"platform: {dev.platform}")
    say(f"device_kind: {dev.device_kind}")
    say(f"devices: {len(devices)}")
    say(f"jax {jax.__version__}")
    if dev.platform != ON:
        say(f"chip_smoke: this is the on-chip check and jax found platform "
            f"{dev.platform!r} ({dev.device_kind}), not a TPU; nothing was "
            f"run")
        return 1

    from fedml_tpu.experiments.main import (compile_cache_dir,
                                            enable_compile_cache)
    enable_compile_cache()
    say(f"compile cache: "
        f"{compile_cache_dir() or os.environ['JAX_COMPILATION_CACHE_DIR']}"
        f" (min compile time "
        f"{jax.config.jax_persistent_cache_min_compile_time_secs} s)")
    kernels, phases = KernelPaths(), Phases()

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        with phases("round"):
            phase_round(tmp)
        with phases("kernels"):
            phase_kernels(tmp, kernels)
        with phases("selected"):
            phase_selected(kernels)
        with phases("flash"):
            phase_flash()
        with phases("decode"):
            phase_decode()
        if len(devices) >= 4:
            with phases("mesh"):
                phase_mesh(tmp)
        else:
            say(f"mesh: not run ({len(devices)} device)")

    say("phase       wall_s  trace_s  lower_s  compile_s  compiles  "
        "cache_hits  cache_misses")
    for r in phases.rows:
        say(f"{r['phase']:<10} {r['wall_s']:>7} {r['trace_s']:>8} "
            f"{r['lower_s']:>8} {r['compile_s']:>10} {r['compiles']:>9} "
            f"{r['cache_hits']:>11} {r['cache_misses']:>13}")
    say(f"kernel paths: { {k: sorted(v) for k, v in kernels.paths.items()} }")
    say(json.dumps({"ok": True,
                    "device": {"platform": dev.platform,
                               "kind": dev.device_kind,
                               "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
