"""The window and full grouped-query expert model on the normal path
(Laguna-XS.2, ``model_type`` ``laguna``): `models/window_attention.py`
under `TransformerLM`'s ``arch`` scaffolding against the plain reference
`benchmark/configs/laguna_xs2.py` at a tiny size on the CPU (seeded
weights, products at ``highest``); the window in both paths of
`causal_blocked_attention` (the XLA blocks against a dense masked oracle,
the fused kernels through the interpreter against the XLA blocks); YaRN and
the partial rotary; the share test of the expert cut; the key tiles the
window core visits and their counters on `wave.dispatch`; the built tree's
size; and that GLM's and Keye's trees do not move with the layer index
their arches are now given.
"""

import functools
import json
import math
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import window_attention as wa
from benchmark.configs import laguna_xs2 as ref
from benchmark.token_shards import token_shard_arrays, write_token_shards
from fedml_tpu.experiments.models import arch_of
from fedml_tpu.models import fused_attention as fa
from fedml_tpu.models import transformer as tr
from fedml_tpu.models.moe import HeldExpertMoE
from fedml_tpu.models.window_attention import (WindowAttention,
                                               WindowGQAArch, full_rotary)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODELS = os.path.join(ROOT, "benchmark", "tests", "tiny", "models")
TINY = json.load(open(os.path.join(MODELS, "laguna_xs2.json")))
FULL = json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                   "laguna_xs2.json")))


def _oracle(q, k, v, window):
    """Dense causal softmax over [T, T] scores, the key heads repeated,
    masked to ``t - window < s <= t``."""
    t, d = q.shape[1], q.shape[-1]
    g = q.shape[2] // k.shape[2]
    pos = np.arange(t)
    seen = (pos[None, :] <= pos[:, None]) & (pos[None, :] > pos[:, None]
                                             - window)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, g, axis=2)) \
        / math.sqrt(d)
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, jnp.repeat(v, g, axis=2))


def _qkvw(t, h, kv, d, seed=0, b=1):
    keys = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(keys[0], (b, t, h, d)),
            jax.random.normal(keys[1], (b, t, kv, d)),
            jax.random.normal(keys[2], (b, t, kv, d)),
            jax.random.normal(keys[3], (b, t, h, d)))


def _all_of(core, q, k, v, w):
    """(out, dq, dk, dv) of ``sum(core(q, k, v) * w)``."""
    def weighted(q, k, v):
        out = core(q, k, v)
        return jnp.sum(out * w), out
    (_, out), grads = jax.value_and_grad(weighted, (0, 1, 2),
                                         has_aux=True)(q, k, v)
    return (out,) + grads


# ---------------------------------------------------------------------------
# the window in the core
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t,block,window", [
    (2048, 512, 512), (2048, 512, 700), (2048, 256, 700),
    (40, 8, 1), (40, 8, 12), (40, 7, 5), (40, None, 12), (40, 16, 64)],
    ids=lambda x: str(x))
def test_xla_window_is_the_dense_masked_oracle(t, block, window):
    """Values and the gradients by q, k and v, float32 at ``highest`` on
    both sides: sums in another order only (blocks against one [T, T]
    array), 2e-6 of the largest entry."""
    q, k, v, w = _qkvw(t, 4, 2, 16, seed=t + window)
    with jax.default_matmul_precision("highest"):
        got = _all_of(lambda q, k, v: tr._xla_blocked_attention(
            q, k, v, block, window=window), q, k, v, w)
        want = _all_of(lambda q, k, v: _oracle(q, k, v, window), q, k, v, w)
    for x, y in zip(got, want):
        assert float(jnp.max(jnp.abs(x - y))) <= 2e-6 * max(
            float(jnp.max(jnp.abs(y))), 1.0)


# (window, query heads a key head): T = 4 x BLOCK, one key head
KERNEL_CASES = {"window_block_g6": (fa.BLOCK, 6), "window_block_g8":
                (fa.BLOCK, 8), "window_700_g6": (700, 6),
                "window_700_g8": (700, 8)}


@functools.lru_cache(maxsize=None)
def _both(case):
    """((out, dq, dk, dv) through the kernels, the same through XLA at
    ``highest``)."""
    window, g = KERNEL_CASES[case]
    q, k, v, w = _qkvw(4 * fa.BLOCK, g, 1, 128, seed=window + g)
    fused = _all_of(lambda q, k, v: fa.fused_causal_attention(
        q, k, v, window=window, interpret=True), q, k, v, w)
    with jax.default_matmul_precision("highest"):
        plain = _all_of(lambda q, k, v: tr._xla_blocked_attention(
            q, k, v, fa.BLOCK // 2, window=window), q, k, v, w)
    return fused, plain


@pytest.mark.parametrize("what", ["out", "dq", "dk", "dv"])
@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_window_kernels_agree_with_the_xla_path(case, what):
    """The kernels round the operands of a product to bfloat16 as the
    chip's default precision does; the XLA path here does not: they agree
    to that rounding (as `tests/test_fused_attention.py`'s other kernels
    do), nowhere near a wrong window, mask, block or key head."""
    fused, plain = _both(case)
    i = ["out", "dq", "dk", "dv"].index(what)
    got, want = np.asarray(fused[i], np.float64), np.asarray(plain[i],
                                                             np.float64)
    assert got.shape == want.shape and fused[i].dtype == jnp.float32
    assert np.isfinite(got).all()
    assert np.linalg.norm(got - want) <= 8e-3 * np.linalg.norm(want)
    assert np.abs(got - want).max() <= 0.05 * np.abs(want).max()


def test_rows_see_no_key_outside_their_window():
    """Keys and values more than ``window - 1`` positions before a row
    leave it as it was, bit for bit, through the kernels: in tiles the
    loop no longer visits and in the tile it masks by the window."""
    t, window, block = 512, 150, 128
    q, k, v, _ = _qkvw(t, 2, 1, 128, seed=5)
    k2, v2, *_ = _qkvw(t, 1, 1, 128, seed=6)
    run = functools.partial(fa.fused_causal_attention, block=block,
                            interpret=True, window=window)
    base = run(q, k, v)
    cut = 200           # rows from cut + window - 1 on see no key < cut
    early = jnp.arange(t)[None, :, None, None] < cut
    moved = run(q, jnp.where(early, k2, k), jnp.where(early, v2, v))
    row = cut + window - 1
    assert jnp.array_equal(moved[:, row:], base[:, row:])
    assert not jnp.array_equal(moved[:, row - 1], base[:, row - 1])


@pytest.mark.parametrize("g", [1, 2])
def test_a_window_past_the_sequence_is_causal_attention(g):
    """A window of T keys or more is the causal core, on the XLA path bit
    for bit and through the kernels to the interpreter's rounding of the
    same bfloat16 products (a hundred times closer than the kernels
    stand to the XLA blocks)."""
    q, k, v, w = _qkvw(384, 2, 2 // g, 128, seed=g)
    np.testing.assert_array_equal(
        tr._xla_blocked_attention(q, k, v, 128, window=384),
        tr._xla_blocked_attention(q, k, v, 128))
    got = _all_of(lambda q, k, v: fa.fused_causal_attention(
        q, k, v, block=128, interpret=True, window=10_000), q, k, v, w)
    want = _all_of(lambda q, k, v: fa.fused_causal_attention(
        q, k, v, block=128, interpret=True), q, k, v, w)
    for x, y in zip(got, want):
        x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
        assert np.linalg.norm(x - y) <= 1e-4 * np.linalg.norm(y)


def test_admits_any_window_of_a_key_and_never_beside_a_selection():
    q, k, v = (jnp.ones((1, 512, h, 128), jnp.float32) for h in (8, 1, 1))
    assert fa.admits(q, k, v, window=1) and fa.admits(q, k, v, window=512)
    assert not fa.admits(q, k, v, window=0)
    selected = jax.ShapeDtypeStruct((1, 512, 512), jnp.bool_)
    assert not fa.admits(q, k, v, selected, window=512)
    assert fa.kernel_name(8, None, 512) == fa.WINDOW_KERNEL
    assert fa.kernel_name(1, None, 512) == fa.WINDOW_KERNEL
    assert fa.kernel_name(8) == fa.SELECTED_KERNEL
    with pytest.raises(ValueError, match="window"):
        tr.causal_blocked_attention(q, k, v, 128, window=0)


@pytest.mark.parametrize("t,block,window,want", [
    (8192, 512, 512, (136, 31)), (2048, 512, 700, (10, 9)),
    (40, 8, 12, (15, 12)), (40, 8, 1, (15, 5)), (40, 8, 64, (15, 15))])
def test_window_tiles(t, block, window, want):
    """A window of 512 at blocks of 512: the diagonal tile and the one
    before it, but the first block's: 31 of 136 (22.79 %)."""
    assert tr.window_tiles(t, block, window) == want
    n = -(-t // block)
    seen = sum(1 for i in range(n) for j in range(i + 1)
               if (i * block) - ((j + 1) * block - 1) < window)
    assert seen == want[1]


@pytest.mark.parametrize("block,window,n", [
    (512, 512, 16), (512, 700, 6), (128, 300, 9), (8, 12, 5), (8, 1, 5),
    (8, 64, 5), (8, 16, 6)])
def test_window_bounds_walk_the_tiles_the_rows_see(block, window, n):
    """The forward loop's key blocks (`window_key_blocks`) are the tiles
    some row sees, the unmasked ones those every row sees whole, and the
    backward loop's query blocks (`window_query_blocks`) are the same tiles
    walked from the key side: the kernels, the XLA path and the tile
    counter read their bounds from these two."""
    def rows_seeing(i, j):      # (some row sees a key of it, all see all)
        some = every = False
        for r in range(i * block, (i + 1) * block):
            lo, hi = max(r - window + 1, j * block), min(r, (j + 1) * block - 1)
            some |= lo <= hi
            every = (every or r == i * block) and (
                r - window + 1 <= j * block and (j + 1) * block - 1 <= r)
        return some, every
    for i in range(n):
        first, whole = fa.window_key_blocks(i, block, window)
        for j in range(i):
            some, every = rows_seeing(i, j)
            assert (first <= j) == some, (i, j)
            assert (whole <= j) == every, (i, j)
    for j in range(n):
        part, end = fa.window_query_blocks(j, block, window, n)
        for i in range(j + 1, n):
            first, whole = fa.window_key_blocks(i, block, window)
            assert (i < end) == (first <= j), (i, j)
            assert (i < part) == (whole <= j), (i, j)


# ---------------------------------------------------------------------------
# rotary
# ---------------------------------------------------------------------------

def _yarn_by_hand(dim, base, factor, original, beta_fast, beta_slow):
    """transformers' ``_compute_yarn_parameters`` (truncate on) written out
    again in float64."""
    def correction_dim(rotations):
        return (dim * math.log(original / (rotations * 2 * math.pi))) / (
            2 * math.log(base))
    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    out = []
    for j in range(dim // 2):
        pos_freq = base ** (2 * j / dim)
        ramp = min(max((j - low) / (high - low), 0.0), 1.0)
        extrapolation = 1 - ramp
        out.append(1 / (factor * pos_freq) * (1 - extrapolation)
                   + 1 / pos_freq * extrapolation)
    return np.asarray(out), low, high


def test_yarn_frequencies_are_transformers():
    """At the published full-layer values the correction range is
    frequencies [5, 16] of the 32: up to its start extrapolated
    (``theta^(-2j/64)``), from its end on divided by 64, a ramp between."""
    rope = FULL["rope_parameters"]["full_attention"]
    dim = int(FULL["head_dim"] * rope["partial_rotary_factor"])
    assert dim == 64
    got = tr.yarn_inv_freq(dim, rope["rope_theta"], rope["factor"],
                           rope["original_max_position_embeddings"],
                           rope["beta_fast"], rope["beta_slow"])
    want, low, high = _yarn_by_hand(
        dim, rope["rope_theta"], rope["factor"],
        rope["original_max_position_embeddings"], rope["beta_fast"],
        rope["beta_slow"])
    assert got.dtype == np.float32 and got.shape == (32,)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    assert (low, high) == (5, 16)
    plain = 5e5 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(got[:low + 1], plain[:low + 1], rtol=2e-6)
    np.testing.assert_allclose(got[high:], plain[high:] / 64, rtol=2e-6)
    assert np.all(np.diff(got) < 0)
    # the reference's own transcription
    np.testing.assert_array_equal(ref._yarn(dim, rope), got)
    # the attention factor is the published one: 0.1 ln(64) + 1
    assert rope["attention_factor"] == pytest.approx(0.1 * math.log(64) + 1)


def test_partial_rotary_turns_the_first_half_only():
    """A full layer's rotary leaves elements 64-127 of each head as they
    are, turns 0-63 (element i with i + 32) at YaRN's frequencies, and
    scales every turned pair's length by the attention factor."""
    arch = arch_of(os.path.join(ROOT, "benchmark", "models",
                                "laguna_xs2.json"))
    x = jax.random.normal(jax.random.key(3), (1, 40, 2, 128))
    pos = jnp.arange(40)
    got = full_rotary(arch, x, pos)
    np.testing.assert_array_equal(got[..., 64:], x[..., 64:])
    factor = arch.full_attention_attention_factor
    pair = lambda y: jnp.hypot(y[..., :32], y[..., 32:64])
    np.testing.assert_allclose(pair(got), factor * pair(x), rtol=1e-5)
    np.testing.assert_allclose(got[:, 0, :, :64], factor * x[:, 0, :, :64],
                               rtol=1e-6)
    freq = tr.yarn_inv_freq(64, 5e5, 64, 4096, 64, 1)
    angle = 7.0 * freq
    a, b = np.asarray(x[0, 7, 0, :32]), np.asarray(x[0, 7, 0, 32:64])
    np.testing.assert_allclose(
        got[0, 7, 0, :32], factor * (a * np.cos(angle) - b * np.sin(angle)),
        rtol=1e-4, atol=1e-5)
    # the reference turns the same way, to a unit in the last place of
    # its frequencies (numpy's power against XLA's) times angles up to 39
    np.testing.assert_allclose(
        got[0], ref._turn(x[0], "full_attention", FULL), atol=1e-5)
    np.testing.assert_allclose(
        tr.rotary(x, pos, 1e4)[0],
        ref._turn(x[0], "sliding_attention", FULL), atol=1e-5)


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------

def _pair(block=8, **kw):
    """(program model, reference model) under the same keys."""
    m = {**TINY, **kw}
    return (tr.TransformerLM(vocab_size=m["vocab_held"],
                             arch=WindowGQAArch.from_dict(m),
                             block_size=block),
            ref.build_model({"model": m}))


@pytest.mark.parametrize("block", [8, 16, None])
def test_program_agrees_with_the_plain_reference(block):
    """The same initial values from the same key (the two trees are laid
    out alike), the same logits and the same gradient of every leaf.
    Tolerances: float32 at ``highest`` on both sides, sums in another
    order (blocks of queries against the keys from the window's first
    block, against blocks of 12 queries or one [T, T] array a head): 2e-6
    absolute on logits of order 0.5, 5e-5 of a leaf's largest gradient
    entry.  The selection bias only selects: its gradient is zero on both
    sides."""
    prog, plain = _pair(block)
    tokens = jax.random.randint(jax.random.key(1), (2, 40), 1,
                                TINY["vocab_held"])
    with jax.default_matmul_precision("highest"):
        p = jax.jit(prog.init)(jax.random.key(0), tokens)["params"]
        q = jax.jit(plain.init)(jax.random.key(0), tokens)["params"]
        assert jax.tree.structure(p) == jax.tree.structure(q)
        for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(q)):
            np.testing.assert_array_equal(a, b)

        def loss(model, params):
            logits = model.apply({"params": params}, tokens, train=True)
            return jnp.mean(jnp.square(logits)), logits
        (lp, logits_p), gp = jax.jit(jax.value_and_grad(
            functools.partial(loss, prog), has_aux=True))(p)
        (lq, logits_q), gq = jax.jit(jax.value_and_grad(
            functools.partial(loss, plain), has_aux=True))(p)
    np.testing.assert_allclose(logits_p, logits_q, atol=2e-6)
    np.testing.assert_allclose(lp, lq, rtol=1e-6)
    biases = 0
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(gp),
                            jax.tree.leaves(gq)):
        name = jax.tree_util.keystr(path)
        if "select_bias" in name:
            biases += 1
            assert not np.any(np.asarray(a)) and not np.any(np.asarray(b))
            continue
        scale = float(jnp.max(jnp.abs(b)))
        assert scale > 0, name
        assert float(jnp.max(jnp.abs(a - b))) / scale < 5e-5, name
    assert biases == 4


def test_the_layers_are_of_their_kinds():
    """Full layers with 4 query heads, window layers with 6; the first
    block dense, the rest expert blocks with a shared expert; a window
    layer's result is the same whatever happens more than 12 keys before
    a row, a full layer's is not."""
    prog, _ = _pair()
    tokens = jnp.ones((1, 40), jnp.int32)
    shapes = jax.eval_shape(lambda: prog.init(jax.random.key(0), tokens))[
        "params"]
    d, hd = TINY["hidden_size"], TINY["head_dim"]
    for i, heads in enumerate([4, 6, 6, 6, 4]):
        assert shapes[f"layer_{i}"]["attn"]["q"]["kernel"].shape == (
            d, heads * hd)
    assert set(shapes["layer_0"]) == {"attn_norm", "attn", "ffn_norm", "mlp"}
    assert set(shapes["layer_1"]["moe"]) == {
        "router", "select_bias", "experts_gate", "experts_up",
        "experts_down", "shared"}
    arch = WindowGQAArch.from_dict(TINY)
    assert arch.first_k_dense_replace == 1
    assert [arch.window(i) for i in range(5)] == [None, 12, 12, 12, None]
    x = jax.random.normal(jax.random.key(2), (1, 40, d))
    x2 = x.at[:, :10].set(jax.random.normal(jax.random.key(3), (1, 10, d)))
    for layer, same in ((1, True), (4, False)):
        attn = WindowAttention(arch, layer, block_size=8)
        params = attn.init(jax.random.key(0), x, jnp.arange(40))
        a = attn.apply(params, x, jnp.arange(40))
        b = attn.apply(params, x2, jnp.arange(40))
        assert bool(jnp.array_equal(a[:, 10 + 12 - 1:],
                                    b[:, 10 + 12 - 1:])) is same


@pytest.mark.parametrize("keys,match", [
    ({"gating": False}, "gating"),
    ({"rope_parameters": {"full_attention": {"rope_type": "linear"}}},
     "rotary"),
    ({"mlp_layer_types": ["sparse", "dense"] * 20}, "dense"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings")])
def test_what_is_not_built_is_refused_by_name(keys, match):
    with pytest.raises(NotImplementedError, match=match):
        WindowGQAArch.from_dict({**TINY, **keys})


def test_the_thirty_two_shares_add_up_to_the_uncut_layer():
    """Thirty-two chips of 8 of 256 experts each: the routed parts of
    their results, with the shared expert (every chip's) counted once, add
    up to the uncut layer's result (the plain reference holding all of
    them)."""
    m = ref._Frozen({**TINY, "num_experts": 256, "num_experts_per_tok": 8,
                     "experts_held": 256, "first_held": 0,
                     "initializer_range": 0.2})
    x = jax.random.normal(jax.random.key(7), (2, 10, m["hidden_size"]))
    whole = ref._Experts(m)
    with jax.default_matmul_precision("highest"):
        wp = whole.init(jax.random.key(0), x)["params"]
        want = whole.apply({"params": wp}, x)
        shared = ref._GatedMLP(m["shared_expert_intermediate_size"], 0.2)
        alone = shared.apply({"params": wp["shared"]}, x)
        total = alone
        parts = []
        for chip in range(32):
            lo = 8 * chip
            layer = HeldExpertMoE(
                256, 8, lo, 8, m["moe_intermediate_size"], n_shared=1,
                scale=m["moe_routed_scaling_factor"], tile=8)
            share = {**wp, **{k: wp[k][lo:lo + 8] for k in (
                "experts_gate", "experts_up", "experts_down")}}
            parts.append(layer.apply({"params": share}, x))
            total = total + parts[-1] - alone
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-6)
    # no share is the whole, and the routed parts of the chips differ
    routed = [part - alone for part in parts]
    busy = [r for r in routed if float(jnp.max(jnp.abs(r))) > 1e-3]
    assert len(busy) > 16
    assert float(jnp.max(jnp.abs(want - parts[0]))) > 1e-3
    assert float(jnp.max(jnp.abs(busy[0] - busy[1]))) > 1e-3


# ---------------------------------------------------------------------------
# the counters, the kernels a step holds, the cell's size
# ---------------------------------------------------------------------------

def _on_tpu():
    return mock.patch.object(jax, "default_backend", lambda: "tpu")


def _wide(**kw):
    """The tiny configuration at heads of 128 the kernels admit."""
    return WindowGQAArch.from_dict({**TINY, "head_dim": 128, **kw})


@pytest.mark.parametrize("where,t,fused", [
    ("cpu", 512, 0.0), ("tpu", 520, 0.0), ("tpu", 512, 1.0),
    ("tpu", 8192, 1.0)], ids=["cpu", "ragged_length", "admitted", "cell"])
def test_window_attention_counts_its_tiles(where, t, fused):
    """``attn_stats/calls`` [1, fused] and ``window_stats/tiles`` [causal,
    visited] x sequences x query heads at the blocks of the path that
    takes the core: 31 of 136 a head at 8,192 with the kernels' blocks of
    512 (a window of 512 here), the XLA blocks of 128 elsewhere."""
    arch = _wide(sliding_window=512)
    layer = WindowAttention(arch, 1, block_size=128)
    x = jax.random.normal(jax.random.key(1), (2, t, arch.hidden_size))
    params = {"params": layer.init(jax.random.key(0), x[:, :8],
                                   jnp.arange(8))["params"]}
    stand_in = mock.Mock(side_effect=lambda q, k, v, *a, **kw: jnp.zeros(
        q.shape, jnp.float32))
    with mock.patch.object(jax, "default_backend", lambda: where), \
            mock.patch.object(fa, "fused_causal_attention", stand_in):
        _, sown = layer.apply(params, x, jnp.arange(t),
                              mutable=["attn_stats", "window_stats"])
    calls, = jax.tree.leaves(sown["attn_stats"])
    tiles, = jax.tree.leaves(sown["window_stats"])
    assert calls.tolist() == [1.0, fused]
    want = tr.window_tiles(t, fa.BLOCK if fused else 128, 512)
    assert tiles.tolist() == [2 * 6 * n for n in want]
    if t == 8192:
        assert want == (136, 31)
    if fused:
        (q, k, *_), kw = stand_in.call_args
        assert kw["window"] == 512 and q.shape[2] == 6 and k.shape[2] == 2


def _kernel_calls(jaxpr):
    found = []

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(eqn.params["name"])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jaxpr.jaxpr)
    return found


def test_a_step_runs_each_forward_kernel_once():
    """On a (mocked) TPU the window layers take the window kernels and
    the full layers the grouped ones, and the block's checkpoint keeps
    what the core names: one forward and one backward kernel a layer in a
    training step's gradient."""
    arch = _wide()
    model = tr.TransformerLM(vocab_size=TINY["vocab_held"], arch=arch,
                             block_size=128)
    tokens = jnp.ones((1, 512), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0), tokens[:, :8])["params"])
    with _on_tpu():
        found = _kernel_calls(jax.make_jaxpr(jax.grad(
            lambda p: jnp.sum(model.apply({"params": p}, tokens,
                                          train=True))))(params))
    assert sorted(found) == sorted(
        ["window_attention_forward", "window_attention_backward"] * 3
        + ["selected_attention_forward", "selected_attention_backward"] * 2)


def test_the_built_tree_has_the_files_parameter_count():
    arch = arch_of(os.path.join(ROOT, FULL["cli"]["model_config"]))
    assert isinstance(arch, WindowGQAArch)
    model = tr.TransformerLM(vocab_size=arch.vocab_held, arch=arch)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 16), jnp.int32)))["params"]
    count = sum(int(np.prod(v.shape)) for v in jax.tree.leaves(shapes))
    assert count == FULL["model"]["parameters"] == 389_045_248
    layers = [sum(int(np.prod(v.shape))
                  for v in jax.tree.leaves(shapes[f"layer_{i}"]))
              for i in range(5)]
    assert layers == [79_695_872] + [66_588_928] * 3 + [58_200_320]
    assert [arch.layer_types[i] for i in range(5)] == [
        "full_attention"] + ["sliding_attention"] * 3 + ["full_attention"]
    # the published keys stand in the file as the catalog has them
    for key in FULL["reduced"]:
        assert FULL[key] != FULL["published"][key]
        assert FULL["model"][key] in (FULL["published"][key], FULL[key])
    assert len(FULL["layer_types"]) == FULL["published"][
        "num_hidden_layers"] == 40


def test_required_macs_are_a_hand_count():
    per_token = ref.forward_macs_per_sample(FULL, (8192,)) / 8192
    d, hd = 2048, 128
    causal, window = 8192 * 8193 // 2, 4_063_488
    assert wa.window_pairs(8192, 512) == window
    assert window / causal == pytest.approx(0.1211, abs=1e-4)

    def attention(heads, pairs):
        return d * hd * (2 * heads + 16) + heads * 2 * hd * pairs / 8192
    experts = 2048 * 256 + 3 * 2048 * 512 + 8 * 8 / 256 * 3 * 2048 * 512
    want = (attention(48, causal) + 3 * 2048 * 8192
            + 3 * (attention(64, window) + experts)
            + attention(48, causal) + experts + 2048 * 12544)
    assert per_token == pytest.approx(want)
    # 2 silos x 2 steps of 8,192 tokens, three passes: 76.8 TFLOP a round
    assert 3 * 2 * per_token * 8192 * 4 == pytest.approx(7.6848e13,
                                                         rel=1e-4)


# ---------------------------------------------------------------------------
# the CLI: the arch the file names, the counters, GLM's and Keye's trees
# ---------------------------------------------------------------------------

def test_model_config_trains_through_the_wave_engine(tmp_path):
    from fedml_tpu.experiments.main import main
    data_dir = str(tmp_path / "shards")
    write_token_shards(token_shard_arrays(
        11, silos=4, sequences=3, seq_len=32, vocab=100, doc_median=10),
        data_dir)
    run_dir = str(tmp_path / "run")
    main(["--algo", "cross_device", "--model", "transformer",
          "--model_config", os.path.join(MODELS, "laguna_xs2.json"),
          "--dataset", "token_shards", "--data_dir", data_dir,
          "--client_num_in_total", "4", "--client_num_per_round", "2",
          "--wave_size", "2", "--batch_size", "2", "--epochs", "1",
          "--client_optimizer", "sgd", "--lr", "0.05",
          "--attn_block_size", "8", "--comm_round", "2",
          "--frequency_of_the_test", "1", "--run_dir", run_dir,
          "--perf", "true", "--log_stdout", "false"])
    rows = [json.loads(line) for line in open(
        os.path.join(run_dir, "metrics.jsonl"))]
    losses = [r["train_loss"] for r in rows if "train_loss" in r]
    assert len(losses) == 2 and losses[1] < losses[0] < 5.0
    events = json.load(open(os.path.join(run_dir, "trace.json")))[
        "traceEvents"]
    dispatch = [e["args"] for e in events if e["name"] == "wave.dispatch"]
    assert len(dispatch) == 2
    causal, visited = tr.window_tiles(32, 8, TINY["sliding_window"])
    for args in dispatch:
        # 2 silos x 2 steps of 2 sequences, 5 layers of which 3 window
        steps = args["attn_calls"] / 5
        assert steps == 4 and args["attn_calls_fused"] == 0
        assert args["attn_tiles_causal"] == steps * 3 * 2 * 6 * causal
        assert args["attn_tiles_visited"] == steps * 3 * 2 * 6 * visited
        assert args["attn_pairs_causal"] == 0
        assert args["expert_assignments"] == \
            TINY["num_experts_per_tok"] * args["tokens"]
        assert 0 < args["expert_assignments_held"] < args[
            "expert_assignments"]


def test_a_model_without_a_window_counts_no_tiles(cli_run):
    """`wave.dispatch` always carries the two counts (a reader's data file
    names them whatever the model): 0 and 0 on the logistic regression of
    `tests/conftest.py`'s run."""
    dispatch = [e["args"] for e in cli_run["events"]
                if e["name"] == "wave.dispatch"]
    assert dispatch
    for args in dispatch:
        assert args["attn_tiles_causal"] == 0
        assert args["attn_tiles_visited"] == 0


@pytest.mark.parametrize("name,reference", [
    ("glm47_flash", "glm47_flash"), ("keye_vl2_30b_a3b", "keye_vl2_30b_a3b")])
def test_other_archs_pass_over_the_layer_index(name, reference):
    """GLM's and Keye's attention is the same module whatever layer index
    it is given, and their trees are still their plain references' leaf
    for leaf (neither reference reads a layer's index)."""
    import importlib
    plain_ref = importlib.import_module(f"benchmark.configs.{reference}")
    tiny = json.load(open(os.path.join(MODELS, name + ".json")))
    arch = arch_of(os.path.join(MODELS, name + ".json"))
    assert arch.attention(None, 8, 0) == arch.attention(None, 8, 3)
    model = tr.TransformerLM(vocab_size=arch.vocab_held, arch=arch,
                             block_size=8)
    plain = plain_ref.build_model({"model": tiny})
    tokens = jnp.ones((1, 16), jnp.int32)
    p = jax.jit(model.init)(jax.random.key(0), tokens)["params"]
    q = jax.jit(plain.init)(jax.random.key(0), tokens)["params"]
    assert jax.tree.structure(p) == jax.tree.structure(q)
    for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(q)):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# what the benchmark's reader finds the window kernels by
# ---------------------------------------------------------------------------

def _laguna_model():
    return wa.with_layers(FULL["model"], FULL["cli"]["batch_size"])


@pytest.mark.parametrize("kernel", ["forward", "backward"])
def test_window_first_result_is_what_the_reader_groups_by(kernel):
    """The window kernels' first result is the 64 query heads' ``[B,
    heads, T, width]``, and the reader finds them by their name; the full
    layers' grouped kernels (48 heads) and Keye's selected ones (32) fall
    in no window group."""
    q = jax.ShapeDtypeStruct((1, 64, 8192, 128), jnp.float32)
    k = jax.ShapeDtypeStruct((1, 8, 8192, 128), jnp.float32)
    row = jax.ShapeDtypeStruct((1, 64, 1, 8192), jnp.float32)
    if kernel == "forward":
        first = jax.eval_shape(lambda q, k: fa._forward(
            q, k, k, None, fa.BLOCK, False, 512), q, k)[0]
    else:
        first = jax.eval_shape(lambda q, k, row: fa._backward(
            q, k, k, row, row, q, None, fa.BLOCK, False, 512), q, k, row)[0]
    assert first.shape == (1, 64, 8192, 128)
    m = _laguna_model()
    line = (f"%window_attention_{kernel}.4 = (f32[1,64,8192,128]"
            f"{{3,2,1,0:T(8,128)}}, f32[1,64,1,8192]{{3,2,1,0:T(1,128)}}) "
            f"custom-call(%q, %k, %v), custom_call_target="
            f"\"tpu_custom_call\"")
    assert wa.group_of(line, m) == "window"
    full = line.replace("window_attention", "selected_attention").replace(
        "[1,64,", "[1,48,")
    assert wa.group_of(full, m) is None
