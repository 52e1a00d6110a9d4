"""The latent-attention expert model on the normal path (ISSUE 37):
`models/transformer.py` under ``arch`` and `models/moe.HeldExpertMoE`
against the plain reference `benchmark/configs/glm47_flash.py` at a tiny
size on the CPU (seeded weights, products at ``highest``), the router and
the attention on their own, the share test of the expert cut, and
``--model_config`` + ``token_shards`` through `main()`.
"""

import dataclasses
import json
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.configs import glm47_flash as ref
from benchmark.token_shards import token_shard_arrays, write_token_shards
from fedml_tpu.models.moe import (HeldExpertMoE, held_expert_sum,
                                  plan_held_tiles, route_sigmoid_topk)
from fedml_tpu.models.transformer import (LatentAttention, LatentMoEArch,
                                          TransformerLM,
                                          causal_blocked_attention, rotary)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = json.load(open(os.path.join(
    ROOT, "benchmark", "tests", "tiny", "models", "glm47_flash.json")))


def _arch(**kw):
    return LatentMoEArch.from_dict({**TINY, **kw})


def _pair(**kw):
    """(program model, reference model, arch) under the same keys."""
    m = {**TINY, **kw}
    return (TransformerLM(vocab_size=m["vocab_held"],
                          arch=LatentMoEArch.from_dict(m), block_size=8),
            ref.build_model({"model": m}))


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.key(1), (2, 24), 1,
                              TINY["vocab_held"])


def _loss_and_grads(model, params, tokens):
    def f(p):
        logits, sown = model.apply({"params": p}, tokens, train=True,
                                   mutable=["losses", "moe_stats"])
        extra = sum(jax.tree.leaves(sown.get("losses", {})), 0.0)
        return jnp.mean(jnp.square(logits)) + extra, (logits, extra)
    (loss, (logits, extra)), grads = jax.value_and_grad(f, has_aux=True)(
        params)
    return loss, logits, extra, grads


# dense block alone, expert block alone, both with the MTP loss term
CASES = {
    "dense_block": dict(num_hidden_layers=1, first_k_dense_replace=1,
                        num_nextn_predict_layers=0),
    "expert_block": dict(num_hidden_layers=1, first_k_dense_replace=0,
                         num_nextn_predict_layers=0),
    "mtp_loss": dict(num_hidden_layers=2, first_k_dense_replace=1,
                     num_nextn_predict_layers=1),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_program_agrees_with_the_plain_reference(case, tokens):
    """The same initial values from the same key (the two trees are laid
    out alike), the same logits, the same sown loss and the same
    gradient of every leaf."""
    prog, plain = _pair(**CASES[case])
    with jax.default_matmul_precision("highest"):
        p = prog.init(jax.random.key(0), tokens)["params"]
        q = plain.init(jax.random.key(0), tokens)["params"]
        assert jax.tree.structure(p) == jax.tree.structure(q)
        for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(q)):
            np.testing.assert_array_equal(a, b)
        lp, logits_p, extra_p, gp = _loss_and_grads(prog, p, tokens)
        lq, logits_q, extra_q, gq = _loss_and_grads(plain, p, tokens)
    np.testing.assert_allclose(logits_p, logits_q, atol=2e-6)
    if case == "mtp_loss":
        assert float(extra_p) > 0.5      # 0.3 x a cross-entropy near ln V
    np.testing.assert_allclose(extra_p, extra_q, rtol=1e-6)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(gp),
                            jax.tree.leaves(gq)):
        scale = float(jnp.max(jnp.abs(b))) + 1e-12
        assert float(jnp.max(jnp.abs(a - b))) / scale < 5e-5, \
            jax.tree_util.keystr(path)
    bias = [g for path, g in jax.tree_util.tree_leaves_with_path(gp)
            if "select_bias" in jax.tree_util.keystr(path)]
    assert all(not np.any(np.asarray(g)) for g in bias)


def test_required_macs_count_a_held_experts_share():
    cfg = {"model": {**TINY, "num_nextn_predict_layers": 0}}
    more = {"model": {**cfg["model"], "experts_held": 4}}
    d, f = TINY["hidden_size"], TINY["moe_intermediate_size"]
    gap = (ref.forward_macs_per_sample(more, (24,))
           - ref.forward_macs_per_sample(cfg, (24,)))
    # two more held experts of eight, two chosen a token: 0.5 expert more
    assert gap == pytest.approx(24 * 0.5 * 3 * d * f)
    full = json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                       "glm47_flash.json")))
    per_token = ref.forward_macs_per_sample(full, (8192,)) / 8192
    assert 3.1e8 < per_token < 3.3e8


# ---------------------------------------------------------------------------
# the router
# ---------------------------------------------------------------------------

def test_the_bias_changes_the_choice_and_not_the_weights():
    logits = jnp.asarray([[2.0, 1.0, 0.5, -1.0, 0.0, -2.0]])
    chosen, w = route_sigmoid_topk(logits, jnp.zeros(6), 2, 1.8)
    assert set(np.asarray(chosen[0])) == {0, 1}
    assert float(jnp.sum(w)) == pytest.approx(1.8, rel=1e-6)
    bias = jnp.asarray([0.0, 0.0, 0.0, 5.0, 0.0, 0.0])
    chosen_b, w_b = route_sigmoid_topk(logits, bias, 2, 1.8)
    assert set(np.asarray(chosen_b[0])) == {0, 3}
    s = jax.nn.sigmoid(logits[0])
    want = 1.8 * s[jnp.asarray([0, 3])] / (s[0] + s[3])
    got = {int(e): float(x) for e, x in zip(chosen_b[0], w_b[0])}
    assert got[0] == pytest.approx(float(want[0]), rel=1e-6)
    assert got[3] == pytest.approx(float(want[1]), rel=1e-6)
    grad = jax.grad(lambda b: jnp.sum(
        route_sigmoid_topk(logits, b, 2, 1.8)[1] ** 2))(bias)
    assert not np.any(np.asarray(grad))


def test_no_token_is_dropped_when_every_token_takes_one_expert():
    """All 40 tokens alike: all choose the same two experts, one of them
    held, and every one gets that expert's answer."""
    layer = HeldExpertMoE(experts_total=8, experts_held=2, first_held=2,
                            top_k=2, d_ff=12, n_shared=0, scale=1.8, tile=8)
    x = jnp.tile(jax.random.normal(jax.random.key(3), (1, 1, 16)),
                 (4, 10, 1))
    with jax.default_matmul_precision("highest"):
        params = layer.init(jax.random.key(0), x)["params"]
        router = np.zeros((16, 8), np.float32)
        params = {**params, "router": jnp.asarray(router),
                  "select_bias": jnp.asarray(
                      [0, 0, 9.0, 0, 0, 8.0, 0, 0], jnp.float32)}
        y, sown = layer.apply({"params": params}, x, mutable=["moe_stats"])
    counts = np.asarray(sown["moe_stats"]["counts"][0])
    # tokens, assignments, held assignments, max load, mean load
    np.testing.assert_array_equal(counts, [40, 80, 40, 40, 20])
    e = 0       # expert 2 is the first held
    h = jax.nn.silu(x @ params["experts_gate"][e]) \
        * (x @ params["experts_up"][e])
    want = 1.8 * 0.5 * (h @ params["experts_down"][e])   # s = 0.5 both
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-7)


def test_grouped_product_skips_nothing_and_computes_only_active_tiles():
    chosen = jnp.asarray([[0, 5], [1, 0], [7, 6], [1, 2]], jnp.int32)
    row_choice, tile_expert, n_active, counts = plan_held_tiles(
        chosen, first_held=0, held=2, tile=2)
    np.testing.assert_array_equal(counts, [2, 2])
    assert int(n_active) == 2
    rows = np.asarray(row_choice)
    assert sorted(rows[:4] // 2) == [0, 1, 1, 3]       # the rows' tokens
    np.testing.assert_array_equal(tile_expert[:2], [0, 1])
    # every held choice has one row and no other choice has any; a
    # padding row names the choice past the last, so the token past the last
    np.testing.assert_array_equal(
        np.sort(rows[rows < chosen.size]),
        np.flatnonzero(np.asarray(chosen).reshape(-1) < 2))
    assert set(rows[rows >= chosen.size]) == {chosen.size}


# the layer's sum against every held expert over every token, masked by
# the choice: 24 tokens, top-3 of 12 experts, 4..6 held, tiles of 4 rows
N_TOK, TOP, EXPERTS, FIRST, HELD, TILE = 24, 3, 12, 4, 3, 4
NOT_HELD = [e for e in range(EXPERTS) if not FIRST <= e < FIRST + HELD]
SUM_CASES = {
    # token 0 takes all three held experts, tokens 1 and 2 two each
    "several_held_choices": (
        {0: [4, 5, 6], 1: [4, 6], 2: [5, 6], 3: [5]},
        lambda counts: int(counts[2]) == 3),
    "held_expert_without_token": (
        {t: [4] for t in range(5)} | {t: [6] for t in range(5, 8)},
        lambda counts: int(counts[1]) == 0),
    "run_over_several_tiles": (
        {t: [4] for t in range(11)} | {11: [5]},
        lambda counts: int(counts[0]) > 2 * TILE),
    # the last active tile holds one row and three of padding
    "last_tile_mostly_padding": (
        {t: [6] for t in range(TILE + 1)} | {7: [4, 5]},
        lambda counts: int(counts[2]) % TILE == 1),
    "nothing_held": ({}, lambda counts: int(counts.sum()) == 0),
}


def _route(forced):
    rng = np.random.default_rng(0)
    chosen = np.stack([rng.choice(NOT_HELD, TOP, replace=False)
                       for _ in range(N_TOK)])
    for t, experts in forced.items():
        chosen[t, :len(experts)] = experts
    return jnp.asarray(chosen, jnp.int32)


def _dense_held_sum(xt, chosen, w, wg, wu, wd):
    """Every held expert over every token, weighted by the router's weight
    where the token chose it and by 0 elsewhere."""
    y = jnp.zeros_like(xt)
    for e in range(HELD):
        m = jnp.sum(jnp.where(chosen == FIRST + e, w, 0.0), axis=1)
        h = jax.nn.silu(xt @ wg[e]) * (xt @ wu[e])
        y = y + m[:, None] * (h @ wd[e])
    return y


@pytest.mark.parametrize("case", sorted(SUM_CASES))
def test_held_sum_and_its_gradients_are_the_dense_masked_sum(case):
    forced, holds = SUM_CASES[case]
    chosen = _route(forced)
    keys = jax.random.split(jax.random.key(11), 6)
    d, f = 16, 8
    xt = jax.random.normal(keys[0], (N_TOK, d))
    w = jax.random.uniform(keys[1], (N_TOK, TOP), minval=0.1)
    wg, wu = (0.3 * jax.random.normal(k, (HELD, d, f)) for k in keys[2:4])
    wd = 0.3 * jax.random.normal(keys[4], (HELD, f, d))
    cot = jax.random.normal(keys[5], (N_TOK, d))
    _, tile_expert, n_active, counts = plan_held_tiles(
        chosen, FIRST, HELD, TILE)
    assert holds(np.asarray(counts)), case
    assert int(n_active) < tile_expert.shape[0]
    assert int(n_active) == sum(-(-int(c) // TILE) for c in counts)

    def program(*a):
        y, _ = held_expert_sum(a[0], chosen, a[1], *a[2:], FIRST, TILE)
        return y

    def dense(*a):
        return _dense_held_sum(a[0], chosen, *a[1:])

    args = (xt, w, wg, wu, wd)
    with jax.default_matmul_precision("highest"):
        got, want = program(*args), dense(*args)
        grads = [jax.grad(lambda *a: jnp.sum(fn(*a) * cot),
                          argnums=tuple(range(5)))(*args)
                 for fn in (program, dense)]
    scale = float(jnp.max(jnp.abs(want))) + 1e-12
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6 * scale)
    if case == "nothing_held":
        assert not np.any(np.asarray(got))
    else:
        assert scale > 1e-2
    for name, a, b in zip(("x", "w", "gate", "up", "down"), *grads):
        scale = float(jnp.max(jnp.abs(b))) + 1e-12
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6 * scale,
                                   err_msg=name)


def test_no_array_of_the_layouts_length_and_the_models_width_is_compiled():
    """The gradient of a Keye-shaped layer (1,024 tokens, top-8 of 128, 8
    held, tiles of 128 rows) holds no array of the worst-case layout's
    rows (``L = 1,024 x 8 + 8 x 128``, or ``L + 1``) or of every choice's
    (``N x k``) at the model's width: each tile is summed into the
    ``[N + 1, d]`` tokens."""
    n, k, held, tile, d = 1024, 8, 8, 128, 256
    layer = HeldExpertMoE(experts_total=128, experts_held=held, first_held=0,
                          top_k=k, d_ff=64, n_shared=0, tile=tile,
                          router="softmax")
    x = jax.ShapeDtypeStruct((1, n, d), jnp.float32)
    params = jax.eval_shape(layer.init, jax.random.key(0), x)["params"]

    def loss(p, x):
        return jnp.sum(layer.apply({"params": p}, x) ** 2)

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, x).compile().as_text()
    rows = set()
    for dims in re.findall(r"\[([0-9]+(?:,[0-9]+)+)\]", hlo):
        dims = [int(v) for v in dims.split(",")]
        if dims[-1] == d:
            rows.add(math.prod(dims[:-1]))
    length = n * k + held * tile
    assert n + 1 in rows            # the tokens the tiles are summed into
    assert not rows & {length, length + 1, n * k}, sorted(rows)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def test_blocked_attention_is_the_unblocked_one():
    q, k, v = (jax.random.normal(jax.random.key(i), (2, 24, 3, d))
               for i, d in ((0, 20), (1, 20), (2, 16)))
    with jax.default_matmul_precision("highest"):
        whole = causal_blocked_attention(q, k, v, None)
        for block in (8, 5, 24, 64):
            np.testing.assert_allclose(
                causal_blocked_attention(q, k, v, block), whole, atol=2e-6)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(20.0)
        scores = jnp.where(np.tril(np.ones((24, 24), bool)), scores,
                           -np.inf)
        dense = jnp.einsum("bhqk,bkhd->bqhd",
                           jax.nn.softmax(scores, axis=-1), v)
    np.testing.assert_allclose(whole, dense, atol=2e-6)


def test_latent_attention_is_multi_head_attention_of_the_same_matrices():
    """Uncompressed: per head ``W_q = W_qa . W_qb`` (through the latent's
    norm), keys ``[c_kv W_kvb_nope, rope(x W_kva_rope)]``, values ``c_kv
    W_kvb_v``: ordinary multi-head attention with a shared rotary key."""
    a = _arch()
    x = jax.random.normal(jax.random.key(5), (2, 12, a.hidden_size))
    pos = jnp.arange(12)
    layer = LatentAttention(a)
    with jax.default_matmul_precision("highest"):
        p = layer.init(jax.random.key(0), x, pos)["params"]
        got = layer.apply({"params": p}, x, pos)

        def norm(v, scale):
            return v / jnp.sqrt(jnp.mean(v * v, -1, keepdims=True)
                                + a.rms_norm_eps) * scale
        h, nope, rope, dv = (a.num_attention_heads, a.qk_nope_head_dim,
                             a.qk_rope_head_dim, a.v_head_dim)
        c_q = norm(x @ p["q_a"]["kernel"], p["q_norm"]["scale"])
        ckv = x @ p["kv_a"]["kernel"]
        c_kv = norm(ckv[..., :a.kv_lora_rank], p["kv_norm"]["scale"])
        k_rope = rotary(ckv[..., None, a.kv_lora_rank:], pos,
                        a.rope_theta)[:, :, 0]
        w_qb = p["q_b"]["kernel"].reshape(-1, h, nope + rope)
        w_kvb = p["kv_b"]["kernel"].reshape(-1, h, nope + dv)
        heads = []
        for i in range(h):
            q = c_q @ w_qb[:, i]
            q = jnp.concatenate([q[..., :nope], rotary(
                q[..., None, nope:], pos, a.rope_theta)[:, :, 0]], -1)
            k = jnp.concatenate([c_kv @ w_kvb[:, i, :nope], k_rope], -1)
            v = c_kv @ w_kvb[:, i, nope:]
            s = jnp.einsum("bqd,bkd->bqk", q, k) / np.sqrt(nope + rope)
            s = jnp.where(np.tril(np.ones((12, 12), bool)), s, -np.inf)
            heads.append(jax.nn.softmax(s, -1) @ v)
        want = jnp.concatenate(heads, -1) @ p["o"]["kernel"]
    np.testing.assert_allclose(got, want, atol=2e-6)


# ---------------------------------------------------------------------------
# the share test
# ---------------------------------------------------------------------------

def test_the_shares_routed_parts_and_the_shared_expert_once_are_the_layer():
    """Four chips of two experts each: the sum of their routed parts,
    with the shared expert counted once, is the uncut layer's result (the
    plain reference holding all eight)."""
    x = jax.random.normal(jax.random.key(7), (2, 10, TINY["hidden_size"]))
    whole_m = ref._Frozen({**TINY, "experts_held": 8, "first_held": 0,
                           "initializer_range": 0.2})
    whole = ref._Experts(whole_m)
    with jax.default_matmul_precision("highest"):
        wp = whole.init(jax.random.key(0), x)["params"]
        want = whole.apply({"params": wp}, x)
        shared = ref._GatedMLP(TINY["moe_intermediate_size"], 0.2).apply(
            {"params": wp["shared"]}, x)
        total = shared
        for chip in range(4):
            lo = 2 * chip
            layer = HeldExpertMoE(
                experts_total=8, experts_held=2, first_held=lo,
                top_k=TINY["num_experts_per_tok"],
                d_ff=TINY["moe_intermediate_size"], n_shared=1,
                scale=TINY["routed_scaling_factor"], tile=8)
            share = {**wp, **{k: wp[k][lo:lo + 2] for k in (
                "experts_gate", "experts_up", "experts_down")}}
            total = total + layer.apply({"params": share}, x) - shared
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-6)
    assert float(jnp.max(jnp.abs(want - shared))) > 1e-3


# ---------------------------------------------------------------------------
# the CLI
# ---------------------------------------------------------------------------

def test_model_config_and_token_shards_through_main(tmp_path):
    from fedml_tpu.experiments.config import ExperimentConfig
    from fedml_tpu.experiments.main import main
    fields = {f.name for f in dataclasses.fields(ExperimentConfig)}
    assert "model_config" in fields
    data_dir = str(tmp_path / "shards")
    write_token_shards(token_shard_arrays(
        11, silos=4, sequences=3, seq_len=32, vocab=TINY["vocab_held"],
        doc_median=10), data_dir)
    run_dir = str(tmp_path / "run")
    main(["--algo", "cross_device", "--model", "transformer",
          "--model_config", os.path.join(ROOT, "benchmark", "tests", "tiny",
                                         "models", "glm47_flash.json"),
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
    for args in dispatch:
        # 2 silos x 2 steps x 2 x 32 tokens less the padded half step,
        # one expert layer + the MTP module's (one position short)
        assert args["expert_assignments"] == 2 * args["tokens"]
        assert 0 < args["expert_assignments_held"] < args[
            "expert_assignments"]
        assert args["expert_load_max"] >= args["expert_load_mean"] > 0


def test_a_vocabulary_the_data_does_not_have_is_refused(tmp_path):
    from fedml_tpu.experiments.models import create_workload
    path = os.path.join(ROOT, "benchmark", "tests", "tiny", "models",
                        "glm47_flash.json")
    with pytest.raises(ValueError, match="vocabulary"):
        create_workload("transformer", "token_shards", 101, (32,),
                        model_config=path)
    with pytest.raises(ValueError, match="next-token"):
        create_workload("lr", "mnist", 10, (784,), model_config=path)
