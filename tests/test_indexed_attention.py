"""The indexed grouped-query expert model on the normal path (ISSUE 39):
`models/indexed_attention.py` under `TransformerLM`'s ``arch`` scaffolding
and `models/moe.HeldExpertMoE`'s softmax router against the plain
reference `benchmark/configs/keye_vl2_30b_a3b.py` at a tiny size on the
CPU (seeded weights, products at ``highest``): the whole model, the
selection, the three-axis rotary, grouped key heads and the router on
their own, the share test of the expert cut, the counters on
`wave.dispatch`, and that GLM's file still builds GLM's model.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.configs import keye_vl2_30b_a3b as ref
from benchmark.token_shards import token_shard_arrays, write_token_shards
from fedml_tpu.experiments.models import arch_of
from fedml_tpu.models.indexed_attention import (IndexedAttention,
                                                IndexedGQAArch,
                                                index_selection, topk_mask)
from fedml_tpu.models.moe import HeldExpertMoE, route_softmax_topk
from fedml_tpu.models.transformer import (LatentMoEArch, TransformerLM,
                                          causal_blocked_attention, rotary)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODELS = os.path.join(ROOT, "benchmark", "tests", "tiny", "models")
TINY_PATH = os.path.join(MODELS, "keye_vl2_30b_a3b.json")
TINY = json.load(open(TINY_PATH))


def _pair(block=8, **kw):
    """(program model, reference model) under the same keys."""
    m = {**TINY, **kw}
    return (TransformerLM(vocab_size=m["vocab_held"],
                          arch=IndexedGQAArch.from_dict(m),
                          block_size=block),
            ref.build_model({"model": m}))


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.key(1), (2, 40), 1,
                              TINY["vocab_held"])


def _loss_and_grads(model, params, tokens, positions=None):
    def f(p):
        logits = model.apply({"params": p}, tokens, train=True,
                             positions=positions)
        return jnp.mean(jnp.square(logits)), logits
    (loss, logits), grads = jax.value_and_grad(f, has_aux=True)(params)
    return loss, logits, grads


# text (one position for the three axes), and an image's patch grid: the
# height and width rows differ from the temporal one
POSITIONS = {
    "text": None,
    "image_grid": np.stack([np.repeat(np.arange(10), 4),
                            np.tile(np.repeat(np.arange(2), 2), 10) + 3,
                            np.tile(np.arange(2), 20) + 7]),
}


@pytest.mark.parametrize("case", sorted(POSITIONS))
def test_program_agrees_with_the_plain_reference(case, tokens):
    """The same initial values from the same key (the two trees are laid
    out alike), the same logits and the same gradient of every leaf; the
    indexer's leaves read zero on both sides.  Tolerances: float32 at
    ``highest`` on both sides, sums in another order (blocks of 8 queries
    against one [T, T] array a head): 2e-6 absolute on logits of order
    0.1, 5e-5 of a leaf's largest gradient entry."""
    prog, plain = _pair()
    positions = POSITIONS[case]
    if positions is not None:
        positions = jnp.asarray(positions)
    with jax.default_matmul_precision("highest"):
        p = prog.init(jax.random.key(0), tokens)["params"]
        q = plain.init(jax.random.key(0), tokens)["params"]
        assert jax.tree.structure(p) == jax.tree.structure(q)
        for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(q)):
            np.testing.assert_array_equal(a, b)
        lp, logits_p, gp = _loss_and_grads(prog, p, tokens, positions)
        lq, logits_q, gq = _loss_and_grads(plain, p, tokens, positions)
    np.testing.assert_allclose(logits_p, logits_q, atol=2e-6)
    np.testing.assert_allclose(lp, lq, rtol=1e-6)
    indexer = 0
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(gp),
                            jax.tree.leaves(gq)):
        name = jax.tree_util.keystr(path)
        if "idx_" in name:
            indexer += 1
            assert not np.any(np.asarray(a)) and not np.any(np.asarray(b))
            continue
        scale = float(jnp.max(jnp.abs(b)))
        assert scale > 0, name
        assert float(jnp.max(jnp.abs(a - b))) / scale < 5e-5, name
    assert indexer == 5 * TINY["num_hidden_layers"]


def test_unequal_position_rows_change_the_result(tokens):
    """The image grid above is no relabelling of the text positions: the
    three-axis rotary reads the height and width rows."""
    prog, _ = _pair()
    with jax.default_matmul_precision("highest"):
        p = prog.init(jax.random.key(0), tokens)["params"]
        text = prog.apply({"params": p}, tokens)
        grid = prog.apply({"params": p}, tokens,
                          positions=jnp.asarray(POSITIONS["image_grid"]))
        three = prog.apply({"params": p}, tokens, positions=jnp.broadcast_to(
            jnp.arange(40), (3, 40)))
    np.testing.assert_array_equal(text, three)
    assert float(jnp.max(jnp.abs(text - grid))) > 1e-3


def test_three_axis_rotary_against_the_reference():
    """Of 8 frequencies the first 2 turn by the temporal row, the next 3
    by the height, the last 3 by the width; one row and no sections is
    the one-axis rotary."""
    x = jax.random.normal(jax.random.key(2), (1, 40, 3, 16))
    pos = jnp.asarray(POSITIONS["image_grid"])
    got = rotary(x, pos, 1e7, (2, 3, 3))
    for h in range(3):
        np.testing.assert_allclose(
            got[0, :, h], ref._rotate(x[0, :, h], pos, 1e7, (2, 3, 3)),
            atol=1e-6)
    half = 8
    freq = 1e7 ** (-np.arange(half) / half)
    row = np.repeat(np.arange(3), (2, 3, 3))
    angle = np.asarray(pos)[row].T * freq                   # [T, half]
    a, b = np.asarray(x[0, :, 0, :half]), np.asarray(x[0, :, 0, half:])
    np.testing.assert_allclose(got[0, :, 0, :half],
                               a * np.cos(angle) - b * np.sin(angle),
                               atol=2e-5)
    np.testing.assert_array_equal(
        rotary(x, pos[0], 1e7),
        rotary(x, jnp.broadcast_to(pos[0], (3, 40)), 1e7, (2, 3, 3)))
    with pytest.raises(ValueError, match="add"):
        rotary(x, pos, 1e7, (2, 3, 2))


# ---------------------------------------------------------------------------
# the selection
# ---------------------------------------------------------------------------

def _top_k_reference(scores, k, valid):
    """`jax.lax.top_k` over the valid entries of each [Q, K] array, as
    the plain reference selects."""
    def one(s):
        _, keys = jax.lax.top_k(jnp.where(valid, s, -jnp.inf),
                                min(k, s.shape[-1]))
        return jnp.zeros(s.shape, bool).at[
            jnp.arange(s.shape[0])[:, None], keys].set(True) & valid
    return jax.vmap(one)(scores)


@pytest.mark.parametrize("case", ["random", "ties", "negative_and_zero",
                                  "fewer_than_k", "k_is_all"])
def test_topk_mask_is_top_k(case):
    t, k = 24, 6
    causal = np.tril(np.ones((t, t), bool))
    scores = jax.random.normal(jax.random.key(3), (2, t, t))
    if case == "ties":
        # a few distinct values: most rows' k-th largest is shared, and
        # the smaller key has to win as `top_k` has it.  (+ 0.0: no
        # -0.0, which `top_k` and the search put under +0.0 and the
        # reference's float compare does not; an index score is -0.0
        # only where every indexer head is silent)
        scores = jnp.round(scores * 2) / 2 + 0.0
    elif case == "negative_and_zero":
        scores = jnp.where(scores > 0.3, 0.0, scores - 1.0)
    elif case == "fewer_than_k":
        k = 30
    elif case == "k_is_all":
        k = t
    got = topk_mask(scores, k, causal)
    want = _top_k_reference(scores, k, causal)
    np.testing.assert_array_equal(got, want)
    # the plain reference takes `top_k`'s k-th value, not its indices
    np.testing.assert_array_equal(ref._select(scores[0], k), want[0])
    counts = np.asarray(jnp.sum(got, axis=-1))
    np.testing.assert_array_equal(
        counts, np.broadcast_to(np.minimum(np.arange(t) + 1, k), (2, t)))


@pytest.mark.parametrize("block,topk", [(8, 20), (16, 20), (40, 20),
                                        (8, 16), (7, 5), (8, 64)])
def test_selection_in_blocks_is_the_reference_selection(block, topk):
    """A block that ends under ``topk`` (nothing scored), one that
    straddles it, a ``topk`` that does not divide the block, a ragged
    last block, and a ``topk`` past the sequence: all the reference's
    `top_k` over the whole [T, T] array."""
    t, ih, d = 40, 3, 8
    q_i, k_i, w_i = (jax.random.normal(jax.random.key(i), s) for i, s in
                     ((4, (2, t, ih, d)), (5, (2, t, d)), (6, (2, t, ih))))
    with jax.default_matmul_precision("highest"):
        got = index_selection(q_i, k_i, w_i, topk, block)
        for b in range(2):
            index = sum(w_i[b, :, j, None] * jax.nn.relu(
                q_i[b, :, j] @ k_i[b].T) for j in range(ih))
            np.testing.assert_array_equal(got[b], ref._select(index, topk))
    assert got.shape == (2, t, t) and got.dtype == bool
    np.testing.assert_array_equal(
        jnp.sum(got, axis=-1)[0], np.minimum(np.arange(t) + 1, topk))


def test_the_selection_is_made_once_a_step():
    """The block's checkpoint keeps the selection by name: the search
    loop (the `while` that carries the scores mapped onto uint32) stands
    once a searched block and layer in the gradient's program, not again
    in the recomputed forward pass."""
    prog, _ = _pair(block=8)
    tokens = jnp.ones((1, 40), jnp.int32)
    p = jax.eval_shape(lambda: prog.init(jax.random.key(0), tokens))[
        "params"]

    def loss(p):
        return jnp.mean(prog.apply({"params": p}, tokens, train=True))
    text = jax.jit(jax.grad(loss)).lower(p).as_text()
    blocks_searched = sum(1 for lo in range(0, 40, 8)
                          if lo + 8 > TINY["sa_config"]["topk"])
    searches = [line for line in text.splitlines()
                if "stablehlo.while" in line and "xui32>" in line]
    assert len(searches) == blocks_searched * TINY["num_hidden_layers"]


# ---------------------------------------------------------------------------
# the core: a selection as an operand, key heads grouped
# ---------------------------------------------------------------------------

def test_grouped_key_heads_are_repeated_ones():
    q = jax.random.normal(jax.random.key(0), (2, 24, 6, 16))
    k, v = (jax.random.normal(jax.random.key(i), (2, 24, 2, 16))
            for i in (1, 2))
    selected = topk_mask(jax.random.normal(jax.random.key(3), (2, 24, 24)),
                         5, np.tril(np.ones((24, 24), bool)))
    with jax.default_matmul_precision("highest"):
        for chosen in (None, selected):
            want = causal_blocked_attention(
                q, jnp.repeat(k, 3, axis=2), jnp.repeat(v, 3, axis=2), 8,
                chosen)
            for block in (8, 5, None):
                np.testing.assert_allclose(
                    causal_blocked_attention(q, k, v, block, chosen), want,
                    atol=2e-6)
        # and the repeated, selected core is the dense formula
        s = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, 3, axis=2)) / 4.0
        s = jnp.where(selected[:, None], s, -jnp.inf)
        dense = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1),
                           jnp.repeat(v, 3, axis=2))
    np.testing.assert_allclose(want, dense, atol=2e-6)


def test_a_selection_of_everything_is_causal_attention():
    q, k, v = (jax.random.normal(jax.random.key(i), (1, 24, 4, 16))
               for i in range(3))
    everything = jnp.asarray(np.tril(np.ones((1, 24, 24), bool)))
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            causal_blocked_attention(q, k, v, 8, everything),
            causal_blocked_attention(q, k, v, 8), atol=1e-6)


def test_indexed_attention_counts_its_pairs():
    a = IndexedGQAArch.from_dict(TINY)
    layer = IndexedAttention(a, block_size=16)
    x = jax.random.normal(jax.random.key(5), (2, 40, a.hidden_size))
    pos = jnp.broadcast_to(jnp.arange(40), (3, 40))
    p = layer.init(jax.random.key(0), x, pos)["params"]
    _, sown = layer.apply({"params": p}, x, pos,
                          mutable=["attn_stats", "select_stats"])
    np.testing.assert_array_equal(sown["attn_stats"]["calls"][0], [1, 0])
    np.testing.assert_array_equal(
        sown["select_stats"]["pairs"][0],
        [2 * 40 * 41 // 2, 2 * ref.selected_pairs(40, 20)])


# ---------------------------------------------------------------------------
# the router and the share test
# ---------------------------------------------------------------------------

def test_softmax_router_against_the_reference():
    """Probabilities over all experts, the largest chosen, normalised
    over the chosen or not; the layer has no ``select_bias`` leaf."""
    logits = jnp.asarray([[2.0, 1.0, 0.5, -1.0, 0.0, -2.0]])
    p = jax.nn.softmax(logits[0])
    chosen, w = route_softmax_topk(logits, 2, normalize=True)
    assert list(np.asarray(chosen[0])) == [0, 1]
    np.testing.assert_allclose(w[0], p[:2] / (p[0] + p[1]), rtol=1e-6)
    _, raw = route_softmax_topk(logits, 2, normalize=False)
    np.testing.assert_allclose(raw[0], p[:2], rtol=1e-6)
    for normalize in (True, False):
        m = ref._Frozen({**TINY, "initializer_range": 0.2,
                         "norm_topk_prob": normalize})
        x = jax.random.normal(jax.random.key(8), (2, 10, m["hidden_size"]))
        plain = ref._Experts(m)
        layer = HeldExpertMoE(
            m["num_experts"], m["experts_held"], m["first_held"],
            m["num_experts_per_tok"], m["moe_intermediate_size"],
            n_shared=0, normalize=normalize, init_std=0.2, tile=8,
            router="softmax")
        with jax.default_matmul_precision("highest"):
            wp = plain.init(jax.random.key(0), x)["params"]
            assert set(wp) == {"router", "experts_gate", "experts_up",
                               "experts_down"}
            assert jax.tree.structure(wp) == jax.tree.structure(
                layer.init(jax.random.key(0), x)["params"])
            np.testing.assert_allclose(
                layer.apply({"params": wp}, x),
                plain.apply({"params": wp}, x), rtol=2e-5, atol=2e-6)
    with pytest.raises(ValueError, match="router"):
        HeldExpertMoE(8, 2, 0, 2, 8, router="hash").init(
            jax.random.key(0), jnp.zeros((1, 4, 8)))


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """Sixteen chips of 8 experts each: the sum of their parts is the
    uncut 128-expert layer's result (the plain reference holding all of
    them); nothing is shared, so nothing is counted once."""
    m = ref._Frozen({**TINY, "num_experts": 128, "num_experts_per_tok": 8,
                     "experts_held": 128, "first_held": 0,
                     "initializer_range": 0.2})
    x = jax.random.normal(jax.random.key(7), (2, 10, m["hidden_size"]))
    whole = ref._Experts(m)
    with jax.default_matmul_precision("highest"):
        wp = whole.init(jax.random.key(0), x)["params"]
        want = whole.apply({"params": wp}, x)
        total = jnp.zeros_like(want)
        parts = []
        for chip in range(16):
            lo = 8 * chip
            layer = HeldExpertMoE(
                128, 8, lo, 8, m["moe_intermediate_size"], n_shared=0,
                tile=8, router="softmax")
            share = {**wp, **{k: wp[k][lo:lo + 8] for k in (
                "experts_gate", "experts_up", "experts_down")}}
            parts.append(layer.apply({"params": share}, x))
            total = total + parts[-1]
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-6)
    # no share is the whole, and the shares differ
    assert float(jnp.max(jnp.abs(want - parts[0]))) > 1e-3
    assert float(jnp.max(jnp.abs(parts[0] - parts[1]))) > 1e-3


def test_required_macs_are_the_issues_count():
    full = json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                       "keye_vl2_30b_a3b.json")))
    per_token = ref.forward_macs_per_sample(full, (8192,)) / 8192
    layer = (18_874_368 + 2_260_992 + 262_144 + 8 * 8 / 128 * 4_718_592
             + 32 * 256 * ref.selected_pairs(8192, 2048) / 8192
             + 16 * 64 * 8193 / 2)
    assert per_token == pytest.approx(4 * layer + 18_992 * 2_048)
    assert 2.09e8 < per_token < 2.10e8
    assert ref.selected_pairs(8192, 2048) / (8192 * 8193 / 2) == \
        pytest.approx(0.4375, abs=1e-4)
    more = {"model": {**full["model"], "experts_held": 16}}
    assert (ref.forward_macs_per_sample(more, (8192,))
            - ref.forward_macs_per_sample(full, (8192,))) == pytest.approx(
        8192 * 4 * 0.5 * 4_718_592)


def test_the_built_tree_has_the_files_parameter_count():
    full = json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                       "keye_vl2_30b_a3b.json")))
    arch = arch_of(os.path.join(ROOT, full["cli"]["model_config"]))
    assert isinstance(arch, IndexedGQAArch)
    model = TransformerLM(vocab_size=arch.vocab_held, arch=arch)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 16), jnp.int32)))["params"]
    count = sum(int(np.prod(v.shape)) for v in jax.tree.leaves(shapes))
    assert count == full["model"]["parameters"] == 314_396_160
    layer = sum(int(np.prod(v.shape))
                for v in jax.tree.leaves(shapes["layer_0"]))
    assert layer == 59_150_720
    assert "select_bias" not in shapes["layer_0"]["moe"]
    # the published keys stand in the file as the catalog has them
    for key in full["reduced"]:
        assert full[key] != full["published"][key]
        assert full["model"][key] in (full["published"][key], full[key])


# ---------------------------------------------------------------------------
# the CLI: the arch the file names, the counters, GLM's file unchanged
# ---------------------------------------------------------------------------

def _run(tmp_path, name, config, extra=()):
    from fedml_tpu.experiments.main import main
    data_dir = str(tmp_path / f"shards_{name}")
    write_token_shards(token_shard_arrays(
        11, silos=4, sequences=3, seq_len=32, vocab=100, doc_median=10),
        data_dir)
    run_dir = str(tmp_path / f"run_{name}")
    main(["--algo", "cross_device", "--model", "transformer",
          "--model_config", os.path.join(MODELS, config),
          "--dataset", "token_shards", "--data_dir", data_dir,
          "--client_num_in_total", "4", "--client_num_per_round", "2",
          "--wave_size", "2", "--batch_size", "2", "--epochs", "1",
          "--client_optimizer", "sgd", "--lr", "0.05",
          "--attn_block_size", "8", "--comm_round", "2",
          "--frequency_of_the_test", "1", "--run_dir", run_dir,
          "--perf", "true", "--log_stdout", "false", *extra])
    rows = [json.loads(line) for line in open(
        os.path.join(run_dir, "metrics.jsonl"))]
    events = json.load(open(os.path.join(run_dir, "trace.json")))[
        "traceEvents"]
    ledger = [json.loads(line) for line in open(
        os.path.join(run_dir, "perf.jsonl"))]
    return (rows, [e["args"] for e in events
                   if e["name"] == "wave.dispatch"], ledger)


def test_model_config_trains_through_the_wave_engine(tmp_path):
    from fedml_tpu.experiments.config import ExperimentConfig
    assert len(dataclasses.fields(ExperimentConfig)) == 190
    rows, dispatch, _ = _run(tmp_path, "keye", "keye_vl2_30b_a3b.json")
    losses = [r["train_loss"] for r in rows if "train_loss" in r]
    assert len(losses) == 2 and losses[1] < losses[0] < 5.0
    assert len(dispatch) == 2
    layers, t, topk = TINY["num_hidden_layers"], 32, 20
    for args in dispatch:
        # 2 silos x 2 steps of 2 sequences (a padded half step counts its
        # pairs too: the model sees rows, not the mask)
        calls = args["attn_calls"]
        assert calls == layers * 4 and args["attn_calls_fused"] == 0
        assert args["attn_pairs_causal"] == calls * 2 * t * (t + 1) // 2
        assert args["attn_pairs_selected"] == \
            calls * 2 * ref.selected_pairs(t, topk)
        assert args["expert_assignments"] == \
            TINY["num_experts_per_tok"] * args["tokens"]
        assert 0 < args["expert_assignments_held"] < args[
            "expert_assignments"]


def test_a_model_without_an_indexer_counts_no_pairs(cli_run):
    """`wave.dispatch` always carries the two counts (a reader's data file
    names them whatever the model): 0 and 0 on the logistic regression
    of `tests/conftest.py`'s run."""
    dispatch = [e["args"] for e in cli_run["events"]
                if e["name"] == "wave.dispatch"]
    assert dispatch
    for args in dispatch:
        assert args["attn_pairs_causal"] == 0
        assert args["attn_pairs_selected"] == 0
        assert args["attn_calls"] == 0


def test_glm47s_file_still_builds_glm47s_model(tmp_path):
    """The arch by ``model_type`` (a file without the key is GLM's, the
    one arch there was), its tree leaf for leaf, and the tiny run's
    global bit for bit on this stack (jax 0.9.0 on the CPU): a change to
    the CRCs is a change to what GLM's cell computes, made on purpose.
    They last moved when the expert layer began to add each tile's rows
    into the tokens, which reorders float32 sums (the run's losses kept
    every printed digit)."""
    glm = os.path.join(ROOT, "benchmark", "models", "glm47_flash.json")
    assert isinstance(arch_of(glm), LatentMoEArch)
    tiny = arch_of(os.path.join(MODELS, "glm47_flash.json"))
    assert isinstance(tiny, LatentMoEArch)
    model = TransformerLM(vocab_size=tiny.vocab_held, arch=tiny)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 16), jnp.int32)))["params"]
    moe = shapes["layer_1"]["moe"]
    assert set(moe) == {"router", "select_bias", "experts_gate",
                        "experts_up", "experts_down", "shared"}
    assert set(shapes["layer_1"]["attn"]) == {"q_a", "q_norm", "q_b", "kv_a",
                                              "kv_norm", "kv_b", "o"}
    _, dispatch, ledger = _run(tmp_path, "glm", "glm47_flash.json")
    assert [line["global_crc"] for line in ledger] == [1831787180,
                                                       4167136790]
    for args in dispatch:
        assert args["attn_pairs_causal"] == 0 and args["attn_calls"] > 0
    with pytest.raises(ValueError, match="model_type"):
        bad = tmp_path / "bad.json"
        bad.write_text(json.dumps({**TINY, "model_type": "nope"}))
        arch_of(str(bad))
    with pytest.raises(NotImplementedError, match="mlp_only_layers"):
        IndexedGQAArch.from_dict({**TINY, "mlp_only_layers": [0]})


# ---------------------------------------------------------------------------
# the benchmark's reader of the two groups (benchmark/sparse_attention.py)
# ---------------------------------------------------------------------------

L = "{3,2,1,0:T(8,128)}"
OPS = [
    # scores of a block: 4 key heads x 8 runs of 1,024 queries, 3,072 keys
    ("attention", f"%fusion.1 = f32[1,4,8192,3072]{L} fusion(f32[1,8192,4,"
     f"128]{L} %a, f32[1,3072,4,128]{L} %b, pred[1,1,8192,3072]{L} %m), "
     f"kind=kOutput, calls=%fused_computation.1"),
    # a row reduction of the softmax: found by what it reads
    ("attention", f"%fusion.2 = f32[1,4,8192]{L} fusion(f32[1,4,8192,3072]"
     f"{L} %s), kind=kInput, calls=%fused_computation.2"),
    ("attention", f"%fusion.3 = f32[1,8192,4,128]{L} fusion(f32[1,4,8192,"
     f"8192]{L} %p, f32[1,8192,4,128]{L} %v), kind=kOutput"),
    ("attention", f"%fusion.4 = f32[1,8,1024,4,128]{L} fusion(f32[1,1024,"
     f"32,128]{L} %q), kind=kLoop"),
    ("attention", f"%fusion.5 = (f32[1,32,1024,1024]{L}, f32[1,32,1024]{L}) "
     f"fusion(f32[1,1024,32,128]{L} %q), kind=kOutput"),
    ("indexer", f"%fusion.6 = f32[1,16,1024,3072]{L} fusion(f32[1,1024,16,"
     f"64]{L} %q, f32[1,3072,64]{L} %k), kind=kOutput"),
    ("indexer", f"%fusion.7 = u32[1,1024,3072]{L} fusion(f32[1,16,1024,"
     f"3072]{L} %s, f32[1,16,1024]{L} %w), kind=kInput"),
    # a count of the search: found by the mapped scores it reads
    ("indexer", f"%fusion.8 = s32[1,1024]{L} fusion(u32[1,1024,3072]{L} "
     f"%key, u32[1,1024]{L} %kth), kind=kInput"),
    ("indexer", f"%pad.9 = pred[1,1024,8192]{L} pad(pred[1,1024,3072]{L} "
     f"%chosen, pred[] %false), padding=0_0x0_0x0_5120"),
    ("indexer", f"%concatenate.10 = pred[1,8192,8192]{L} concatenate("
     f"pred[1,1024,8192]{L} %a, pred[1,1024,8192]{L} %b), dimensions={{1}}"),
    # the search loop spans its body's ops: an op of its own it is not
    (None, f"%while.11 = (s32[], u32[1,1024]{L}, u32[1,1024,3072]{L}) "
     f"while((s32[], u32[1,1024]{L}, u32[1,1024,3072]{L}) %t), "
     f"condition=%c, body=%b"),
    # the tiled selection the core reads decides nothing
    (None, f"%concatenate.12 = pred[1,1,8192,3072]{L} concatenate(pred[1,1,"
     f"1024,3072]{L} %a, pred[1,1,1024,3072]{L} %b), dimensions={{2}}"),
    (None, f"%fusion.13 = f32[512,768]{L} fusion(f32[512,2048]{L} %x, "
     f"f32[2048,768]{L} %w), kind=kOutput"),
    (None, f"%fusion.14 = f32[8192,4096]{L} fusion(f32[8192,2048]{L} %x, "
     f"f32[2048,4096]{L} %w), kind=kOutput"),
    (None, f"%fusion.15 = f32[1,8192,18992]{L} fusion(f32[1,8192,2048]{L} "
     f"%x, f32[2048,18992]{L} %w), kind=kOutput"),
    (None, f"%fusion.16 = f32[1,8192,16,64]{L} fusion(f32[1,8192,1024]{L} "
     f"%x), kind=kLoop"),
]


@pytest.mark.parametrize("want,hlo", OPS,
                         ids=[hlo.split(" = ")[0] for _, hlo in OPS])
def test_the_reader_groups_an_op_by_the_shapes_its_line_names(want, hlo):
    from benchmark import sparse_attention
    full = json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                       "keye_vl2_30b_a3b.json")))
    m = dict(full["model"], block=1024, batch=1)
    assert sparse_attention.group_of(hlo, m) == want


def test_required_work_is_over_the_pairs_the_counters_report():
    from benchmark import sparse_attention as sa
    full = json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                       "keye_vl2_30b_a3b.json")))
    m = full["model"]
    causal, chosen = 8192 * 8193 // 2, ref.selected_pairs(8192, 2048)
    flops, nbytes = sa.selected_attention_required(m, chosen, 1)
    # three passes of the 32 x 256 multiply-accumulates a selected pair
    assert flops == 3 * 2 * 32 * 256 * chosen
    assert nbytes == 3 * (4 * 8192 * 128 * (2 * 32 + 2 * 4) + causal)
    flops, nbytes = sa.indexer_required(m, causal, 1)
    assert flops == 2 * 16 * 64 * causal        # one pass: no gradient
    assert nbytes == 4 * 8192 * (16 * 64 + 64 + 16) + causal
    # a model without an indexer, a run without the counters: nothing
    ctx = {"cell": "glm47_flash.silos2"}
    assert sa._model(ctx) is None
    assert sa.group_seconds(ctx, "attention") is None
    assert sa.attention_roofline_share(ctx) is None
    assert sa.indexer_roofline_share(ctx) is None
    assert sa.share_of_wave(dict(ctx, trace={})) is None
    assert sa._model({"cell": "keye_vl2_30b_a3b.silos2"})["block"] == 1024
