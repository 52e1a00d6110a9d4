"""Sequence/context parallelism: ring attention over a ``sequence`` mesh
axis must exactly reproduce dense causal attention (the long-context design
the reference lacks entirely, SURVEY.md §5.7)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.models import TransformerLM
from fedml_tpu.parallel.ring_attention import (
    full_attention, make_sequence_mesh, make_sequence_parallel_apply,
    ring_attention)


def _qkv(rng, b=2, t=32, h=2, d=8):
    q = rng.randn(b, t, h, d).astype(np.float32)
    k = rng.randn(b, t, h, d).astype(np.float32)
    v = rng.randn(b, t, h, d).astype(np.float32)
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


def _dense_reference(q, k, v, causal):
    """Plain softmax attention in numpy-ish jnp, no online accumulation."""
    d = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(d * 1.0)
    if causal:
        t = q.shape[1]
        mask = jnp.tril(jnp.ones((t, t), bool))
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v)


@pytest.mark.parametrize("causal", [True, False])
def test_full_attention_matches_dense_softmax(rng, causal):
    q, k, v = _qkv(np.random.RandomState(0))
    pos = jnp.arange(q.shape[1])
    got = full_attention(q, k, v, pos, pos, causal=causal)
    want = _dense_reference(q, k, v, causal)
    np.testing.assert_allclose(got, want, atol=1e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_full(devices, causal):
    """Sharded ring == dense, on the 8-device mesh."""
    from jax.sharding import PartitionSpec as P

    q, k, v = _qkv(np.random.RandomState(1), t=32)
    pos = jnp.arange(32)
    want = full_attention(q, k, v, pos, pos, causal=causal)

    mesh = make_sequence_mesh(8)

    def _sharded(q, k, v, pos):
        return ring_attention(q, k, v, pos, pos, "sequence", causal=causal)

    fn = jax.jit(jax.shard_map(
        _sharded, mesh=mesh,
        in_specs=(P(None, "sequence"), P(None, "sequence"),
                  P(None, "sequence"), P("sequence")),
        out_specs=P(None, "sequence")))
    got = fn(q, k, v, pos)
    np.testing.assert_allclose(got, want, atol=1e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_blockwise_attention_matches_full(rng, causal):
    """Flash-style kv-block scan == dense, including gradients."""
    from fedml_tpu.parallel.ring_attention import blockwise_attention

    q, k, v = _qkv(np.random.RandomState(5), t=32)
    pos = jnp.arange(32)
    want = full_attention(q, k, v, pos, pos, causal=causal)
    got = blockwise_attention(q, k, v, pos, pos, block_size=8, causal=causal)
    np.testing.assert_allclose(got, want, atol=1e-5)

    def loss_block(q, k, v):
        return jnp.sum(blockwise_attention(q, k, v, pos, pos, 8,
                                           causal=causal) ** 2)

    def loss_full(q, k, v):
        return jnp.sum(full_attention(q, k, v, pos, pos,
                                      causal=causal) ** 2)

    g_block = jax.grad(loss_block, argnums=(0, 1, 2))(q, k, v)
    g_full = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_block, g_full):
        np.testing.assert_allclose(a, b, atol=1e-4)


def test_transformer_blockwise_matches_dense():
    """TransformerLM(block_size=...) forward == dense TransformerLM with the
    same params."""
    dense = TransformerLM(vocab_size=40, d_model=32, n_heads=2, n_layers=2,
                          d_ff=64, max_len=64)
    blocked = TransformerLM(vocab_size=40, d_model=32, n_heads=2, n_layers=2,
                            d_ff=64, max_len=64, block_size=8)
    toks = jnp.asarray(np.random.RandomState(6).randint(0, 40, (2, 32)),
                       jnp.int32)
    params = dense.init(jax.random.key(0), toks)["params"]
    np.testing.assert_allclose(blocked.apply({"params": params}, toks),
                               dense.apply({"params": params}, toks),
                               atol=1e-4)


def test_transformer_tp_sharded_matches_dense(devices):
    """GSPMD dp×tp on the transformer: with q/k/v DenseGeneral kernels
    head-sharded over a model axis, the jitted forward equals the
    replicated one (XLA inserts the tensor-parallel collectives)."""
    from fedml_tpu.parallel.mesh import make_mesh, tp_shard_params

    model = TransformerLM(vocab_size=40, d_model=32, n_heads=2, n_layers=1,
                         d_ff=64, max_len=32)
    toks = jnp.asarray(np.random.RandomState(9).randint(0, 40, (4, 32)),
                       jnp.int32)
    params = model.init(jax.random.key(0), toks)["params"]
    want = model.apply({"params": params}, toks)

    mesh = make_mesh(client_axis=4, model_axis=2)
    params_tp = tp_shard_params(params, mesh, min_size=512)
    # every large 3-D DenseGeneral kernel must shard its HEADS dim (size 2
    # here) — in-projections at dim 1, the out-projection at dim 0 — so
    # the column/row-parallel pair needs one psum, not a reshard
    n_sharded = 0
    for p in jax.tree.leaves(params_tp):
        if getattr(p, "ndim", 0) != 3:
            continue
        spec = p.sharding.spec
        sharded_dims = [i for i, s in enumerate(spec) if s == "model"]
        assert sharded_dims, (p.shape, spec)
        assert p.shape[sharded_dims[0]] == 2, (p.shape, spec)
        n_sharded += 1
    assert n_sharded >= 4  # q, k, v, out
    got = jax.jit(lambda p, x: model.apply({"params": p}, x))(params_tp, toks)
    np.testing.assert_allclose(got, want, atol=1e-5)


@pytest.mark.parametrize("t", [1024, 2000])  # 2000: largest divisor is 500
def test_transformer_auto_blockwise_past_threshold(t):
    """With no backend flag, sequences past auto_block_len silently switch
    to blockwise — including lengths not divisible by 512 (the block is
    the largest 64-512 divisor of T) — with exact parity vs dense."""
    dense = TransformerLM(vocab_size=20, d_model=16, n_heads=2, n_layers=1,
                          d_ff=32, max_len=2048, auto_block_len=1 << 30)
    auto = TransformerLM(vocab_size=20, d_model=16, n_heads=2, n_layers=1,
                         d_ff=32, max_len=2048, auto_block_len=512)
    toks = jnp.asarray(np.random.RandomState(8).randint(0, 20, (1, t)),
                       jnp.int32)
    params = dense.init(jax.random.key(0), toks)["params"]
    np.testing.assert_allclose(auto.apply({"params": params}, toks),
                               dense.apply({"params": params}, toks),
                               atol=1e-4)


def test_auto_block_divisor_choice():
    from fedml_tpu.models.transformer import _auto_block
    assert _auto_block(1024, 1 << 30) is None          # under threshold
    assert _auto_block(2048, 1024) == 512
    assert _auto_block(2000, 1024) == 500
    assert _auto_block(1031, 1024) is None             # prime: stay dense


def test_transformer_flash_backend_rejects_cpu():
    """use_flash is the TPU pallas kernel; off-TPU it must fail loudly with
    guidance, never fall back silently (a silent fallback would fake a
    flash benchmark)."""
    model = TransformerLM(vocab_size=16, d_model=32, n_heads=2, n_layers=1,
                         d_ff=64, max_len=16, use_flash=True)
    toks = jnp.zeros((1, 16), jnp.int32)
    with pytest.raises(RuntimeError, match="needs a TPU backend"):
        model.init(jax.random.key(0), toks)


def test_transformer_sequence_parallel_parity(devices):
    """The FULL model (embeddings, LN, MLP, attention, head) under a
    sequence-sharded shard_map equals the single-device forward."""
    model = TransformerLM(vocab_size=50, d_model=32, n_heads=2, n_layers=2,
                         d_ff=64, max_len=64)
    b, t = 2, 32
    toks = jnp.asarray(np.random.RandomState(2).randint(0, 50, (b, t)),
                       jnp.int32)
    params = model.init(jax.random.key(0), toks)["params"]
    want = model.apply({"params": params}, toks)

    mesh = make_sequence_mesh(8)
    sp_apply = make_sequence_parallel_apply(model, mesh)
    got = sp_apply(params, toks)
    np.testing.assert_allclose(got, want, atol=1e-4)


def test_transformer_is_causal():
    """Changing tokens at positions > t must not change logits at t."""
    model = TransformerLM(vocab_size=50, d_model=32, n_heads=2, n_layers=1,
                         d_ff=64, max_len=64)
    rng = np.random.RandomState(3)
    toks = jnp.asarray(rng.randint(0, 50, (1, 16)), jnp.int32)
    params = model.init(jax.random.key(0), toks)["params"]
    out = model.apply({"params": params}, toks)
    toks2 = toks.at[0, 10:].set((toks[0, 10:] + 1) % 50)
    out2 = model.apply({"params": params}, toks2)
    np.testing.assert_allclose(out[0, :10], out2[0, :10], atol=1e-5)
    assert not np.allclose(out[0, 10:], out2[0, 10:])


def test_sp_cohort_step_matches_dense_cohort(devices):
    """Federated long-context: the dp×sp [4 clients, 2 sequence] mesh round
    (ring attention + psum'd loss/grads within each client, weighted psum
    aggregation across clients) == the single-chip vmap cohort with dense
    attention."""
    from fedml_tpu.data.stacking import stack_client_data
    from fedml_tpu.parallel.cohort import make_cohort_step
    from fedml_tpu.parallel.sequence import (
        make_sp_cohort_step, make_sp_mesh, make_sp_nwp_workload)
    from fedml_tpu.trainer.local_sgd import make_local_trainer
    from fedml_tpu.trainer.workload import NWPWorkload, make_client_optimizer

    model = TransformerLM(vocab_size=30, d_model=32, n_heads=2, n_layers=1,
                         d_ff=64, max_len=16)
    rng = np.random.RandomState(7)
    xs = [rng.randint(1, 30, (6, 16)).astype(np.int32) for _ in range(4)]
    ys = [np.concatenate([x[:, 1:], x[:, :1]], axis=1) for x in xs]
    stacked = {k: jnp.asarray(v)
               for k, v in stack_client_data(xs, ys, batch_size=3).items()}

    dense_wl = NWPWorkload(model)
    params = dense_wl.init(jax.random.key(0), jax.tree.map(
        lambda v: v[0, 0], {k: stacked[k] for k in ("x", "y", "mask")}))

    opt = make_client_optimizer("sgd", 0.1)
    dense_step = make_cohort_step(make_local_trainer(dense_wl, opt, 1))
    want, want_metrics = dense_step(params, stacked, jax.random.key(1))

    sp_wl = make_sp_nwp_workload(model)
    sp_step = make_sp_cohort_step(sp_wl, opt, epochs=1,
                                  mesh=make_sp_mesh(4, 2))
    got, got_metrics = sp_step(params, stacked, jax.random.key(1))

    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, atol=1e-4),
                 got, want)
    np.testing.assert_allclose(got_metrics["train_loss_per_step"],
                               want_metrics["train_loss_per_step"],
                               atol=1e-4)


@pytest.mark.slow
def test_transformer_federated_learning_to_target():
    """The attention path LEARNS, not just runs: federated training on a
    deterministic next-token task (y_t = x_t) must reach >90% token accuracy
    — the convergence-suite pattern applied to the transformer family."""
    from conftest import identity_lm_data
    from fedml_tpu.algorithms import FedAvg, FedAvgConfig
    from fedml_tpu.trainer.workload import NWPWorkload

    model = TransformerLM(vocab_size=12, d_model=32, n_heads=2, n_layers=1,
                         d_ff=64, max_len=16)
    data = identity_lm_data()
    cfg = FedAvgConfig(comm_round=30, client_num_per_round=4, epochs=2,
                       batch_size=8, lr=0.3, frequency_of_the_test=29)
    algo = FedAvg(NWPWorkload(model), data, cfg)
    algo.run()
    assert algo.history[-1]["train_acc"] > 0.9, algo.history[-1]


def test_transformer_nwp_federated_round(devices):
    """Transformer drives the NWP workload through a full FedAvg cohort
    step (vmap'd clients + weighted aggregation) — loss finite, params move."""
    from fedml_tpu.data.stacking import stack_client_data
    from fedml_tpu.parallel.cohort import make_cohort_step
    from fedml_tpu.trainer.local_sgd import make_local_trainer
    from fedml_tpu.trainer.workload import NWPWorkload, make_client_optimizer

    model = TransformerLM(vocab_size=30, d_model=32, n_heads=2, n_layers=1,
                         d_ff=64, max_len=32)
    wl = NWPWorkload(model)
    rng = np.random.RandomState(4)
    xs = [rng.randint(1, 30, (6, 16)).astype(np.int32) for _ in range(4)]
    ys = [np.concatenate([x[:, 1:], x[:, :1]], axis=1) for x in xs]
    stacked = {k: jnp.asarray(v)
               for k, v in stack_client_data(xs, ys, batch_size=3).items()}
    params = wl.init(jax.random.key(0), jax.tree.map(
        lambda v: v[0, 0], {k: stacked[k] for k in ("x", "y", "mask")}))
    step = make_cohort_step(
        make_local_trainer(wl, make_client_optimizer("sgd", 0.1), epochs=1))
    new_params, metrics = step(params, stacked, jax.random.key(1))
    assert np.isfinite(float(metrics["train_loss_per_step"].mean()))
    delta = max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.abs(a - b).max()), params, new_params)))
    assert delta > 0
