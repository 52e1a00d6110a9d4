"""Pipeline parallelism (parallel/pipeline.py): GPipe microbatching over a
[stages] mesh must be numerically invisible — forward and gradients equal
the single-device scan-over-layers reference — and trainable end-to-end.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from fedml_tpu.parallel.pipeline import PipelineLM, make_stage_mesh


@pytest.fixture(scope="module")
def setup():
    lm = PipelineLM(vocab_size=32, d_model=32, n_heads=2, n_layers=4,
                    d_ff=64, max_len=16)
    toks = jnp.asarray(np.random.RandomState(0).randint(1, 32, (8, 16)),
                       jnp.int32)
    params = lm.init(jax.random.key(0), toks)
    return lm, toks, params


def _ce(logits, y):
    return optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), y).mean()


@pytest.mark.parametrize("n_stages,n_micro", [(4, 4), (2, 8), (4, 2), (1, 4)])
def test_pp_forward_matches_sequential(setup, devices, n_stages, n_micro):
    """Every stage/microbatch split — including a bubble-heavy one
    (n_micro < n_stages) and the degenerate 1-stage pipeline — computes
    exactly the sequential forward."""
    lm, toks, params = setup
    mesh = make_stage_mesh(n_stages, devices=devices)
    pp = lm.pp_shard_params(params, mesh, n_stages)
    out = jax.jit(lm.make_pp_apply(mesh, n_micro=n_micro))(pp, toks)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(lm.apply_seq(params, toks)),
                               rtol=1e-4, atol=1e-5)


def test_pp_gradients_match_sequential(setup, devices):
    """Autodiff through the pipeline (ppermute transpose = reverse hop)
    must reproduce the sequential gradients for blocks, embed, and head."""
    lm, toks, params = setup
    y = jnp.roll(toks, -1, axis=1)
    mesh = make_stage_mesh(4, devices=devices)
    pp = lm.pp_shard_params(params, mesh, 4)
    pp_fn = lm.make_pp_apply(mesh, n_micro=4)

    g_seq = jax.grad(lambda p: _ce(lm.apply_seq(p, toks), y))(params)
    g_pp = jax.jit(jax.grad(lambda p: _ce(pp_fn(p, toks), y)))(pp)
    g_pp_blocks = jax.tree.map(np.asarray, g_pp["blocks"])
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5),
        g_seq["blocks"], g_pp_blocks)
    for part in ("embed", "final"):
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5),
            g_seq[part], jax.tree.map(np.asarray, g_pp[part]))


def test_pp_trains(setup, devices):
    lm, toks, params = setup
    y = jnp.roll(toks, -1, axis=1)
    mesh = make_stage_mesh(4, devices=devices)
    p = lm.pp_shard_params(params, mesh, 4)
    pp_fn = lm.make_pp_apply(mesh, n_micro=4)
    loss = lambda p: _ce(pp_fn(p, toks), y)
    opt = optax.sgd(0.3)
    st = opt.init(p)
    l0 = float(loss(p))
    vg = jax.jit(jax.value_and_grad(loss))
    for _ in range(10):
        _, g = vg(p)
        up, st = opt.update(g, st, p)
        p = optax.apply_updates(p, up)
    assert float(loss(p)) < 0.8 * l0


def test_pp_workload_local_training_matches_sequential(setup, devices):
    """The pipelined Workload rides the standard local trainer: a full
    silo-local SGD run (scan over batches) through the GPipe forward must
    match the sequential-forward twin bit-for-bit-ish — pp is a silo-side
    execution detail, invisible to the federated choreography."""
    from fedml_tpu.data.stacking import stack_client_data
    from fedml_tpu.parallel.pipeline import (make_pp_nwp_workload,
                                             make_seq_nwp_workload)
    from fedml_tpu.trainer.local_sgd import make_evaluator, make_local_trainer
    from fedml_tpu.trainer.workload import make_client_optimizer

    lm, toks, params = setup
    rng = np.random.RandomState(1)
    x = rng.randint(1, 32, (16, 16)).astype(np.int32)
    y = np.concatenate([x[:, 1:], x[:, :1]], axis=1)
    stacked = stack_client_data([x], [y], batch_size=8)
    data = jax.tree.map(lambda v: jnp.asarray(v[0]),
                        {k: stacked[k] for k in ("x", "y", "mask")})

    mesh = make_stage_mesh(4, devices=devices)
    wl_pp = make_pp_nwp_workload(lm, mesh, n_micro=4)
    wl_seq = make_seq_nwp_workload(lm)
    one_batch = jax.tree.map(lambda v: v[0], data)
    assert jax.tree.structure(wl_pp.init(jax.random.key(0), one_batch)) \
        == jax.tree.structure(params)

    opt = make_client_optimizer("sgd", 0.3)
    out_seq, _ = make_local_trainer(wl_seq, opt, epochs=2)(
        params, data, jax.random.key(2))
    pp_params = lm.pp_shard_params(params, mesh, 4)
    out_pp, _ = make_local_trainer(wl_pp, opt, epochs=2)(
        pp_params, data, jax.random.key(2))
    out_pp_blocks = jax.tree.map(np.asarray, out_pp["blocks"])
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4),
        out_seq["blocks"], out_pp_blocks)

    # eval parity through the same Workload contract
    m_seq = make_evaluator(wl_seq)(out_seq, data)
    m_pp = make_evaluator(wl_pp)(out_pp, data)
    assert float(m_seq["total"]) == float(m_pp["total"])
    np.testing.assert_allclose(float(m_seq["loss_sum"]),
                               float(m_pp["loss_sum"]), rtol=1e-3)
    assert abs(float(m_seq["correct"]) - float(m_pp["correct"])) <= 2


@pytest.fixture(scope="module")
def moe_setup():
    lm = PipelineLM(vocab_size=32, d_model=32, n_heads=2, n_layers=4,
                    d_ff=64, max_len=16, moe_experts=4)
    rng = np.random.RandomState(3)
    toks = np.asarray(rng.randint(1, 32, (8, 16)), np.int32)
    toks[-1, 10:] = 0  # pad tail: routing must exclude it at every stage
    toks = jnp.asarray(toks)
    params = lm.init(jax.random.key(0), toks)
    return lm, toks, params


@pytest.mark.parametrize("n_stages,n_micro", [(4, 4), (2, 8)])
def test_pp_moe_forward_and_balance_match_sequential(moe_setup, devices,
                                                     n_stages, n_micro):
    """ep x pp: the Switch-MoE block stack pipelined over stages must
    reproduce the sequential MoE twin — logits AND the balance loss (per
    microbatch routing stats, mean over microbatches; the loss the old
    loud rejection said would be silently dropped)."""
    lm, toks, params = moe_setup
    mesh = make_stage_mesh(n_stages, devices=devices)
    pp = lm.pp_shard_params(params, mesh, n_stages)
    out_pp, bal_pp = jax.jit(
        lm.make_pp_apply(mesh, n_micro=n_micro, with_aux=True))(pp, toks)
    out_seq, bal_seq = lm.apply_seq_with_aux(params, toks, n_micro=n_micro)
    np.testing.assert_allclose(np.asarray(out_pp), np.asarray(out_seq),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(float(bal_pp), float(bal_seq),
                               rtol=1e-5, atol=1e-7)
    assert float(bal_pp) > 0.0  # real routing pressure, not a dropped sow


def test_pp_moe_gradients_carry_balance_loss(moe_setup, devices):
    """The balance term must flow into the ROUTER's gradient through the
    pipeline: d(loss)/d(router) equals the sequential twin's, and is
    nonzero (a dropped balance loss would leave the router driven only by
    the gate path)."""
    lm, toks, params = moe_setup
    mesh = make_stage_mesh(4, devices=devices)
    pp = lm.pp_shard_params(params, mesh, 4)
    pp_fn = lm.make_pp_apply(mesh, n_micro=4, with_aux=True)

    def loss_pp(p):
        logits, bal = pp_fn(p, toks)
        return _ce(logits, jnp.roll(toks, -1, axis=1)) \
            + lm.moe_aux_weight * bal

    def loss_seq(p):
        logits, bal = lm.apply_seq_with_aux(p, toks, n_micro=4)
        return _ce(logits, jnp.roll(toks, -1, axis=1)) \
            + lm.moe_aux_weight * bal

    g_seq = jax.grad(loss_seq)(params)
    g_pp = jax.jit(jax.grad(loss_pp))(pp)
    g_pp_blocks = jax.tree.map(np.asarray, g_pp["blocks"])
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=2e-3, atol=1e-5),
        g_seq["blocks"], g_pp_blocks)
    router_g = g_pp_blocks["moe"]["router"]["kernel"]
    assert float(np.abs(router_g).max()) > 0.0


def test_pp_moe_workload_local_training_matches_sequential(moe_setup,
                                                           devices):
    """The MoE pipeline rides the standard Workload/local-trainer seam,
    training to the same params as the sequential MoE twin."""
    from fedml_tpu.data.stacking import stack_client_data
    from fedml_tpu.parallel.pipeline import (make_pp_nwp_workload,
                                             make_seq_nwp_workload)
    from fedml_tpu.trainer.local_sgd import make_local_trainer
    from fedml_tpu.trainer.workload import make_client_optimizer

    lm, toks, params = moe_setup
    rng = np.random.RandomState(5)
    x = rng.randint(1, 32, (8, 16)).astype(np.int32)
    y = np.concatenate([x[:, 1:], x[:, :1]], axis=1)
    stacked = stack_client_data([x], [y], batch_size=8)
    data = jax.tree.map(lambda v: jnp.asarray(v[0]),
                        {k: stacked[k] for k in ("x", "y", "mask")})

    mesh = make_stage_mesh(2, devices=devices)
    wl_pp = make_pp_nwp_workload(lm, mesh, n_micro=4)
    wl_seq = make_seq_nwp_workload(lm, n_micro=4)
    opt = make_client_optimizer("sgd", 0.3)
    out_seq, _ = make_local_trainer(wl_seq, opt, epochs=2)(
        params, data, jax.random.key(2))
    pp_params = lm.pp_shard_params(params, mesh, 2)
    out_pp, _ = make_local_trainer(wl_pp, opt, epochs=2)(
        pp_params, data, jax.random.key(2))
    out_pp_blocks = jax.tree.map(np.asarray, out_pp["blocks"])
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=2e-3, atol=1e-4),
        out_seq["blocks"], out_pp_blocks)


def test_pp_shape_errors(setup, devices):
    lm, toks, params = setup
    mesh = make_stage_mesh(3, devices=devices)
    with pytest.raises(ValueError, match="not divisible"):
        lm.pp_shard_params(params, mesh, 3)  # 4 layers / 3 stages
    mesh4 = make_stage_mesh(4, devices=devices)
    pp = lm.pp_shard_params(params, mesh4, 4)
    with pytest.raises(ValueError, match="microbatches"):
        lm.make_pp_apply(mesh4, n_micro=3)(pp, toks)  # 8 % 3 != 0
