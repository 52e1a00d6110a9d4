"""Switch MoE (models/moe.py) + expert parallelism (parallel/expert.py).

The reference has no MoE; ep is here because the framework treats every
parallelism as a placement knob (SURVEY.md §2.5).  Core claims: the
routed layer computes what it says (capacity drops ride the residual),
the balance loss reaches the optimizer, and GSPMD expert sharding is
numerically invisible — forward AND gradients — on the 8-device mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.models import SwitchFFN, TransformerLM
from fedml_tpu.parallel.expert import (ep_shard_params, make_dp_ep_mesh,
                                       make_expert_mesh)
from fedml_tpu.trainer.workload import NWPWorkload


@pytest.fixture(scope="module")
def lm_setup():
    lm = TransformerLM(vocab_size=32, d_model=32, n_heads=2, n_layers=2,
                      d_ff=64, max_len=16, moe_experts=8)
    toks = jnp.asarray(np.random.RandomState(0).randint(1, 32, (4, 16)),
                       jnp.int32)
    params = lm.init(jax.random.key(0), toks)["params"]
    return lm, toks, params


def test_switch_ffn_routes_and_drops():
    """Tiny capacity with 64 tokens (one routing group): most tokens are
    dropped and must come back EXACTLY zero (they ride the transformer
    residual); kept tokens must be nonzero."""
    ffn = SwitchFFN(n_experts=2, d_model=8, d_ff=16, capacity_factor=0.04)
    x = jnp.asarray(np.random.RandomState(1).randn(1, 64, 8), jnp.float32)
    params = ffn.init(jax.random.key(0), x)["params"]
    y = ffn.apply({"params": params}, x)
    assert y.shape == x.shape
    row_norm = np.asarray(jnp.abs(y[0]).sum(axis=-1))
    kept = (row_norm > 0).sum()
    # one 64-token group: cap = ceil(0.04*64/2) = 2/expert -> <= 4 kept
    assert 1 <= kept <= 4, kept


def test_switch_ffn_pads_excluded():
    """Masked (pad) positions must return exactly zero, must not shift or
    consume real tokens' expert capacity, and must not enter the balance
    statistics — real-token outputs and the sown aux are identical with
    and without trailing pads."""
    ffn = SwitchFFN(n_experts=4, d_model=8, d_ff=16, capacity_factor=4.0)
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(1, 16, 8), jnp.float32)
    params = ffn.init(jax.random.key(0), x)["params"]
    mask = jnp.asarray([[1.0] * 8 + [0.0] * 8])

    y_all, sown_all = ffn.apply({"params": params}, x,
                                mutable=["losses"])
    y_mask, sown_mask = ffn.apply({"params": params}, x, mask,
                                  mutable=["losses"])
    # pads come back zero; real tokens unaffected by the pads' presence
    # (capacity_factor=4 ensures zero drops in both runs)
    np.testing.assert_array_equal(np.asarray(y_mask[0, 8:]), 0.0)
    np.testing.assert_allclose(np.asarray(y_mask[0, :8]),
                               np.asarray(y_all[0, :8]), rtol=1e-6)
    # aux over real tokens only == aux of the unpadded prefix
    _, sown_prefix = ffn.apply({"params": params}, x[:, :8],
                               mutable=["losses"])
    aux_mask = float(jax.tree.leaves(sown_mask["losses"])[0])
    aux_prefix = float(jax.tree.leaves(sown_prefix["losses"])[0])
    aux_all = float(jax.tree.leaves(sown_all["losses"])[0])
    assert abs(aux_mask - aux_prefix) < 1e-5
    assert abs(aux_mask - aux_all) > 1e-6  # pads DID move the unmasked aux


def test_switch_ffn_grouped_routing_bounds_dispatch():
    """group_size splits routing: with G groups the dispatch tensor is
    [G, g, E, C] (linear in tokens).  Outputs stay exact for the kept
    tokens; per-group capacity means drop behavior is LOCAL to a group."""
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(2, 64, 8), jnp.float32)
    big = SwitchFFN(n_experts=4, d_model=8, d_ff=16, capacity_factor=4.0,
                    group_size=128)
    small = SwitchFFN(n_experts=4, d_model=8, d_ff=16, capacity_factor=4.0,
                      group_size=32)
    params = big.init(jax.random.key(0), x)["params"]
    # no-drop regime: group choice cannot change the math
    np.testing.assert_allclose(
        np.asarray(big.apply({"params": params}, x)),
        np.asarray(small.apply({"params": params}, x)), rtol=1e-5,
        atol=1e-6)
    with pytest.raises(ValueError, match="must divide"):
        SwitchFFN(n_experts=4, d_model=8, d_ff=16, group_size=48).apply(
            {"params": params}, x)


def test_balance_loss_reaches_training(lm_setup):
    """The sown load-balance terms must change the training loss (plain
    CE vs CE + alpha*aux) and produce router gradients."""
    lm, toks, params = lm_setup
    wl = NWPWorkload(lm)
    batch = {"x": toks, "y": jnp.roll(toks, -1, axis=1),
             "mask": jnp.ones(4, jnp.float32)}
    loss, _ = wl.loss_fn(params, batch, None, True)

    lm0 = lm.copy(moe_aux_weight=0.0)
    loss0, _ = NWPWorkload(lm0).loss_fn(params, batch, None, True)
    assert float(loss) > float(loss0)  # aux is nonnegative and active

    g = jax.grad(lambda p: wl.loss_fn(p, batch, None, True)[0])(params)
    assert float(jnp.abs(g["moe_0"]["router"]["kernel"]).max()) > 0


def test_ep_sharding_placement(lm_setup, devices):
    """Expert tables land on the experts axis; the router and every
    non-MoE leaf stay replicated (every token needs every router row)."""
    from jax.sharding import PartitionSpec as P
    lm, toks, params = lm_setup
    mesh = make_expert_mesh(8, devices=devices)
    placed = ep_shard_params(params, mesh, 8)
    assert placed["moe_0"]["w1"].sharding.spec == P("experts", None, None)
    assert placed["moe_1"]["w2"].sharding.spec == P("experts", None, None)
    assert placed["moe_0"]["b1"].sharding.spec == P("experts", None)
    assert placed["moe_0"]["router"]["kernel"].sharding.spec == P()
    assert placed["tok_embed"]["embedding"].sharding.spec == P()


def test_ep_matches_single_chip(lm_setup, devices):
    """GSPMD ep: forward and gradients with experts sharded over 8 devices
    must equal the unsharded computation — XLA's inserted dispatch/combine
    collectives change layout, not math."""
    lm, toks, params = lm_setup
    wl = NWPWorkload(lm)
    batch = {"x": toks, "y": jnp.roll(toks, -1, axis=1),
             "mask": jnp.ones(4, jnp.float32)}
    mesh = make_expert_mesh(8, devices=devices)
    params_ep = ep_shard_params(params, mesh, 8)

    fwd = jax.jit(lambda p, x: lm.apply({"params": p}, x))
    np.testing.assert_allclose(np.asarray(fwd(params, toks)),
                               np.asarray(fwd(params_ep, toks)),
                               rtol=1e-5, atol=2e-5)
    grad = jax.jit(jax.grad(lambda p: wl.loss_fn(p, batch, None, True)[0]))
    g, g_ep = grad(params), grad(params_ep)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-4, atol=2e-5), g, g_ep)


def test_ep_shard_rejects_indivisible(lm_setup, devices):
    lm, toks, params = lm_setup
    mesh = make_expert_mesh(8, devices=devices)
    with pytest.raises(ValueError, match="not divisible"):
        ep_shard_params(params, mesh, 12)


def test_dp_ep_cohort_round_matches_single_chip(devices):
    """dp x ep: the FULL federated round on a [clients=2, experts=4] mesh
    — cohort rows on clients, expert tables on experts, plain vmapped
    cohort step under GSPMD — must equal the unsharded round."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from fedml_tpu.data.stacking import stack_client_data
    from fedml_tpu.parallel.cohort import make_cohort_step
    from fedml_tpu.trainer.local_sgd import make_local_trainer
    from fedml_tpu.trainer.workload import make_client_optimizer

    lm = TransformerLM(vocab_size=32, d_model=32, n_heads=2, n_layers=1,
                      d_ff=64, max_len=8, moe_experts=4)
    wl = NWPWorkload(lm)
    rng = np.random.RandomState(0)
    xs = [rng.randint(1, 32, (4, 8)).astype(np.int32) for _ in range(4)]
    ys = [np.concatenate([x[:, 1:], x[:, :1]], axis=1) for x in xs]
    cohort = {k: jnp.asarray(v)
              for k, v in stack_client_data(xs, ys, batch_size=2).items()}
    params = wl.init(jax.random.key(0), jax.tree.map(
        lambda v: v[0, 0], {k: cohort[k] for k in ("x", "y", "mask")}))
    step = make_cohort_step(
        make_local_trainer(wl, make_client_optimizer("sgd", 0.1), epochs=1))
    want, _ = step(params, cohort, jax.random.key(5))

    mesh = make_dp_ep_mesh(2, 4, devices=devices)
    params_s = ep_shard_params(params, mesh, 4)
    cohort_s = jax.tree.map(
        lambda v: jax.device_put(v, NamedSharding(mesh, P("clients"))),
        cohort)
    got, _ = step(params_s, cohort_s, jax.random.key(5))
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-4, atol=2e-5), want, got)


def test_moe_lm_learns_federatedly():
    """The MoE transformer rides the standard federated machinery: a few
    FedAvg rounds on the identity-LM task must cut the loss."""
    from fedml_tpu.data.stacking import stack_client_data
    from fedml_tpu.parallel.cohort import make_cohort_step
    from fedml_tpu.trainer.local_sgd import make_local_trainer
    from fedml_tpu.trainer.workload import make_client_optimizer

    lm = TransformerLM(vocab_size=16, d_model=32, n_heads=2, n_layers=1,
                      d_ff=64, max_len=8, moe_experts=4)
    wl = NWPWorkload(lm)
    rng = np.random.RandomState(0)
    xs = [rng.randint(1, 16, (8, 8)).astype(np.int32) for _ in range(4)]
    ys = [x.copy() for x in xs]  # identity task
    cohort = {k: jnp.asarray(v)
              for k, v in stack_client_data(xs, ys, batch_size=4).items()}
    params = wl.init(jax.random.key(0), jax.tree.map(
        lambda v: v[0, 0], {k: cohort[k] for k in ("x", "y", "mask")}))
    step = make_cohort_step(
        make_local_trainer(wl, make_client_optimizer("sgd", 0.3), epochs=1))
    losses = []
    for r in range(6):
        params, m = step(params, cohort, jax.random.key(r))
        losses.append(float(m["train_loss_per_step"].mean()))
    assert losses[-1] < losses[0] * 0.7, losses


def test_auto_group_and_block_helpers():
    from fedml_tpu.models.moe import _auto_group
    assert _auto_group(1024) == 512     # largest divisor <= 512
    assert _auto_group(96) == 96        # <= target: itself (loop hit)
    assert _auto_group(1031) == 1031    # prime > target: n_tok fallback
    from fedml_tpu.models.transformer import _auto_block
    assert _auto_block(2048, threshold=1024) == 512
    assert _auto_block(512, threshold=1024) is None   # dense is fine
    assert _auto_block(1031, threshold=1024) is None  # prime, no divisor
