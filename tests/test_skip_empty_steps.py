"""A local step whose batch holds no row is not computed (ISSUE 33).

`trainer/local_sgd.make_local_trainer` branches around an empty batch with
a `lax.cond`: run in sequence (one client's jit, the cohort engine's
`lax.scan` over a conv model's clients) that is a real conditional, under
`jax.vmap` JAX's batching rule turns it into the select over both branches
that the trainer used to spell itself, and a trainer given ``grad_reduce``
(a collective every shard must enter) keeps the unconditional step.

`_parent_trainer` below is the step as it was before the branch (compute,
then `jnp.where` over every carry), kept as the reference: nothing a real
step computes, no key it draws and no state it carries may differ from it
by a bit, in either lowering.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from fedml_tpu.algorithms.cross_device import CrossDevice, CrossDeviceConfig
from fedml_tpu.data import load_data
from fedml_tpu.data.stacking import gather_cohort
from fedml_tpu.device_cohort.waves import make_wave_fn
from fedml_tpu.experiments.models import create_workload, sample_shape_of
from fedml_tpu.parallel.cohort import train_cohort
from fedml_tpu.trainer.local_sgd import make_local_trainer
from fedml_tpu.trainer.workload import make_client_optimizer

B, STEPS = 4, 5
# rows held by each client of the ragged population: 5, 3, 1, 4, 0, 2, 5, 1
# steps of B=4 hold a row; client 4 is empty
ROWS = (18, 9, 2, 13, 0, 7, 20, 4)
REAL_STEPS = tuple(-(-n // B) for n in ROWS)


def _parent_trainer(workload, optimizer, epochs, prox_mu=0.0):
    """`make_local_trainer`'s step before ISSUE 33: every step computed,
    a fully padded one thrown away by a select over every carry."""
    clip = (optax.clip_by_global_norm(workload.grad_clip_norm)
            if workload.grad_clip_norm is not None else None)
    grad_fn = jax.value_and_grad(
        lambda trained, batch, rng: workload.loss_fn(trained, batch, rng,
                                                     True), has_aux=True)

    def train(params, data, rng):
        opt_state = optimizer.init(params)
        clip_state = clip.init(params) if clip is not None else None
        num_steps = jax.tree.leaves(data)[0].shape[0]

        def step(carry, step_idx):
            trained, opt_state, rng = carry
            rng, dropout_rng = jax.random.split(rng)
            batch = jax.tree.map(lambda x: x[step_idx % num_steps], data)
            (loss, _), grads = grad_fn(trained, batch, dropout_rng)
            if prox_mu:
                grads = jax.tree.map(lambda g, p, p0: g + prox_mu * (p - p0),
                                     grads, trained, params)
            if clip is not None:
                grads, _ = clip.update(grads, clip_state)
            updates, new_opt = optimizer.update(grads, opt_state, trained)
            new_trained = optax.apply_updates(trained, updates)
            got_data = jnp.sum(batch["mask"]) > 0
            keep = lambda n, o: jax.tree.map(
                lambda a, b: jnp.where(got_data, a, b), n, o)
            return (keep(new_trained, trained), keep(new_opt, opt_state),
                    rng), loss

        (trained, _, _), losses = jax.lax.scan(
            step, (params, opt_state, rng), jnp.arange(epochs * num_steps))
        return trained, {"train_loss_per_step": losses}

    return train


@pytest.fixture(scope="module")
def data():
    """The hermetic FEMNIST twin, made ragged: 8 clients of `ROWS` rows in
    5 steps of 4, so the empty steps trail each client's real ones."""
    full = load_data("femnist", data_dir=None, batch_size=B,
                     num_clients=len(ROWS), samples_per_client=STEPS * B,
                     seed=0)
    train = {k: np.array(v[:, :STEPS]) for k, v in full.train.items()
             if k != "num_samples"}
    assert train["mask"].shape == (len(ROWS), STEPS, B)
    train["mask"] = (np.arange(STEPS * B) < np.asarray(ROWS)[:, None]
                     ).astype(np.float32).reshape(train["mask"].shape)
    train["num_samples"] = np.asarray(ROWS, np.float32)
    return dataclasses.replace(full, train=train)


@pytest.fixture(scope="module")
def cnn(data):
    # CNNDropOut: convolutions (the sequential client axis) and dropout
    return create_workload("cnn", "femnist", data.class_num,
                           sample_shape_of(data))


@pytest.fixture(scope="module")
def lr(data):
    return create_workload("lr", "femnist", data.class_num,
                           sample_shape_of(data))


def _client(data, c, steps=None):
    return {k: jnp.asarray(data.train[k][c][:steps])
            for k in ("x", "y", "mask")}


def _init(workload, data):
    return workload.init(jax.random.key(0), jax.tree.map(
        lambda v: v[0], _client(data, 0)))


def _bit_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _primitives(jaxpr):
    return {eqn.primitive.name for eqn in _eqns(jaxpr)}


def _conds(jaxpr):
    return [eqn for eqn in _eqns(jaxpr) if eqn.primitive.name == "cond"]


# ---------------------------------------------------------------------------
# (a) trailing empty batches: the client trains to what its real steps alone
# train it to
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("opt", ["sgd", "adam"])
@pytest.mark.parametrize("epochs", [1, 2])
def test_padded_client_trains_to_its_real_steps(lr, data, epochs, opt):
    """Client 1 holds 9 rows: 3 real steps, then 2 empty ones, which at
    ``epochs=2`` lie BETWEEN the epochs (steps 3, 4, 8, 9 of 10).  Adam is
    the optimizer an empty step would move: its eps drifts the params on
    a zero gradient."""
    train = jax.jit(make_local_trainer(
        lr, make_client_optimizer(opt, 0.05), epochs))
    params, rng = _init(lr, data), jax.random.key(7)
    k = REAL_STEPS[1]
    padded, m_pad = train(params, _client(data, 1), rng)
    cut, m_cut = train(params, _client(data, 1, k), rng)
    assert _bit_equal(padded, cut)
    assert not _bit_equal(padded, params)
    losses = np.asarray(m_pad["train_loss_per_step"]).reshape(epochs, STEPS)
    assert np.array_equal(
        losses[:, :k],
        np.asarray(m_cut["train_loss_per_step"]).reshape(epochs, k))
    assert np.all(losses[:, :k] > 0) and np.all(losses[:, k:] == 0)


def test_dropout_keys_of_the_real_steps_do_not_move(cnn, data):
    """The key chain advances outside the branch: real step j draws the
    j-th key with or without empty steps behind it (``epochs=1``: the cut
    client's chain is the padded client's first three links)."""
    train = jax.jit(make_local_trainer(
        cnn, make_client_optimizer("sgd", 0.05), 1))
    params, rng = _init(cnn, data), jax.random.key(7)
    padded, _ = train(params, _client(data, 1), rng)
    cut, _ = train(params, _client(data, 1, REAL_STEPS[1]), rng)
    assert _bit_equal(padded, cut)
    other_key, _ = train(params, _client(data, 1), jax.random.key(8))
    assert not _bit_equal(padded, other_key)     # dropout is live


@pytest.mark.parametrize("opt,prox_mu", [("sgd", 0.0), ("adam", 0.0),
                                         ("sgd", 0.1)])
@pytest.mark.parametrize("epochs", [1, 2])
def test_one_client_equals_the_parent_trainer(cnn, data, epochs, opt,
                                              prox_mu):
    """Dropout, two epochs, empty steps between them: every key the
    parent's chain drew on a real step is drawn on it still."""
    optimizer = make_client_optimizer(opt, 0.05)
    params, rng = _init(cnn, data), jax.random.key(7)
    for c in (1, 4):        # three real steps of five; no row at all
        got, m = jax.jit(make_local_trainer(
            cnn, optimizer, epochs, prox_mu=prox_mu))(
                params, _client(data, c), rng)
        want, m_parent = jax.jit(_parent_trainer(
            cnn, optimizer, epochs, prox_mu=prox_mu))(
                params, _client(data, c), rng)
        assert _bit_equal(got, want)
        real = np.tile(np.arange(STEPS) < REAL_STEPS[c], epochs)
        assert np.array_equal(
            np.asarray(m["train_loss_per_step"])[real],
            np.asarray(m_parent["train_loss_per_step"])[real])
    assert _bit_equal(got, params)      # the empty client stays the global


# ---------------------------------------------------------------------------
# (b) what each lowering compiles
# ---------------------------------------------------------------------------

def _wave_program(workload, data, client_axis=None):
    local = make_local_trainer(workload, make_client_optimizer("sgd", 0.05),
                               1)

    def make_stacked(params, wave_data, rng, offset):
        stacked, _ = train_cohort(local, params, wave_data, rng,
                                  index_offset=offset,
                                  client_axis=client_axis)
        return stacked, {}

    wave_fn = make_wave_fn(make_stacked)
    args = (_init(workload, data), gather_cohort(data.train, np.arange(4)),
            jax.random.key(3), jnp.int32(0))
    return wave_fn, args


def test_conv_wave_program_branches_around_the_convolutions(cnn, data):
    wave_fn, args = _wave_program(cnn, data)
    conds = _conds(jax.make_jaxpr(wave_fn)(*args).jaxpr)
    assert len(conds) == 1
    skip, take = conds[0].params["branches"]      # index 0 is `False`
    assert "conv_general_dilated" in _primitives(take.jaxpr)
    assert not {"conv_general_dilated", "dot_general"} \
        & _primitives(skip.jaxpr)
    text = wave_fn.lower(*args).as_text()
    assert "stablehlo.case" in text
    # the compiler keeps it a conditional (CPU here; the chip's program is
    # read from its trace, PERF.md section 5)
    assert "conditional(" in wave_fn.lower(*args).compile().as_text()


@pytest.mark.parametrize("client_axis", [None, "vmap"])
def test_vmapped_wave_program_holds_no_conditional(lr, cnn, data,
                                                   client_axis):
    """A model without a convolution (the default engine vmaps it), and
    the CNN forced under ``vmap``: a `cond` on a batched predicate is a
    select over both branches, the program of before."""
    workload = lr if client_axis is None else cnn
    wave_fn, args = _wave_program(workload, data, client_axis)
    jaxpr = jax.make_jaxpr(wave_fn)(*args).jaxpr
    assert not _conds(jaxpr)
    assert "select_n" in _primitives(jaxpr)
    text = wave_fn.lower(*args).as_text()
    assert "stablehlo.case" not in text and "stablehlo.if" not in text


# ---------------------------------------------------------------------------
# (c) the two lowerings on ragged clients
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("opt,epochs", [("sgd", 1), ("sgd", 2), ("adam", 2)])
def test_vmap_and_sequential_agree_on_ragged_clients(cnn, data, opt, epochs):
    """All eight ragged clients and two padded slots, under both engines
    and under the parent's trainer vmapped: three stacks, one set of
    bits.  A padded slot and the empty client come back as the global."""
    optimizer = make_client_optimizer(opt, 0.05)
    local = make_local_trainer(cnn, optimizer, epochs)
    params, rng = _init(cnn, data), jax.random.key(3)
    cohort = gather_cohort(data.train, np.arange(len(ROWS)), pad_to=10)

    def run(trainer, axis):
        return jax.jit(lambda p, d, r: train_cohort(
            trainer, p, d, r, client_axis=axis))(params, cohort, rng)

    seq, m_seq = run(local, "scan")
    par, m_par = run(local, "vmap")
    parent, _ = run(_parent_trainer(cnn, optimizer, epochs), "vmap")
    assert _bit_equal(seq, par) and _bit_equal(seq, parent)
    # the engine drops this metric; an empty step reports 0 in both
    assert _bit_equal(m_seq, m_par)
    losses = np.asarray(m_seq["train_loss_per_step"]).reshape(
        10, epochs, STEPS)
    for c, k in enumerate(REAL_STEPS + (0, 0)):
        assert np.all(losses[c, :, :k] > 0) and np.all(losses[c, :, k:] == 0)
    for c in (4, 8, 9):
        assert _bit_equal(jax.tree.map(lambda x: x[c], seq), params)
    assert not _bit_equal(jax.tree.map(lambda x: x[2], seq), params)


# ---------------------------------------------------------------------------
# (d) the mesh wave: both branches vary over the clients axis
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("local_alg,client_optimizer",
                         [("sgd", "sgd"), ("sgd", "adam"),
                          ("fedprox", "sgd")])
def test_mesh_wave_matches_the_one_chip_wave(cnn, data, local_alg,
                                             client_optimizer):
    """`make_wave_fn`'s shard_map keeps ``check_vma`` on: the skipped
    branch's zero loss is made from the mask, so it varies as the taken
    branch's does.  Six ragged clients in waves of four (two padded
    slots), each device training its two slots in sequence."""
    from fedml_tpu.parallel.mesh import make_mesh
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 virtual devices (conftest forces 8)")
    cfg = CrossDeviceConfig(
        comm_round=2, client_num_per_round=6, epochs=2, batch_size=B,
        wave_size=4, seed=0, frequency_of_the_test=10, lr=0.05,
        local_alg=local_alg, client_optimizer=client_optimizer)
    mesh = make_mesh(client_axis=2, devices=jax.devices()[:2])
    single = CrossDevice(cnn, data, cfg).run()
    sharded = CrossDevice(cnn, data, cfg, mesh=mesh)
    assert _bit_equal(single, sharded.run())
    assert sharded._wave_axis == "scan"


# ---------------------------------------------------------------------------
# (e) a collective inside the step: no branch
# ---------------------------------------------------------------------------

def test_trainer_given_grad_reduce_keeps_the_unconditional_step(lr, data):
    optimizer = make_client_optimizer("adam", 0.05)
    reduced = make_local_trainer(lr, optimizer, 2, grad_reduce=lambda g: g)
    args = (_init(lr, data), _client(data, 1), jax.random.key(7))
    jaxpr = jax.make_jaxpr(reduced)(*args).jaxpr
    assert not _conds(jaxpr)
    assert _conds(jax.make_jaxpr(
        make_local_trainer(lr, optimizer, 2))(*args).jaxpr)
    got, m = jax.jit(reduced)(*args)
    want, m_parent = jax.jit(_parent_trainer(lr, optimizer, 2))(*args)
    assert _bit_equal(got, want)
    # and reports the loss the workload gives an empty batch, as before
    assert _bit_equal(m, m_parent)
