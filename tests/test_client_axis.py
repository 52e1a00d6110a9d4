"""The cohort engine picks its client axis from the model's shapes
(ISSUE 28): `parallel/cohort.choose_client_axis`, what `train_cohort`
does with no engine named, and the paths that keep their own vmap.

The engines themselves are pinned bit-equal by
`test_cross_device.py::test_vmap_vs_scan_client_axis_parity` and
`test_fedavg_oracle.py::test_scan_client_axis_equals_vmap`; here the
default is held to the forced engine the rule names: bit-equal outputs for
a conv model, the same StableHLO text for a model without a convolution
(it must compile the program it compiled before the rule existed).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fedml_tpu.algorithms.cross_device import CrossDevice, CrossDeviceConfig
from fedml_tpu.algorithms.fedavg import FedAvg, FedAvgConfig
from fedml_tpu.data import load_data
from fedml_tpu.data.stacking import gather_cohort
from fedml_tpu.experiments.config import (ExperimentConfig,
                                          config_from_argv)
from fedml_tpu.experiments.models import create_workload, sample_shape_of
from fedml_tpu.parallel.cohort import (choose_client_axis, make_cohort_step,
                                       train_cohort)
from fedml_tpu.trainer.local_sgd import make_local_trainer
from fedml_tpu.trainer.workload import make_client_optimizer

# the CLI's model families: (--model, --dataset, classes, sample shape,
# input dtype, create_workload keywords) -> the engine the rule names
FAMILIES = {
    "lr": ("lr", "mnist", 10, (28, 28, 1), jnp.float32, {}, "vmap"),
    "rnn": ("rnn", "shakespeare", 90, (80,), jnp.int32, {}, "vmap"),
    "rnn_stackoverflow": ("rnn", "stackoverflow_nwp", 10004, (20,),
                          jnp.int32, {}, "vmap"),
    "transformer": ("transformer", "shakespeare", 90, (80,), jnp.int32, {},
                    "vmap"),
    "moe": ("transformer", "shakespeare", 90, (80,), jnp.int32,
            {"moe_experts": 4}, "vmap"),
    "cnn": ("cnn", "femnist", 62, (28, 28, 1), jnp.float32, {}, "scan"),
    "resnet56": ("resnet56", "cifar10", 10, (32, 32, 3), jnp.float32, {},
                 "scan"),
    "mobilenet": ("mobilenet", "cifar10", 10, (32, 32, 3), jnp.float32, {},
                  "scan"),
    "vgg11": ("vgg11", "cifar10", 10, (32, 32, 3), jnp.float32, {}, "scan"),
}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_choose_client_axis_by_model_family(family):
    model, dataset, classes, shape, dtype, kw, want = FAMILIES[family]
    wl = create_workload(model, dataset, classes, shape, **kw)
    x = jax.ShapeDtypeStruct((4,) + shape, dtype)
    # shapes only: the rule reads nothing else
    params = jax.eval_shape(
        lambda x: wl.init(jax.random.key(0), {"x": x}), x)
    assert choose_client_axis(params) == want
    has_rank4 = any(len(leaf.shape) == 4
                    for leaf in jax.tree.leaves(params))
    assert has_rank4 == (want == "scan")


def test_the_knob_is_gone_from_the_cli():
    assert "client_axis" not in ExperimentConfig.__dataclass_fields__
    with pytest.raises(SystemExit):
        config_from_argv(["--client_axis", "scan"])
    assert FedAvgConfig().client_axis is None


# ---------------------------------------------------------------------------
# the default against the forced engine the rule names
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def data():
    # the hermetic FEMNIST twin: 28 x 28 x 1 images, 62 classes
    return load_data("femnist", data_dir=None, batch_size=4, num_clients=12,
                     samples_per_client=10, seed=0)


@pytest.fixture(scope="module")
def cnn(data):
    return create_workload("cnn", "femnist", data.class_num,
                           sample_shape_of(data))


@pytest.fixture(scope="module")
def lr(data):
    return create_workload("lr", "femnist", data.class_num,
                           sample_shape_of(data))


def _cd_cfg(**kw):
    base = dict(comm_round=2, client_num_per_round=6, epochs=1,
                batch_size=4, wave_size=4, seed=0, frequency_of_the_test=10)
    base.update(kw)
    return CrossDeviceConfig(**base)


def _bit_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


def _cohort_inputs(workload, data, n=4):
    local = make_local_trainer(workload, make_client_optimizer("sgd", 0.1),
                               epochs=1)
    cohort = gather_cohort(data.train, np.arange(n))
    sample = jax.tree.map(lambda v: v[0, 0],
                          {k: cohort[k] for k in ("x", "y", "mask")})
    params = workload.init(jax.random.key(0), sample)
    return local, params, cohort, jax.random.key(3)


def test_cnn_wave_engine_default_is_the_sequential_engine(cnn, data):
    default = CrossDevice(cnn, data, _cd_cfg())
    assert default.cfg.client_axis is None
    out = default.run()
    assert default._wave_axis == "scan"
    assert _bit_equal(out, CrossDevice(
        cnn, data, _cd_cfg(client_axis="scan")).run())


def test_cnn_cohort_step_default_is_the_sequential_engine(cnn, data):
    local, params, cohort, rng = _cohort_inputs(cnn, data)
    got = make_cohort_step(local)(params, cohort, rng)
    want = make_cohort_step(local, client_axis="scan")(params, cohort, rng)
    assert _bit_equal(got, want)
    text = {axis: make_cohort_step(local, client_axis=axis).lower(
        params, cohort, rng).as_text() for axis in (None, "vmap", "scan")}
    assert text[None] == text["scan"] != text["vmap"]


def test_cnn_mesh_wave_default_matches_single_chip(cnn, data):
    """The sequential engine inside `make_wave_fn`'s shard_map: each
    device trains its shard of the wave one client after another."""
    from fedml_tpu.parallel.mesh import make_mesh
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 virtual devices (conftest forces 8)")
    mesh = make_mesh(client_axis=2, devices=jax.devices()[:2])
    single = CrossDevice(cnn, data, _cd_cfg()).run()
    sharded = CrossDevice(cnn, data, _cd_cfg(), mesh=mesh)
    assert _bit_equal(single, sharded.run())
    assert sharded._wave_axis == "scan"


def test_model_without_a_convolution_lowers_to_the_vmap_program(lr, data):
    """`lr` (and every rank 1-3 model) compiles what it compiled before
    the rule: the default's StableHLO is the forced ``vmap`` engine's,
    text for text, and is not the sequential engine's."""
    local, params, cohort, rng = _cohort_inputs(lr, data)
    text = {axis: make_cohort_step(local, client_axis=axis).lower(
        params, cohort, rng).as_text() for axis in (None, "vmap", "scan")}
    assert text[None] == text["vmap"] != text["scan"]

    def stacked(axis):
        return jax.jit(lambda p, d, r: train_cohort(
            local, p, d, r, client_axis=axis)).lower(
                params, cohort, rng).as_text()
    assert stacked(None) == stacked("vmap")


def test_lr_wave_engine_default_is_the_vmap_engine(lr, data):
    default = CrossDevice(lr, data, _cd_cfg())
    out = default.run()
    assert default._wave_axis == "vmap"
    assert _bit_equal(out, CrossDevice(
        lr, data, _cd_cfg(client_axis="vmap")).run())


@pytest.mark.parametrize("n_dev", [1, 2])
def test_fedavg_default_follows_the_rule(cnn, data, n_dev):
    """`FedAvg.cohort_step` (`make_cohort_step`, plain and shard_map'd):
    no engine named is the sequential engine for the CNN."""
    from fedml_tpu.parallel.mesh import make_mesh
    if len(jax.devices()) < n_dev:
        pytest.skip("needs 2 virtual devices (conftest forces 8)")
    mesh = (make_mesh(client_axis=2, devices=jax.devices()[:2])
            if n_dev > 1 else None)
    cfg = dict(comm_round=1, client_num_per_round=4, epochs=1, batch_size=4,
               seed=0, frequency_of_the_test=10)
    assert _bit_equal(
        FedAvg(cnn, data, FedAvgConfig(**cfg), mesh=mesh).run(),
        FedAvg(cnn, data, FedAvgConfig(client_axis="scan", **cfg),
               mesh=mesh).run())


@pytest.mark.parametrize("local_alg", ["scaffold", "fednova"])
def test_waves_that_keep_their_own_vmap_refuse_a_forced_scan(lr, data,
                                                             local_alg):
    with pytest.raises(ValueError, match="client_axis"):
        CrossDevice(lr, data, _cd_cfg(local_alg=local_alg,
                                      client_axis="scan"))
    eng = CrossDevice(lr, data, _cd_cfg(local_alg=local_alg, comm_round=1))
    eng.run()
    assert eng._wave_axis == "vmap"
