"""The wave engine carries a GB-size tree (ISSUE 37).

Where nothing reads one client's result, the wave program trains its
clients in sequence and only their slot-order weighted sum leaves it
(`train_cohort_sum`, `make_summed_wave_fn`, `StreamingAggregator.fold_sum`);
the admission screen reads a few numbers the program computes beside its
summary (`admission_stats`); where a reader needs the global on the host,
the round keeps one copy of it (the CRC worker's, which is the next
round's `round.host_copy`).  Held here:
(a) `choose_client_axis` by size; (b) the summed round against the stacked
round: the same bits for one wave a round, to rounding for three; (c) the
device-side screen gives the host walk's verdicts, case by case; (d) the
CRC is the old one and a run's sequence does not depend on the path, and
the second round's host copy is the first round's, not a transfer.
"""

import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.algorithms import cross_device
from fedml_tpu.algorithms.cross_device import CrossDevice, CrossDeviceConfig
from fedml_tpu.core.stream_agg import StreamingAggregator
from fedml_tpu.data import load_data
from fedml_tpu.data.stacking import gather_cohort
from fedml_tpu.device_cohort import WaveAdmission, admission_stats
from fedml_tpu.experiments.models import create_workload, sample_shape_of
from fedml_tpu.obs.health import HealthAccumulator
from fedml_tpu.obs.perf import PerfRecorder
from fedml_tpu.parallel.cohort import (choose_client_axis,
                                       wave_outgrows_device)
from fedml_tpu.utils.journal import tree_crc

N = 10


# ---------------------------------------------------------------------------
# (a) the client axis by size
# ---------------------------------------------------------------------------

DENSE = {"w": jax.ShapeDtypeStruct((1000, 250), jnp.float32)}   # 1 MB
CONV = {"k": jax.ShapeDtypeStruct((3, 3, 4, 4), jnp.float32)}


@pytest.mark.parametrize("tree,wave,device,axis", [
    (DENSE, 2, None, "vmap"),             # no count kept: the shape rule
    (DENSE, 2, 14 * 10 ** 6, "vmap"),     # 7 trees are half of 14 MB
    (DENSE, 2, 14 * 10 ** 6 - 2, "scan"),  # ... and more than half of less
    (DENSE, 1, 8 * 10 ** 6 - 2, "scan"),  # one client: 4 trees
    (DENSE, 64, 10 ** 9, "vmap"),
    (CONV, 2, None, "scan"),              # a conv kernel, whatever the size
    (CONV, 2, 10 ** 12, "scan"),
], ids=["no_count", "fits", "outgrows", "one_client", "large_device",
        "conv", "conv_large_device"])
def test_choose_client_axis_by_size(tree, wave, device, axis):
    assert choose_client_axis(tree, wave, device) == axis
    if tree is DENSE:
        assert wave_outgrows_device(tree, wave, device) == (axis == "scan")


def test_a_gb_tree_in_waves_of_two_trains_in_sequence():
    """The reading in `choose_client_axis`'s docstring: 591 M float32
    parameters on a 16 GB chip."""
    tree = {"w": jax.ShapeDtypeStruct((591294976,), jnp.float32)}
    assert choose_client_axis(tree, 2, 16909336064) == "scan"
    assert choose_client_axis(tree, 2, None) == "vmap"


# ---------------------------------------------------------------------------
# (b) summed against stacked
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def data():
    """The ResNet cell's traffic at a size the CPU runs: ten silos of
    unequal size, CIFAR-shaped rows."""
    return load_data("cifar10", data_dir=None, batch_size=8, num_clients=N,
                     client_num=N, seed=0)


@pytest.fixture(scope="module")
def workload(data):
    return create_workload("cnn_fedavg", "cifar10", data.class_num,
                           sample_shape_of(data))


def _cfg(**kw):
    base = dict(comm_round=3, client_num_per_round=N, epochs=1,
                batch_size=8, wave_size=N, seed=0, frequency_of_the_test=10,
                client_axis="scan")
    base.update(kw)
    return CrossDeviceConfig(**base)


def _rounds(workload, data, cfg, summed, **kw):
    """The global after every round, with the wave as its sum or
    stacked."""
    out = []
    eng = CrossDevice(workload, data, cfg, publish=lambda params, version:
                      out.append(jax.tree.map(np.asarray, params)), **kw)
    if not summed:
        eng._summed_fn = None
    eng.run()
    assert eng._summed == summed
    return out, eng


@pytest.mark.parametrize("local_alg", ["sgd", "fedprox"])
def test_one_wave_summed_is_the_stacked_fold_bit_for_bit(workload, data,
                                                         local_alg):
    cfg = _cfg(local_alg=local_alg)
    a, _ = _rounds(workload, data, cfg, True)
    b, _ = _rounds(workload, data, cfg, False)
    assert len(a) == 3
    for x, y in zip(a, b):
        assert tree_crc(x) == tree_crc(y)


def test_three_waves_stay_stacked_for_a_small_tree_and_sum_for_a_large(
        workload, data, monkeypatch):
    """Several waves a round: the sums would be added wave by wave, so a
    tree that can be stacked is (the slot-order bits), and one that
    cannot is summed, to rounding."""
    cfg = _cfg(wave_size=4)
    small, eng = _rounds(workload, data, cfg, True)
    stacked, _ = _rounds(workload, data, cfg, False)
    assert not eng._summed_any_round
    assert [tree_crc(x) for x in small] == [tree_crc(y) for y in stacked]
    monkeypatch.setattr(cross_device, "device_memory_bytes", lambda: 10 ** 6)
    large, eng = _rounds(workload, data, cfg, True)
    assert eng._summed_any_round
    for x, y in zip(large, stacked):
        for p, q in zip(jax.tree.leaves(x), jax.tree.leaves(y)):
            np.testing.assert_allclose(p, q, rtol=1e-4, atol=1e-6)


def test_the_sum_is_refused_where_uploads_are_read(workload, data):
    for kw in ({"norm_clip": 1.0}, {"agg_noise_std": 0.1},
               {"wave_adversary": "0:0:scale:10"}):
        assert CrossDevice(workload, data, _cfg(**kw))._summed_fn is None
    agg = StreamingAggregator({"w": jnp.zeros(3)}, norm_clip=1.0)
    agg.reset({"w": jnp.zeros(3)})
    with pytest.raises(RuntimeError, match="pre-summed"):
        agg.fold_sum({"w": jnp.ones(3)}, [1.0], 1.0)


def test_fold_sum_counts_as_fold_wave():
    ref = {"w": jnp.arange(4, dtype=jnp.float32)}
    stacked = {"w": jnp.stack([ref["w"] + i for i in range(3)])}
    w = np.asarray([2.0, 0.0, 3.0], np.float32)
    a, b = StreamingAggregator(ref), StreamingAggregator(ref)
    a.reset(ref), b.reset(ref)
    a.fold_wave(stacked, w)
    b.fold_sum({"w": jnp.sum(stacked["w"] * w[:, None], axis=0)}, w,
               jnp.float32(5.0))
    assert (a.count, a.weight_total) == (b.count, b.weight_total) == (2, 5.0)
    np.testing.assert_array_equal(a.finalize(0)["w"], b.finalize(0)["w"])


# ---------------------------------------------------------------------------
# (c) the screen on the device's statistics
# ---------------------------------------------------------------------------

def _both(adm_host, adm_dev, mean, g):
    """One summary through the host walk and through the device's
    statistics: the two verdicts."""
    host = adm_host.screen(mean, g)
    try:
        stats = jax.device_get(admission_stats(
            jax.tree.map(jnp.asarray, mean), jax.tree.map(jnp.asarray, g)))
    except (ValueError, TypeError):
        stats = {"finite": True, "leaf_sumsq": np.zeros(1)}  # not reached
    dev = adm_dev.screen(jax.tree.map(jnp.asarray, mean), stats=stats)
    return host, dev


def test_device_statistics_give_the_host_walks_verdicts():
    """`tests/test_cross_device.py::test_wave_admission_screens`, every
    case, through both."""
    tmpl = {"w": np.zeros(8, np.float32)}
    g = {"w": np.zeros(8, np.float32)}
    host = WaveAdmission(tmpl, norm_k=2.0, norm_min_history=3)
    dev = WaveAdmission(tmpl, norm_k=2.0, norm_min_history=3)
    host.round_start(), dev.round_start()
    cases = [({"w": np.zeros(4, np.float32)}, "fingerprint"),
             ({"w": np.full(8, np.nan, np.float32)}, "nonfinite"),
             ({"w": np.full(8, np.inf, np.float32)}, "nonfinite")]
    cases += [({"w": np.full(8, s / np.sqrt(8), np.float32)}, None)
              for s in (1.0, 1.05, 0.95, 1.02)]
    cases += [({"w": np.full(8, 50.0, np.float32)}, "norm_outlier")]
    for mean, reason in cases:
        a, b = _both(host, dev, mean, g)
        assert a.ok == b.ok == (reason is None)
        assert a.reason == b.reason == reason
        if a.norm is not None:
            assert b.norm == pytest.approx(a.norm, rel=1e-6)
    assert host.rejected == dev.rejected
    assert host.admitted == dev.admitted == 4
    host.round_start(), dev.round_start()
    a, b = _both(host, dev, {"w": np.full(8, 50.0, np.float32)}, g)
    assert a.ok and b.ok


def test_a_structure_is_fingerprinted_without_leaving_the_device():
    from fedml_tpu.robust.admission import params_fingerprint
    tree = {"a": {"k": np.zeros((2, 3), np.float32)},
            "b": np.zeros(4, np.int32)}
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                          tree)
    assert params_fingerprint(shapes) == params_fingerprint(tree) \
        == params_fingerprint(jax.tree.map(jnp.asarray, tree))


@pytest.mark.parametrize("summed", [True, False], ids=["summed", "stacked"])
def test_a_nonfinite_wave_is_rejected_on_the_devices_word(workload, data,
                                                          summed):
    """One silo's rows turned to NaN: its wave's sum (or mean) is not
    finite, the wave is discarded, the global stays finite."""
    bad = {k: np.array(v) for k, v in data.train.items()}
    bad["x"][3] = np.nan
    poisoned = type(data)(client_num=data.client_num,
                          class_num=data.class_num, train=bad)
    eng = CrossDevice(workload, poisoned, _cfg(comm_round=1, wave_size=5))
    if summed:
        eng._summed_any_round = None    # set below, once bound
        bound = eng._ensure_bound

        def ensure(params):
            bound(params)
            eng._summed_any_round = True
        eng._ensure_bound = ensure
    else:
        eng._summed_fn = None
    params = eng.run()
    assert eng.admission.rejected["nonfinite"] == 1
    assert eng.admission.admitted == 1
    assert all(np.isfinite(x).all() for x in jax.tree.leaves(params))


def test_the_attack_round_folds_what_it_screened(workload, data):
    """The poison seam reads the stacked rows: a run with an attack
    planned keeps the stacked program, and the poisoned wave that passes
    the screen moves the global."""
    clean = CrossDevice(workload, data, _cfg(comm_round=1)).run()
    eng = CrossDevice(workload, data, _cfg(
        comm_round=1, wave_adversary="0:0:sign_flip:1", admission="off"))
    attacked = eng.run()
    assert not eng._summed and eng.admission.admitted == 1
    assert tree_crc(jax.device_get(clean)) != tree_crc(
        jax.device_get(attacked))


# ---------------------------------------------------------------------------
# (d) one host copy a global
# ---------------------------------------------------------------------------

def test_tree_crc_is_crc32_of_the_leaves_bytes():
    tree = {"a": np.arange(7, dtype=np.float32).reshape(7, 1)[::2],
            "b": np.float32(3.5), "c": np.zeros((0, 2), np.int32),
            "d": jnp.arange(6, dtype=jnp.bfloat16)}
    crc = 0
    for leaf in jax.tree.leaves(tree):
        crc = zlib.crc32(np.ascontiguousarray(np.asarray(leaf)).tobytes(),
                         crc)
    assert tree_crc(tree) == crc


def _ledger_crcs(tmp_path, name, workload, data, cfg, **kw):
    import json
    path = tmp_path / f"{name}.jsonl"
    perf = PerfRecorder(str(path))
    try:
        eng = CrossDevice(workload, data, cfg, perf=perf, **kw)
        if name == "stacked":
            eng._summed_fn = None
        eng.run()
    finally:
        perf.close()
    return [json.loads(line)["global_crc"] for line in open(path)], eng


def test_a_runs_crc_sequence_is_the_stacked_paths(workload, data, tmp_path):
    a, _ = _ledger_crcs(tmp_path, "summed", workload, data, _cfg())
    b, _ = _ledger_crcs(tmp_path, "stacked", workload, data, _cfg())
    assert len(a) == 3 and a == b and len(set(a)) == 3


def test_host_copy_after_round_0_is_the_crcs_copy(workload, data, tmp_path,
                                                  monkeypatch):
    """With the health sketch reading the global on the host (so
    ``needs_host``): round 0 transfers it once for `round.host_copy`,
    every later round is handed the copy the CRC worker took of it, the
    same arrays."""
    gets, seen = [], []
    real = jax.device_get

    def counting(tree):
        out = real(tree)
        if isinstance(tree, dict) and len(jax.tree.leaves(tree)) > 4:
            gets.append(out)
        return out

    monkeypatch.setattr(cross_device.jax, "device_get", counting)
    health = HealthAccumulator(ledger_path=str(tmp_path / "health.jsonl"))
    start = health.round_start

    def round_start(round_idx, host_params, **kw):
        seen.append(host_params)
        return start(round_idx, host_params, **kw)

    health.round_start = round_start
    _, eng = _ledger_crcs(tmp_path, "mirror", workload, data, _cfg(),
                          health=health)
    # a round's transfers of a whole global: the wave's mean for the
    # sketch, the new global for `health.round_end` and the worker's
    # copy of it for the next round (its CRC is the device's); round 0
    # has the host copy besides
    assert len(seen) == 3 and len(gets) == 3 * 3 + 1
    crc_copies = [g for g in gets if any(g is s for s in seen[1:])]
    assert len(crc_copies) == 2
    assert eng._mirror is None          # dropped with the run
