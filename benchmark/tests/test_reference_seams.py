"""The seams a configuration's files reach the harness through (PR 29): the
task it states, the count of required operations it may export, and the
kept globals that leave the chip.  CPU, by hand:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_reference_seams.py -q
"""

import copy
import glob
import math
import os
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

from benchmark import flops, run  # noqa: E402
from benchmark.probe import RoundProbe  # noqa: E402
from benchmark.reference import fedavg, tasks  # noqa: E402
from benchmark.tests.tiny.configs import fed_shakespeare_moe as lm  # noqa: E402
import rehearse  # noqa: E402

CONFIG_FILES = sorted(
    glob.glob(os.path.join(ROOT, "benchmark", "configs", "*.json"))
    + glob.glob(os.path.join(HERE, "tiny", "configs", "*.json")))
LM_CONFIG = os.path.join(HERE, "tiny", "configs", "fed_shakespeare_moe.json")


# -- the task ------------------------------------------------------------------

@pytest.mark.parametrize("row_mask", [(1.0, 1.0), (1.0, 0.0), (0.0, 0.0)])
def test_next_token_loss_against_a_loop(row_mask):
    rng = np.random.default_rng(5)
    logits = rng.normal(size=(2, 5, 7)).astype(np.float32)
    targets = np.asarray([[3, 6, 1, 0, 0], [2, 0, 5, 4, 0]], np.int32)
    total, weight = 0.0, 0
    for b in range(2):
        for t in range(5):
            if row_mask[b] and targets[b, t] != 0:
                z = logits[b, t].astype(np.float64)
                total += math.log(np.exp(z).sum()) - z[targets[b, t]]
                weight += 1
    s, w = tasks.next_token(logits, targets, np.asarray(row_mask, np.float32),
                            pad_id=0)
    assert float(w) == weight == int(3 * row_mask[0] + 3 * row_mask[1])
    assert float(s) == pytest.approx(total, rel=1e-5, abs=1e-6)
    # another pad id leaves other positions out
    _, w4 = tasks.next_token(logits, targets, np.ones(2, np.float32),
                             pad_id=4)
    assert float(w4) == 9


def test_row_classification_loss_against_a_loop():
    rng = np.random.default_rng(6)
    logits = rng.normal(size=(4, 5)).astype(np.float32)
    y = np.asarray([0, 4, 2, 2], np.int32)
    mask = np.asarray([1, 1, 0, 1], np.float32)
    total = sum(math.log(np.exp(logits[i].astype(np.float64)).sum())
                - logits[i, y[i]] for i in range(4) if mask[i])
    s, w = tasks.row_classification(logits, y, mask)
    assert float(w) == 3 and float(s) == pytest.approx(total, rel=1e-5)


@pytest.mark.parametrize("path", CONFIG_FILES, ids=os.path.basename)
def test_every_configuration_states_its_task_and_none_is_defaulted(path):
    config = run.load_json(path)
    task = tasks.from_config(config)
    assert callable(task.loss) and task.eval_rows > 0
    assert task.eval_by in tasks.EVAL_BY
    assert task.clip_norm is None or task.clip_norm > 0
    bare = {k: v for k, v in config.items() if k != "task"}
    with pytest.raises(KeyError, match="'task'"):
        tasks.from_config(bare)
    for key in ("loss", "clip_norm", "eval_rows", "eval_by"):
        cut = copy.deepcopy(config)
        del cut["task"][key]
        with pytest.raises(KeyError, match=key):
            tasks.from_config(cut)


def test_a_task_with_no_implementation_is_refused_by_name():
    config = run.load_json(LM_CONFIG)
    config["task"]["loss"] = "benchmark.reference.tasks:tag_prediction"
    with pytest.raises(ValueError, match="task.loss.*tag_prediction"):
        tasks.from_config(config)
    config["task"]["loss"] = "benchmark.reference.tasks:next_token"
    config["task"]["eval_by"] = "wave"
    with pytest.raises(ValueError, match="task.eval_by"):
        tasks.from_config(config)


@pytest.mark.parametrize("key,value", [("client_optimizer", "adam"),
                                       ("server_opt", "fedadam"),
                                       ("epochs", 2)])
def test_the_reference_refuses_what_it_does_not_follow(key, value):
    bench = rehearse.tiny_bench("shakespeare")
    cell = run.Cell(bench, rehearse.CELLS["shakespeare"][2])
    fedavg.refuse_unfollowed(cell.cli)      # as committed: followed
    called = []
    cell.reference = types.SimpleNamespace(
        build_model=lambda c: called.append(c))
    stated = dict(cell.cli)
    cell.cli = {**stated, key: value}
    with pytest.raises(ValueError, match=key):
        run.follow_reference(cell, clients=[], pseed=0)
    cell.cli = {k: v for k, v in stated.items() if k != key}
    with pytest.raises(KeyError, match=key):
        run.follow_reference(cell, clients=[], pseed=0)
    assert not called      # before any model is built, let alone a round


def test_the_evaluation_is_cut_by_population_or_by_client():
    clients = [(np.arange(5.0)[:, None], np.arange(5)),
               (np.zeros((0, 1)), np.zeros(0, int)),
               (10 + np.arange(3.0)[:, None], 10 + np.arange(3))]
    pop = list(fedavg.eval_batches(clients, 4, "population"))
    assert [b[1].tolist() for b in pop] == [[0, 1, 2, 3], [4, 10, 11, 12]]
    assert all(b[2].tolist() == [1, 1, 1, 1] for b in pop)
    by = list(fedavg.eval_batches(clients, 4, "client"))
    assert [b[1].tolist() for b in by] == [[0, 1, 2, 3], [4, 0, 0, 0],
                                           [10, 11, 12, 0]]
    assert [b[2].tolist() for b in by] == [[1, 1, 1, 1], [1, 0, 0, 0],
                                           [1, 1, 1, 0]]
    assert by[1][0].shape == (4, 1)


# -- required operations ---------------------------------------------------------

def _lm(experts: int):
    config = run.load_json(LM_CONFIG)
    config["model"]["experts"] = experts
    return config, lm.build_model(config)


def test_the_walk_takes_integer_inputs():
    import jax
    config, moe = _lm(4)
    shapes = jax.eval_shape(lambda: moe.init(
        jax.random.key(0), np.zeros((4, 80), np.int32))["params"])
    assert (sum(v.size for v in jax.tree.leaves(shapes))
            == config["model"]["parameters"])
    _, model = _lm(0)
    walked = flops.forward_flops_per_sample(model, (80,), "int32")
    assert walked > 0
    assert walked == flops.forward_flops_per_sample(model, (80,), np.int32)


def test_an_exported_count_is_read_and_its_absence_is_walked():
    walk_only = types.SimpleNamespace(build_model=lm.build_model)
    config, dense = _lm(0)
    walked = flops.train_flops_per_sample(walk_only, config, (80,), "int32")
    assert walked == 3 * flops.forward_flops_per_sample(dense, (80,),
                                                        "int32")
    counted = flops.train_flops_per_sample(lm, config, (80,), "int32")
    assert counted == 6 * lm.forward_macs_per_sample(config, (80,))
    # a dense model requires what its plain reference computes
    assert abs(counted / walked - 1) < 0.01, (counted, walked)
    # with experts the reference computes all four for every token and
    # the model requires one: the walk reads high, the count does not
    config4, _ = _lm(4)
    walked4 = flops.train_flops_per_sample(walk_only, config4, (80,), "int32")
    counted4 = flops.train_flops_per_sample(lm, config4, (80,), "int32")
    assert walked4 / counted4 > 1.5
    assert counted4 == pytest.approx(counted, rel=0.01)   # the router's
    # the two image references export nothing and are walked, as before
    from benchmark.configs import femnist_cnn, resnet56_cifar10
    for module, shape, flops_a_row in ((femnist_cnn, (28, 28, 1), 71994624.0),
                                       (resnet56_cifar10, (32, 32, 3),
                                        523287552.0)):
        assert not hasattr(module, "forward_macs_per_sample")
        cfg = run.load_json(os.path.join(
            ROOT, "benchmark", "configs",
            module.__name__.rsplit(".", 1)[1] + ".json"))
        assert flops.train_flops_per_sample(module, cfg, shape) == flops_a_row


# -- the kept globals --------------------------------------------------------------

def test_the_probe_keeps_host_copies_the_program_cannot_reach():
    import jax
    import jax.numpy as jnp

    donated = jax.jit(lambda t: jax.tree.map(lambda v: v + 1.0, t),
                      donate_argnums=0)

    def one_round(self, params, ids):
        return donated(params), {"n": len(ids)}

    spec = {"state_arg": 1, "cohort_arg": 2, "state_out": 0}
    probe = RoundProbe(spec, first=3, n_window=1, keep=3)
    hooked = probe.wrap(one_round)
    params = {"w": jnp.zeros((3, 2)), "b": {"c": jnp.ones(4)}}
    for r in range(probe.rounds_needed):
        params, _ = hooked(None, params, [r])
        # g1 and g3, the globals the comparison reads; g2 is not kept
        assert sorted(probe.states_out) == [1, 3][:1 + (r >= 2)]
        kept = [probe.state_in] + list(probe.states_out.values())
        for leaf in jax.tree.leaves(kept):
            assert type(leaf) is np.ndarray and leaf.flags.owndata
            assert not isinstance(leaf, jax.Array)
    # the values are those of their rounds, whatever the program did to
    # its own buffers since
    assert probe.state_in["w"].tolist() == np.zeros((3, 2)).tolist()
    assert {j: s["b"]["c"][0] for j, s in probe.states_out.items()} == {
        1: 2.0, 3: 4.0}
    assert float(params["w"][0, 0]) == probe.rounds_needed
    assert len(probe.window()["edges_mono"]) == 2
    with pytest.raises(ValueError, match="keep"):
        RoundProbe(spec, first=1, n_window=1, keep=2)
    # nothing is kept where nothing is asked for
    idle = RoundProbe(spec, first=1, n_window=1, keep=0)
    idle.wrap(one_round)(None, params, [0])
    assert idle.state_in is None and idle.states_out == {}


# -- the tiny next-token configuration's data ----------------------------------------

def test_shakespeare_files_load_through_the_programs_loader(tmp_path):
    from fedml_tpu.data.tff_h5 import load_fed_shakespeare
    from fedml_tpu.data.text import CHAR_VOCAB
    assert list(lm.CHARS) == CHAR_VOCAB and lm.VOCAB == 90
    args = dict(clients=5, snippets=2, min_chars=30, max_chars=400)
    a = lm.fed_shakespeare_arrays(7, **args)
    b = lm.fed_shakespeare_arrays(7, **args)
    c = lm.fed_shakespeare_arrays(8, **args)
    assert a == b and a["train"] != c["train"]
    # the same lengths for every seed, so the same rows and steps
    assert (sorted(len(s) for cl in a["train"] for s in cl)
            == sorted(len(s) for cl in c["train"] for s in cl))
    a["train"][0][0] = a["train"][0][0][:-3] + "\t~\t"    # unknown characters
    lm.write_fed_shakespeare_h5(a, str(tmp_path))
    fd = load_fed_shakespeare(str(tmp_path), batch_size=4)
    clients = lm.train_clients(a, {}, 0)
    assert fd.client_num == 5 and fd.class_num == lm.VOCAB
    for i, (x, y) in enumerate(clients):
        n = len(y)
        assert x.shape == (n, 80) and x.dtype == np.int32
        assert fd.train["num_samples"][i] == n
        assert np.array_equal(fd.train["x"][i].reshape(-1, 80)[:n], x)
        assert np.array_equal(fd.train["y"][i].reshape(-1, 80)[:n], y)
    assert clients[0][0].max() == lm.OOV and clients[0][0][0, 0] == lm.BOS
    assert sum((y == lm.PAD).any() for _, y in clients) == 5
