"""The benchmark's own tests.  CPU, run by hand from the checkout's root:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

(the tier-1 command collects ``tests/`` only).  The rehearsal tests drive a
whole run at a tiny size behind ``rehearse.py``; each takes about a minute.
``test_reference_seams.py`` holds the tests of what a configuration's files
state (task, required operations) and of the kept globals.
"""

import contextlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

from benchmark import datagen, flops, layer_readers, run, trace_reduce  # noqa: E402
from benchmark.configs import femnist_cnn, resnet56_cifar10  # noqa: E402
import rehearse  # noqa: E402


# -- flops -------------------------------------------------------------------

@pytest.mark.parametrize("module,shape,low,high,params", [
    # the CNN is all convolutions and matrix products: within 1 %
    (femnist_cnn, (28, 28, 1), 0.99, 1.0, 1206590),
    # ResNet-56's 57 GroupNorms, ReLUs and residual adds are 6.4 % of
    # XLA's count; the model-FLOPs figure leaves element-wise work out
    (resnet56_cifar10, (32, 32, 3), 0.92, 0.95, 591322),
])
def test_flops_agree_with_xla_cost_analysis(module, shape, low, high, params):
    import jax
    import jax.numpy as jnp
    cfg = run.load_json(os.path.join(
        ROOT, "benchmark", "configs",
        module.__name__.rsplit(".", 1)[1] + ".json"))
    model = module.build_model(cfg)
    x = jnp.zeros((8,) + shape)
    p = model.init(jax.random.key(0), x)["params"]
    assert sum(v.size for v in jax.tree.leaves(p)) == params
    assert cfg["model"]["parameters"] == params
    xla = jax.jit(lambda p, x: model.apply({"params": p}, x)).lower(
        p, x).compile().cost_analysis()["flops"] / 8
    ours = flops.forward_flops_per_sample(model, shape)
    assert low <= ours / xla <= high, (ours, xla)


# -- datagen -----------------------------------------------------------------

def test_femnist_files_load_through_the_programs_loader(tmp_path):
    from fedml_tpu.data.tff_h5 import load_federated_emnist
    a = datagen.femnist_arrays(7, writers=5, min_train=6, max_train=14)
    b = datagen.femnist_arrays(7, writers=5, min_train=6, max_train=14)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    c = datagen.femnist_arrays(8, writers=5, min_train=6, max_train=14)
    assert not np.array_equal(a["y_train"][:6], c["y_train"][:6])
    counts = np.diff(a["off_train"])
    assert counts.min() == 6 and counts.max() == 14
    datagen.write_femnist_h5(a, str(tmp_path))
    fd = load_federated_emnist(str(tmp_path), batch_size=4)
    clients = datagen.femnist_clients(a)
    assert fd.client_num == 5 and fd.class_num == 62
    for i, (x, y) in enumerate(clients):
        n = len(y)
        assert fd.train["num_samples"][i] == n
        assert np.array_equal(
            fd.train["x"][i].reshape(-1, 28, 28, 1)[:n], x)
        assert np.array_equal(fd.train["y"][i].reshape(-1)[:n], y)
    assert 0.0 <= a["x_train"].min() and a["x_train"].max() <= 1.0


def test_cifar_files_load_through_the_programs_loader(tmp_path):
    from fedml_tpu.data.cifar import load_cifar_partitioned
    a = datagen.cifar10_arrays(3, train=400, test=40)
    b = datagen.cifar10_arrays(3, train=400, test=40)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert np.bincount(a["y_train"]).tolist() == [40] * 10
    datagen.write_cifar10_pickles(a, str(tmp_path))
    fd = load_cifar_partitioned("cifar10", str(tmp_path), 4, "hetero", 0.5,
                                8, seed=5)
    clients = datagen.cifar10_clients(a, 4, 0.5, 5)
    for i, (x, y) in enumerate(clients):
        n = len(y)
        assert fd.train["num_samples"][i] == n
        assert np.array_equal(
            fd.train["x"][i].reshape(-1, 32, 32, 3)[:n], x)
        assert np.array_equal(fd.train["y"][i].reshape(-1)[:n], y)
    # the split's sizes depend on the partition seed alone, not on the data
    other = datagen.cifar10_clients(
        datagen.cifar10_arrays(4, train=400, test=40), 4, 0.5, 5)
    assert [len(y) for _, y in other] == [len(y) for _, y in clients]


# -- window arithmetic ---------------------------------------------------------

def test_window_metrics_on_recorded_stamps():
    edges = [10.0, 11.0, 12.5, 13.0, 14.0, 16.0]
    m = run.window_metrics(edges, [100, 200, 100, 100, 100], setup_s=42.0)
    assert m["round_s"] == pytest.approx(6.0 / 5)
    assert m["samples_per_s"] == pytest.approx(600 / 6.0)
    assert m["setup_s"] == 42.0
    assert layer_readers.gap_percentile({"edges": edges}, 95) == \
        pytest.approx(float(np.percentile([1.0, 1.5, 0.5, 1.0, 2.0], 95)))
    assert layer_readers.gap_percentile({"edges": [1.0, 4.0]}, 95) == 3.0
    assert layer_readers.gap_percentile({"edges": [1.0]}, 95) is None


def test_program_seed_is_folded_or_pinned():
    assert run.program_seed(2 ** 31 + 12, {"cli": {}}) == 13
    assert run.program_seed(5, {"cli": {"seed": 8}}) == 8


# -- trace reduction and readers ------------------------------------------------

def _recorded():
    with open(os.path.join(HERE, "data", "trace_events.json")) as f:
        return [tuple(e) for e in json.load(f)["events"]]


def test_trace_reduce_on_a_recorded_trace():
    events = _recorded()
    r = trace_reduce.reduce(events, program="jit_wave_fn")
    ops = [(s, s + d) for p, l, n, s, d in events
           if l == trace_reduce.OPS_LINE]
    lo, hi = min(a for a, _ in ops), max(b for _, b in ops)
    assert r["devices"] == 1
    assert 0 < r["busy_s"] <= r["extent_s"] == pytest.approx((hi - lo) / 1e9)
    # busy + every gap = the extent
    merged = trace_reduce._union(ops)
    gaps = sum(b[0] - a[1] for a, b in zip(merged, merged[1:]))
    assert r["busy_s"] + gaps / 1e9 == pytest.approx(r["extent_s"])
    assert r["program_runs"] >= 1 and 0 < r["program_s"] <= r["busy_s"]
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10
    assert r["device_ops"][0][1] >= r["device_ops"][-1][1]
    assert trace_reduce.reduce([e for e in events if e[1] == "host"]) == {}


def test_trace_reduce_names_a_gap_by_the_host_span_over_it():
    dev, ops = "/device:TPU:0", trace_reduce.OPS_LINE
    events = [(dev, ops, "fusion.1", 0.0, 100.0),
              (dev, ops, "fusion.2", 1100.0, 100.0),
              (dev, ops, "fusion.3", 1250.0, 50.0),
              (dev, trace_reduce.MODULES_LINE, "jit_wave_fn(1)", 1100.0, 200.0),
              (trace_reduce.HOST_PLANE, "host", "gather_cohort", 150.0, 900.0)]
    r = trace_reduce.reduce(events, program="jit_wave_fn")
    assert r["idle_gaps"][0] == ["gather_cohort", 1000.0 / 1e9]
    assert r["idle_gaps"][1] == ["unattributed", 50.0 / 1e9]
    assert r["program_gap_s"] == pytest.approx(1000.0 / 1e9)
    assert r["program_s"] == pytest.approx(150.0 / 1e9)
    assert r["busy_s"] == pytest.approx(250.0 / 1e9)
    assert r["rounds"] == 0 and r["window_s"] == r["extent_s"]


def test_trace_reduce_cuts_to_the_annotated_round_cycles():
    dev, ops = "/device:TPU:0", trace_reduce.OPS_LINE
    host = trace_reduce.HOST_PLANE
    events = [(dev, ops, "before.1", 0.0, 100.0),      # the profiler's start
              (dev, ops, "fusion.1", 450.0, 100.0),    # straddles the open
              (dev, ops, "fusion.2", 1100.0, 100.0),
              (dev, ops, "fusion.3", 2300.0, 100.0),
              (dev, ops, "after.1", 2900.0, 500.0),    # straddles the close
              (dev, trace_reduce.MODULES_LINE, "jit_wave_fn(1)", 1100.0, 100.0),
              (dev, trace_reduce.MODULES_LINE, "jit_wave_fn(1)", 2300.0, 100.0),
              (host, "host", "bench_round", 500.0, 1500.0),
              (host, "host", "bench_round", 2000.0, 1000.0),
              (host, "host", "gather_cohort", 600.0, 450.0)]
    r = trace_reduce.reduce(events, program="jit_wave_fn",
                            window="bench_round")
    assert r["rounds"] == 2
    assert r["window_s"] == pytest.approx(2500.0 / 1e9)
    assert r["extent_s"] == pytest.approx(3400.0 / 1e9)
    # 50 of fusion.1, fusion.2, fusion.3 and 100 of after.1
    assert r["busy_s"] == pytest.approx(350.0 / 1e9)
    gaps = sum(g for _, g in r["idle_gaps"])
    assert r["busy_s"] + gaps == pytest.approx(r["window_s"])
    assert r["idle_gaps"][0] == ["unattributed", 1100.0 / 1e9]
    assert r["idle_gaps"][1] == ["gather_cohort", 550.0 / 1e9]
    assert r["program_gap_s"] == pytest.approx((550.0 + 1100.0) / 1e9)
    assert "before.1" not in [k for k, _ in r["device_ops"]]
    assert trace_reduce.reduce(events[:1] + events[7:],
                               window="bench_round") == {}


def test_readers_return_nothing_where_there_is_nothing_to_read():
    ctx = {"perf_lines": [{"phases": {"wave": 1.0, "fold": 0.25}},
                          {"phases": {"wave": 3.0, "fold": 0.25}}],
           "n_rounds": 2, "window_s": 5.0, "samples": [10, 10],
           "train_flops_per_sample": 1e9, "epochs": 1, "chips": 1,
           "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes": 16e9},
           "traced_samples": [10], "edges": [0.0, 2.0, 5.0],
           "memory_peak_bytes": 4e9, "trace": {}}
    assert layer_readers.phase_per_round(ctx, ["wave"]) == 2.0
    assert layer_readers.phase_per_round(ctx, ["fold", "barrier_wait"]) == 0.25
    assert layer_readers.phase_per_round(ctx, ["health"]) is None
    assert layer_readers.round_other(ctx, ["wave", "fold"]) == 0.25
    assert layer_readers.mfu_of_window(ctx) == pytest.approx(0.4)
    assert layer_readers.mfu_of_program(ctx) is None
    assert layer_readers.device_idle_share(ctx) is None
    assert layer_readers.trace_per_round(ctx, "program_gap_s") is None
    assert layer_readers.peak_hbm_share(ctx) == 25.0
    ctx["trace"] = {"busy_s": 4.0, "window_s": 5.0, "rounds": 2,
                    "program_s": 1.0, "program_gap_s": 0.5}
    assert layer_readers.device_idle_share(ctx) == pytest.approx(20.0)
    assert layer_readers.mfu_of_program(ctx) == pytest.approx(1.0)
    assert layer_readers.trace_per_round(ctx, "program_gap_s") == 0.25


def test_every_metric_of_benchmark_json_has_its_files():
    bench = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for m in bench["per_layer"]:
        spec = run.load_json(os.path.join(
            ROOT, "benchmark", "layer_metrics", m["name"] + ".json"))
        assert callable(run.call(spec["reader"]))
    for w in bench["workloads"]:
        cell = run.Cell(bench, w["name"])
        assert set(cell.limits) and cell.reference.build_model(cell.config)


# -- the gate -------------------------------------------------------------------

def test_run_exits_nonzero_without_a_tpu_and_prints_no_result():
    bench = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", bench["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "needs a TPU" in r.stderr


# -- a whole run at a tiny size, sound and broken ----------------------------------

def _unchanged(original):
    def run_round(self, params, ids, round_rng, round_idx):
        _, info = original(self, params, ids, round_rng, round_idx)
        return params, info
    return run_round


def _half_batch(original):
    def gather(stacked, client_ids, pad_to=None):
        out = original(stacked, client_ids, pad_to=pad_to)
        keep = (np.arange(out["mask"].shape[-1]) % 2 == 0)
        out["mask"] = out["mask"] * keep.astype(np.float32)
        return out
    return gather


PAD_ID = 0     # the task's pad id in the next-token configurations


def _half_tokens(original):
    def gather(stacked, client_ids, pad_to=None):
        out = original(stacked, client_ids, pad_to=pad_to)
        out["y"] = out["y"].at[..., 1::2].set(PAD_ID)
        return out
    return gather


FAULTS = {
    "sound": None,
    # a step that returns its state unchanged
    "state_unchanged": (
        "fedml_tpu.algorithms.cross_device:CrossDevice._run_round",
        _unchanged),
    # half of each batch left out, the mean taken over the rest
    "half_batch": ("fedml_tpu.algorithms.cross_device:gather_cohort",
                   _half_batch),
    # every other target the pad, which the loss leaves out: the half of a
    # batch of one row (a next-token task's only)
    "half_tokens": ("fedml_tpu.algorithms.cross_device:gather_cohort",
                    _half_tokens),
    # the control: the program's own lower-precision path switched on
    "control_bfloat16": ("--compute_dtype", "bfloat16"),
}
# the number that has to refuse each fault, whatever else does: the norm of
# the difference of the two changes after three rounds, over the whole tree
REFUSED_BY = {"state_unchanged": "change3_diff", "half_batch": "change3_diff",
              "half_tokens": "change3_diff", "control_bfloat16": "change3_diff"}
SOUND_AT_MOST = 1e-4    # every compared number of a sound run, on the CPU
NEXT_TOKEN = ("shakespeare",)


@pytest.mark.parametrize("fault,which", [
    (fault, which) for fault in FAULTS for which in rehearse.CELLS
    if fault != "half_tokens" or which in NEXT_TOKEN])
def test_rehearsal_is_correct_only_when_sound(fault, which, capfd,
                                              monkeypatch):
    from benchmark.probe import patched
    if which == "resnet56":   # a CPU round of it takes 6 s: a short window
        monkeypatch.setattr(run, "MIN_ROUNDS", 2)
    spec = FAULTS[fault]
    extra = spec if fault.startswith("control") else ()
    ctx = (patched(spec[0], spec[1])
           if spec and not fault.startswith("control")
           else contextlib.nullcontext())
    with ctx:
        result = rehearse.rehearse(which, seed=2147483659, extra=extra)
    capfd.readouterr()
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device", "compared"}
    assert list(result)[-1] == "compared"
    assert result["correct"] is (fault == "sound"), result["compared"]
    if fault == "sound":
        assert all(row["value"] <= SOUND_AT_MOST
                   for row in result["compared"].values()), result["compared"]
    else:
        row = result["compared"][REFUSED_BY[fault]]
        assert row["value"] > row["limit"], (fault, result["compared"])
    assert result["failed"] == 0 and result["attempted"] >= run.MIN_ROUNDS
    assert set(result["metrics"]) == {"round_s", "samples_per_s", "setup_s"}


def test_chip_limits_holds_one_program_set_and_one_reference_set(
        tmp_path, monkeypatch, capfd):
    """``chip_limits.main`` on the tiny indexed expert cell, one seed with
    the control and both faults: each set of globals it compares is let go
    before the next is made, so no more than the reference's and one
    other set are alive whenever a set is made; the program is correct,
    the control and ``half_tokens`` are not."""
    import weakref
    import jax
    import chip_limits
    import rehearse_keye   # noqa: F401  the tiny Keye cell in rehearse.CELLS
    monkeypatch.setattr(chip_limits, "ROOT", str(tmp_path))
    monkeypatch.setattr(run, "check_device", lambda chips: None)
    made = []     # a weak reference to one leaf of each set made, in order
    alive = []    # how many sets were alive as each was made

    def tracked(f):
        def call(*a, **kw):
            out = f(*a, **kw)
            made.append(weakref.ref(jax.tree.leaves(out["states"][0])[0]))
            alive.append(sum(r() is not None for r in made))
            return out
        return call

    monkeypatch.setattr(run, "timed_call", tracked(run.timed_call))
    monkeypatch.setattr(run, "follow_reference", tracked(run.follow_reference))
    cell = rehearse.CELLS["keye"][2]
    seed = 2147483659
    chip_limits.main(cell, [seed], [seed], [seed],
                     bench=rehearse.tiny_bench("keye"))
    capfd.readouterr()
    with open(tmp_path / "chiprun_out" / f"limits.{cell}.jsonl") as f:
        rows = {r["kind"]: r for r in map(json.loads, f)}
    assert list(rows) == ["program", "control", "fault:half_batch",
                          "fault:half_tokens"]
    # program, reference, control, two faults: the reference and one more
    assert alive == [1, 2, 2, 2, 2], alive
    assert rows["program"]["correct"], rows["program"]
    assert not rows["control"]["correct"], rows["control"]
    assert "change3_diff" in rows["fault:half_tokens"]["failed_on"]
    assert all(r["peak_rss_bytes"] > 0 for r in rows.values())
