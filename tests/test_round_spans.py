"""Round spans inside the cross-device engine (ISSUE 27).

One timing site per boundary (`obs.trace.TimedSpan` through
`CrossDevice._span`): the span tree of a round, the ledger phases fed from
the same intervals, the profiler annotations under the span names, the
repaired tracer clock; since ISSUE 35 a wave's rows are staged one wave
ahead on a worker, under `stage.prefetch` (tests/test_stage_prefetch.py
holds the mechanism).  PERF.md section 3 lists every span with the metric
that reads it; tests/test_benchmark_contract.py holds the names the
benchmark's data files read, the programs' module names among them.
"""

import glob
import json
import os

import jax
import numpy as np
import pytest
from conftest import trace_events

from fedml_tpu.algorithms.cross_device import CrossDevice, CrossDeviceConfig
from fedml_tpu.data import load_data
from fedml_tpu.experiments.models import create_workload, sample_shape_of
from fedml_tpu.obs import trace
from fedml_tpu.obs.perf import PerfRecorder
from fedml_tpu.utils.journal import tree_crc

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PHASES = ("wave", "fold", "admission", "barrier_wait", "health")
WAIT_SPANS = {"round.host_copy", "wave.wait", "admission.copy",
              "round.sync"}


@pytest.fixture(scope="module")
def data():
    return load_data("mnist", data_dir=None, batch_size=4, num_clients=24,
                     seed=0)


@pytest.fixture(scope="module")
def workload(data):
    return create_workload("lr", "mnist", data.class_num,
                           sample_shape_of(data))


def _cfg(**kw):
    base = dict(comm_round=2, client_num_per_round=12, epochs=1,
                batch_size=4, wave_size=5, seed=0,
                frequency_of_the_test=10)
    base.update(kw)
    return CrossDeviceConfig(**base)


# ---------------------------------------------------------------------------
# the CLI: --perf writes run_dir/trace.json, one tree a round (`cli_run`,
# tests/conftest.py)
# ---------------------------------------------------------------------------

def _by_round(events):
    roots = [e for e in events if e["name"] == "round"]
    return {r["args"]["round"]: [e for e in events if e["args"]["trace_id"]
                                 == r["args"]["trace_id"]] for r in roots}


def test_every_round_is_one_tree_under_one_root(cli_run):
    rounds = _by_round(cli_run["events"])
    assert sorted(rounds) == [0, 1]
    for members in rounds.values():
        by_id = {e["args"]["span_id"]: e for e in members}
        roots = [e for e in members if e["args"]["parent_id"] is None]
        assert [r["name"] for r in roots] == ["round"]
        for e in members:
            hops = 0
            while e["args"]["parent_id"] is not None:
                parent = by_id[e["args"]["parent_id"]]  # no orphan
                # a child lies inside its parent, on the raw clock;
                # `stage.prefetch` starts inside the round it runs beside
                # and may end across its edge (the worker stages the next
                # round's first wave), and so may `round.crc`: its worker
                # starts it as the round closes and reads the CRC's word
                # once the device program has run
                ends = (e["args"]["t0_ns"] + e["args"]["dur_ns"],
                        parent["args"]["t0_ns"] + parent["args"]["dur_ns"])
                assert e["args"]["t0_ns"] >= parent["args"]["t0_ns"]
                assert e["args"]["t0_ns"] <= ends[1]
                assert ends[0] <= ends[1] or e["name"] in (
                    "stage.prefetch", "round.crc")
                e, hops = parent, hops + 1
                assert hops < 8
            assert e is roots[0]


def _paths(members):
    """(parent's name, name) of every span of one round."""
    by_id = {e["args"]["span_id"]: e for e in members}
    paths = set()
    for e in members:
        parent = by_id.get(e["args"]["parent_id"])
        paths.add((parent["name"] if parent else None, e["name"]))
    return paths


def test_span_names_of_a_round_are_the_contract(cli_run):
    for round_idx, members in _by_round(cli_run["events"]).items():
        paths = _paths(members)
        # the run's first wave is gathered inline, under `wave` (a miss);
        # every later one on the staging worker, under `stage.prefetch`
        # (a hit), and the last round stages nothing past itself
        staging = {("round", "stage.prefetch"),
                   ("stage.prefetch", "stage.gather"),
                   ("stage.prefetch", "stage.put")}
        inline = {("wave", "stage.gather"), ("wave", "stage.put")}
        assert staging <= paths
        assert inline & paths == (inline if round_idx == 0 else set())
        want = {(None, "round"), ("round", "round.sample"),
                ("round", "round.pin"), ("round", "round.host_copy"),
                ("round", "wave"), ("wave", "wave.dispatch"),
                ("wave", "wave.wait"), ("round", "fold_wave"),
                ("fold_wave", "admission.copy"),
                ("fold_wave", "admission.screen"),
                ("fold_wave", "fold.dispatch"), ("fold_wave", "health"),
                ("round", "finalize.dispatch"), ("round", "server_step"),
                ("round", "round.sync"), ("round", "round.crc"),
                ("round", "round.ledger")}
        if cli_run["pipelined"]:
            want.add(("round", "fold.drain"))
        if round_idx > 0:
            # the main thread's wait on the last round's CRC job
            want.add(("round", "round.crc_join"))
        assert want <= paths, want - paths
        assert (("round", "round.crc_join") in paths) == (round_idx > 0)
        for e in members:
            assert (e["args"].get("wait") == "device") \
                == (e["name"] in WAIT_SPANS), e["name"]
    names = {e["name"] for e in cli_run["events"]}
    assert {"setup.data", "setup.init", "eval"} <= names
    # the names the harness patches in stay the harness's own
    hooks = json.load(open(os.path.join(
        ROOT, "benchmark", "hooks", "cross_device.json")))
    assert not names & (set(hooks["spans"]) | {"bench_round"})


def test_fold_wave_hangs_under_its_round_on_either_thread(cli_run):
    for members in _by_round(cli_run["events"]).values():
        root = [e for e in members if e["name"] == "round"][0]
        folds = [e for e in members if e["name"] == "fold_wave"]
        assert len(folds) == 3
        assert {e["args"]["parent_id"] for e in folds} \
            == {root["args"]["span_id"]}
        on_worker = {e["tid"] != root["tid"] for e in folds}
        assert on_worker == {cli_run["pipelined"]}


def test_stage_prefetch_hangs_under_the_round_it_ran_in(cli_run):
    """On the staging worker's thread no span is active: the explicit
    parent ties a prefetch to the round it ran beside, as `fold_wave`'s
    does on the ingest worker.  Round 0 stages its waves 1 and 2 and
    round 1's wave 0; round 1 its waves 1 and 2 and nothing past the
    last round."""
    rounds = _by_round(cli_run["events"])
    for round_idx, members in rounds.items():
        root = [e for e in members if e["name"] == "round"][0]
        staged = [e for e in members if e["name"] == "stage.prefetch"]
        assert len(staged) == (3 if round_idx == 0 else 2)
        for e in staged:
            assert e["args"]["parent_id"] == root["args"]["span_id"]
            assert e["tid"] != root["tid"]
            assert (root["args"]["t0_ns"] <= e["args"]["t0_ns"]
                    <= root["args"]["t0_ns"] + root["args"]["dur_ns"])
            assert "phase" not in e["args"]      # no ledger phase
        # one gather and one put under each, on the worker's thread
        by_parent = {}
        for e in members:
            by_parent.setdefault(e["args"]["parent_id"], []).append(e)
        for e in staged:
            kids = by_parent[e["args"]["span_id"]]
            assert sorted(k["name"] for k in kids) \
                == ["stage.gather", "stage.put"]
            assert {k["tid"] for k in kids} == {e["tid"]}


def test_a_miss_holds_four_children_and_a_hit_two(cli_run):
    """`stage.gather + stage.put + wave.dispatch + wave.wait = wave` on a
    wave gathered inline (the run's first); a hit holds the last two
    and whatever it waited for the worker."""
    events = cli_run["events"]
    waves = sorted((e for e in events if e["name"] == "wave"),
                   key=lambda e: e["args"]["t0_ns"])
    assert len(waves) == 6
    for i, wave in enumerate(waves):
        kids = [e for e in events
                if e["args"]["parent_id"] == wave["args"]["span_id"]]
        names = sorted(k["name"] for k in kids)
        if i == 0:
            assert names == ["stage.gather", "stage.put", "wave.dispatch",
                             "wave.wait"]
        else:
            assert names == ["wave.dispatch", "wave.wait"]
        assert sum(k["args"]["dur_ns"] for k in kids) \
            <= wave["args"]["dur_ns"]


def test_leaf_spans_of_one_thread_do_not_overlap(cli_run):
    events = cli_run["events"]
    parents = {e["args"]["parent_id"] for e in events}
    by_tid = {}
    for e in events:
        if e["args"]["span_id"] not in parents:
            by_tid.setdefault(e["tid"], []).append(
                (e["args"]["t0_ns"], e["args"]["t0_ns"]
                 + e["args"]["dur_ns"], e["name"]))
    for leaves in by_tid.values():
        leaves.sort()
        for (_, end, a), (start, _, b) in zip(leaves, leaves[1:]):
            assert start >= end, (a, b)


def test_each_ledger_phase_is_the_sum_of_its_spans(cli_run):
    """One timing site: a phase of perf.jsonl and the spans tagged with
    it are the same clock readings (the ledger rounds to a microsecond)."""
    rounds = _by_round(cli_run["events"])
    assert len(cli_run["ledger"]) == 2      # one line a round, no other
    for line in cli_run["ledger"]:
        tagged = {}
        for e in rounds[line["round"]]:
            if "phase" in e["args"]:
                tagged[e["args"]["phase"]] = tagged.get(
                    e["args"]["phase"], 0) + e["args"]["dur_ns"]
        assert set(tagged) == set(line["phases"]) <= set(PHASES)
        assert ("barrier_wait" in tagged) == cli_run["pipelined"]
        for phase, ns in tagged.items():
            assert abs(line["phases"][phase] - ns / 1e9) <= 0.51e-6, phase


def test_crc_span_counts_the_globals_bytes(cli_run):
    """`round.crc` carries the global's bytes, which its program reads on
    the device (``on_device`` 1: no copy to the host), and is the CRC
    worker's, parented to the round it closes (lr on MNIST: 784 x 10
    weights and 10 biases in f32)."""
    crcs = [e for e in cli_run["events"] if e["name"] == "round.crc"]
    assert len(crcs) == 2
    assert {e["args"]["bytes"] for e in crcs} == {(784 * 10 + 10) * 4}
    assert {e["args"]["on_device"] for e in crcs} == {1}
    by_id = {e["args"]["span_id"]: e for e in cli_run["events"]}
    for e in crcs:
        root = by_id[e["args"]["parent_id"]]
        assert root["name"] == "round"
        assert e["tid"] != root["tid"]


def test_counts_ride_the_staging_spans(cli_run):
    gathers = [e for e in cli_run["events"] if e["name"] == "stage.gather"]
    puts = [e for e in cli_run["events"] if e["name"] == "stage.put"]
    # still one gather and one put a wave, wherever they ran
    assert len(gathers) == len(puts) == 6
    by_id = {e["args"]["span_id"]: e for e in cli_run["events"]}
    assert sorted(by_id[e["args"]["parent_id"]]["name"] for e in gathers) \
        == sorted(by_id[e["args"]["parent_id"]]["name"] for e in puts) \
        == ["stage.prefetch"] * 5 + ["wave"]
    for g in gathers:
        assert g["args"]["bytes"] > 0
        assert 0 < g["args"]["rows_real"] <= g["args"]["rows_padded"]
    # every wave gathers wave_size slots of S x B rows, live or padded
    # (10 clients in waves of 4: the last wave carries two padded slots)
    padded = {g["args"]["rows_padded"] for g in gathers}
    assert len(padded) == 1 and padded.pop() % 4 == 0


def test_slot_counts_ride_the_dispatch_span(cli_run):
    dispatches = [e for e in cli_run["events"]
                  if e["name"] == "wave.dispatch"]
    assert len(dispatches) == 6
    dispatches.sort(key=lambda e: e["args"]["t0_ns"])
    # every wave but the run's first took its rows from the stager
    # (ISSUE 35): the default sampler's ids are what the worker drew
    assert [d["args"]["slots_prefetched"] for d in dispatches] \
        == [0, 4, 4, 4, 4, 4]
    assert all(d["args"]["slots_staged"] == d["args"]["slots"]
               for d in dispatches)
    for d in dispatches:
        # `lr` holds no convolution: the wave program vmaps its 4 slots
        assert d["args"]["slots"] == 4
        assert d["args"]["slots_sequential"] == 0
        # ... and so computes every client-step it is handed (ISSUE 33)
        assert d["args"]["steps"] > 0 and d["args"]["steps"] % 4 == 0
        assert d["args"]["steps_skipped"] == 0


@pytest.mark.parametrize("local_alg,sequential",
                         [("sgd", 5), ("fednova", 0)])
def test_dispatch_span_says_how_the_wave_ran_its_clients(local_alg,
                                                         sequential,
                                                         tmp_path):
    """``slots_sequential`` is ``slots`` when the wave program trains its
    clients one after another (a conv model on the sgd wave, ISSUE 28),
    else 0 (the fednova wave keeps its own vmap; `lr`, above, holds no
    convolution); the benchmark's `wave_sequential_slot_share` reads
    exactly these names."""
    femnist = load_data("femnist", data_dir=None, batch_size=4,
                        num_clients=12, samples_per_client=10, seed=0)
    wl = create_workload("cnn", "femnist", femnist.class_num,
                         sample_shape_of(femnist))
    perf = PerfRecorder(str(tmp_path / "perf.jsonl"))
    try:
        CrossDevice(wl, femnist, _cfg(comm_round=1, local_alg=local_alg),
                    perf=perf).run()
    finally:
        perf.close()
    dispatches = [s for s in perf.tracer.spans
                  if s["name"] == "wave.dispatch"]
    assert len(dispatches) == 3      # 12 clients in waves of 5
    assert [(s["args"]["slots"], s["args"]["slots_sequential"])
            for s in dispatches] == [(5, sequential)] * 3
    spec = json.load(open(os.path.join(
        ROOT, "benchmark", "layer_metrics",
        "wave_sequential_slot_share.json")))
    assert spec["reader"] == "benchmark.span_readers:arg_share"
    assert spec["args"] == {"name": "wave.dispatch",
                            "part": "slots_sequential", "whole": "slots"}


# rows of the six clients of `ragged_femnist`, B=4: 5 steps a slot, of
# which 1, 3, 5, 1, 3, 1 hold a row
RAGGED_ROWS = (3, 9, 17, 4, 12, 1)


@pytest.fixture(scope="module")
def ragged_femnist():
    import dataclasses
    from fedml_tpu.data.stacking import stack_client_data
    femnist = load_data("femnist", data_dir=None, batch_size=4,
                        num_clients=len(RAGGED_ROWS), samples_per_client=20,
                        seed=0)
    rng = np.random.RandomState(0)
    xs = [rng.rand(n, 28, 28, 1).astype(np.float32) for n in RAGGED_ROWS]
    ys = [rng.randint(0, femnist.class_num, n).astype(np.int32)
          for n in RAGGED_ROWS]
    return dataclasses.replace(femnist,
                               train=stack_client_data(xs, ys, batch_size=4))


@pytest.mark.parametrize("model,local_alg,epochs,want", [
    # all six clients in waves of 4: slots 0-3, then slots 4-5 and two
    # padded ones; a wave is handed 4 x 5 x epochs client-steps and skips
    # those of them with no row, all of a padded slot's among them
    ("cnn", "sgd", 1, [(20, 20 - (1 + 3 + 5 + 1)), (20, 20 - (3 + 1))]),
    ("cnn", "sgd", 2, [(40, 40 - 2 * 10), (40, 40 - 2 * 4)]),
    ("cnn", "fedprox", 1, [(20, 10), (20, 16)]),
    # a vmapped wave computes both branches of every step
    ("lr", "sgd", 1, [(20, 0), (20, 0)]),
    # ... and these two waves keep a vmap and a local loop of their own
    ("cnn", "scaffold", 1, [(20, 0), (20, 0)]),
    ("cnn", "fednova", 1, [(20, 0), (20, 0)]),
])
def test_dispatch_span_counts_the_steps_the_trainer_skips(
        ragged_femnist, model, local_alg, epochs, want, tmp_path):
    """``steps`` and ``steps_skipped`` on `wave.dispatch` (ISSUE 33) are
    the hand-counted numbers of a partition with known row counts."""
    data = ragged_femnist
    assert data.train["mask"].shape[1:] == (5, 4)
    wl = create_workload(model, "femnist", data.class_num,
                         sample_shape_of(data))
    perf = PerfRecorder(str(tmp_path / "perf.jsonl"))
    try:
        CrossDevice(wl, data, _cfg(
            comm_round=1, client_num_per_round=6, wave_size=4,
            epochs=epochs, local_alg=local_alg), perf=perf).run()
    finally:
        perf.close()
    assert [(s["args"]["steps"], s["args"]["steps_skipped"])
            for s in perf.tracer.spans if s["name"] == "wave.dispatch"] \
        == want


def test_skipped_step_share_reads_the_dispatch_span_as_data():
    spec = json.load(open(os.path.join(
        ROOT, "benchmark", "layer_metrics", "wave_skipped_step_share.json")))
    assert spec["reader"] == "benchmark.span_readers:arg_share"
    assert spec["args"] == {"name": "wave.dispatch",
                            "part": "steps_skipped", "whole": "steps"}
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    assert {
        "name": "wave_skipped_step_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "staging and local training",
        "moves": "round_s", "workloads": ["resnet56_cifar10.silos10",
                                           "glm47_flash.silos2",
                                           "keye_vl2_30b_a3b.silos2",
                                           "laguna_xs2.silos2"]} \
        in bench["per_layer"]


def test_prefetch_hit_share_reads_the_dispatch_span_as_data():
    spec = json.load(open(os.path.join(
        ROOT, "benchmark", "layer_metrics",
        "stage_prefetch_hit_share.json")))
    assert spec["reader"] == "benchmark.span_readers:arg_share"
    # the whole is `slots` under a name a program before ISSUE 35 does
    # not carry: `arg_share` picks its spans by the whole's name and
    # raises on one that lacks the part, which the parent's would
    assert spec["args"] == {"name": "wave.dispatch",
                            "part": "slots_prefetched",
                            "whole": "slots_staged"}
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    assert {
        "name": "stage_prefetch_hit_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "staging and local training",
        "moves": "round_s", "workloads": ["resnet56_cifar10.silos10",
                                           "glm47_flash.silos2",
                                           "keye_vl2_30b_a3b.silos2",
                                           "laguna_xs2.silos2"]} \
        in bench["per_layer"]


def test_export_keeps_wall_ts_and_raw_monotonic_clock(cli_run):
    other = cli_run["doc"]["otherData"]
    assert other["clock"] == "perf_counter_ns"
    assert other["dropped_spans"] == 0
    for e in cli_run["events"]:
        wall_us = (other["anchor_wall_ns"] + e["args"]["t0_ns"]
                   - other["anchor_mono_ns"]) // 1000
        assert e["ts"] == wall_us
        assert e["dur"] == e["args"]["dur_ns"] // 1000


# ---------------------------------------------------------------------------
# the engine: same bits with and without the sites; profiler annotations
# ---------------------------------------------------------------------------

def test_global_crc_is_the_same_with_perf_on_and_off(workload, data,
                                                     tmp_path):
    plain = CrossDevice(workload, data, _cfg()).run()
    perf = PerfRecorder(str(tmp_path / "perf.jsonl"))
    try:
        spanned = CrossDevice(workload, data, _cfg(), perf=perf).run()
    finally:
        perf.close()
    crc = tree_crc(jax.tree.map(np.asarray, spanned))
    assert crc == tree_crc(jax.tree.map(np.asarray, plain))
    with open(tmp_path / "perf.jsonl") as f:
        assert json.loads(f.readlines()[-1])["global_crc"] == crc
    assert any(s["name"] == "wave.wait" for s in perf.tracer.spans)


def test_perf_alone_does_not_turn_on_header_propagation(tmp_path):
    perf = PerfRecorder(str(tmp_path / "perf.jsonl"))
    perf.close()
    assert perf.tracer is not None and trace.get_tracer() is None


def test_spans_are_profiler_annotations_on_the_host_plane(workload, data,
                                                          tmp_path):
    from jax.profiler import ProfileData
    perf = PerfRecorder(str(tmp_path / "perf.jsonl"))
    try:
        with jax.profiler.trace(str(tmp_path / "prof")):
            CrossDevice(workload, data, _cfg(), perf=perf).run()
    finally:
        perf.close()
    path = glob.glob(str(tmp_path / "prof" / "plugins" / "profile" / "*"
                         / "*.xplane.pb"))[0]
    host = [p for p in ProfileData.from_file(path).planes
            if p.name == "/host:CPU"]
    names = {e.name for p in host for line in p.lines for e in line.events}
    assert {s["name"] for s in perf.tracer.spans} <= names
    assert {"round", "wave", "stage.prefetch", "stage.gather", "stage.put",
            "wave.dispatch", "wave.wait", "fold_wave", "finalize.dispatch",
            "round.sync"} <= names


@pytest.mark.parametrize("held", [True, False])
def test_crc_join_says_whether_the_main_thread_waited(workload, data,
                                                      tmp_path, held):
    """`round.crc_join`: ``waited`` 1 where the worker still held the job
    (a stub `_crc_of` on a gate), 0 where it had ended; no ledger phase,
    and not a wait on the device."""
    import threading

    class OpenedByTheJoin:
        """The job, whose gate opens only once the join waits on it."""
        def __init__(self, job):
            self.job = job

        def done(self):
            return self.job.done()

        def result(self):
            gate.set()
            return self.job.result()

    perf = PerfRecorder(str(tmp_path / "perf.jsonl"))
    gate = threading.Event()
    try:
        eng = CrossDevice(workload, data, _cfg(), perf=perf)
        eng._crc_of = lambda params, ctx: gate.wait(10.0) and 7
        job = eng._start_crc({})
        if held:
            eng._crc_job = OpenedByTheJoin(job)
        else:
            gate.set()
            job.exception()                     # ended before the join
        assert eng._join_crc() == 7
        eng._stop_staging()
    finally:
        gate.set()
        perf.close()
    [join] = [s for s in perf.tracer.spans if s["name"] == "round.crc_join"]
    assert join["args"] == {"wait": "worker", "joins": 1,
                            "waited": int(held)}


def test_sites_without_a_recorder_keep_nothing(workload, data):
    """The disabled path: one branch to the shared null context."""
    eng = CrossDevice(workload, data, _cfg())
    assert eng._span("wave", "wave", eng._h_wave) is trace.NULL_CONTEXT
    assert trace.child("stage.gather") is trace.NULL_CONTEXT


def test_degrade_reads_the_wave_interval_without_a_recorder(workload, data):
    from fedml_tpu.robust.degrade import ReliabilityTracker
    tracker = ReliabilityTracker(data.client_num)
    eng = CrossDevice(workload, data, _cfg(comm_round=1), degrade=tracker)
    assert eng._tracer is None
    eng.run()
    assert any(len(lat) and all(v > 0 for v in lat)
               for lat in tracker._lat.values())


# ---------------------------------------------------------------------------
# the tracer: clock, cap, ambient child
# ---------------------------------------------------------------------------

def test_tracer_clock_is_monotonic_ns_with_a_wall_anchor():
    ticks = iter(range(1000, 10 ** 6, 250))
    tr = trace.SpanTracer(clock=lambda: next(ticks))   # anchor reads 1000
    with trace.TimedSpan(tr, "outer"):
        with trace.child("inner") as inner:
            inner.set(rows=3)
    inner_rec, outer_rec = tr.spans
    assert (outer_rec["t0_ns"], outer_rec["dur_ns"]) == (1250, 750)
    assert (inner_rec["t0_ns"], inner_rec["dur_ns"]) == (1500, 250)
    assert inner_rec["parent_id"] == outer_rec["span_id"]
    assert inner_rec["args"] == {"rows": 3}
    outer_event = tr.to_trace_events()[1]
    assert outer_event["ts"] == (tr._anchor[0] + 250) // 1000
    assert trace.child("after") is trace.NULL_CONTEXT


def test_kept_spans_are_capped_newest_kept(monkeypatch, tmp_path):
    monkeypatch.setattr(trace, "MAX_SPANS", 4)
    tr = trace.SpanTracer()
    for i in range(7):
        tr.record_span(f"s{i}", 0.001)
    assert [s["name"] for s in tr.spans] == ["s3", "s4", "s5", "s6"]
    tr.export(str(tmp_path / "t.json"))
    doc, events = trace_events(str(tmp_path / "t.json"))
    assert doc["otherData"]["dropped_spans"] == 3 and len(events) == 4


def test_timed_span_feeds_phase_and_histogram_from_one_interval(tmp_path):
    class Hist:
        seen = []

        def observe(self, v):
            self.seen.append(v)

    perf = PerfRecorder(str(tmp_path / "perf.jsonl"))
    try:
        perf.round_start(0)
        with trace.TimedSpan(perf.tracer, "fold.dispatch", perf, "fold",
                             Hist()) as site:
            pass
        line = perf.round_end(0)
    finally:
        perf.close()
    rec = perf.tracer.spans[-1]
    assert rec["args"] == {"phase": "fold"}
    assert Hist.seen == [rec["dur_ns"] / 1e9] == [site.seconds]
    assert line["phases"] == {"fold": round(rec["dur_ns"] / 1e9, 6)}


def test_child_takes_tracer_and_ledger_from_the_open_site(tmp_path):
    """How library code (the staging, the aggregator's finalize) joins
    the caller's round: no tracer in its signature."""
    class Hist:
        def __init__(self):
            self.seen = []

        def observe(self, v):
            self.seen.append(v)

    perf = PerfRecorder(str(tmp_path / "perf.jsonl"))
    under, alone = Hist(), Hist()
    try:
        perf.round_start(0)
        with trace.TimedSpan(perf.tracer, "round", perf) as root:
            with trace.child("finalize.dispatch", phase="fold", hist=under):
                pass
        line = perf.round_end(0)
    finally:
        perf.close()
    rec = perf.tracer.spans[0]
    assert rec["name"] == "finalize.dispatch"
    assert rec["parent_id"] == root.span.span_id
    assert under.seen == [rec["dur_ns"] / 1e9]
    assert line["phases"] == {"fold": round(rec["dur_ns"] / 1e9, 6)}
    # no site open: the histogram alone is fed, nothing is kept
    with trace.child("finalize.dispatch", phase="fold", hist=alone) as site:
        assert site.span is None
    assert len(alone.seen) == 1 and len(perf.tracer.spans) == 2


def test_trace_json_is_one_runs_own(tmp_path):
    """A file left by an earlier run is moved aside when the recorder
    starts, like the ledger; a recorder that rides the process tracer
    (--trace_dir, written per node by main()) writes none."""
    stale = tmp_path / "trace.json"
    stale.write_text("{}")
    perf = PerfRecorder(str(tmp_path / "perf.jsonl"))
    assert not stale.exists()
    assert (tmp_path / "trace.json.prev").read_text() == "{}"
    perf.close()                      # no span recorded: no file
    assert not stale.exists()
    shared = trace.enable()
    try:
        perf = PerfRecorder(str(tmp_path / "perf.jsonl"))
        assert perf.tracer is shared
        with trace.TimedSpan(perf.tracer, "round", perf):
            pass
        perf.close()
    finally:
        trace.disable()
    assert shared.spans and not stale.exists()
