"""Staging one wave ahead in the cross-device engine (ISSUE 35).

Once a wave's program is dispatched, one worker thread gathers and hands
over the next wave's rows: the same round's, or the first wave of the next
round as the sampler draws it.  The loop takes them only if they are the
rows it now asks for.  Held here: (a) the global after every round is
bit-identical with staging ahead on and off; (b) which waves hit and which
miss, and that a miss computes what the inline engine computes; (c) the
worker's life: nothing staged past the last round, no thread left behind,
its exceptions raised on the main thread; (d) staged rows are never written
between staging and use.  The span side (`stage.prefetch`,
`slots_prefetched`) is tests/test_round_spans.py's.
"""

import sys
import threading

import jax
import numpy as np
import pytest

from fedml_tpu.algorithms import cross_device
from fedml_tpu.algorithms.cross_device import CrossDevice, CrossDeviceConfig
from fedml_tpu.comm.ingest import IngestPipeline
from fedml_tpu.core.sampling import sample_clients
from fedml_tpu.data import load_data
from fedml_tpu.experiments.models import create_workload, sample_shape_of
from fedml_tpu.obs.health import HealthAccumulator
from fedml_tpu.obs.perf import PerfRecorder
from fedml_tpu.obs.telemetry import TelemetryRegistry
from fedml_tpu.robust.degrade import ReliabilityTracker
from fedml_tpu.utils.journal import tree_crc

N = 12          # the population


@pytest.fixture(scope="module")
def data():
    return load_data("mnist", data_dir=None, batch_size=4, num_clients=N,
                     seed=0)


@pytest.fixture(scope="module")
def workload(data):
    return create_workload("lr", "mnist", data.class_num,
                           sample_shape_of(data))


def _cfg(**kw):
    base = dict(comm_round=3, client_num_per_round=10, epochs=1,
                batch_size=4, wave_size=4, seed=0, frequency_of_the_test=10)
    base.update(kw)
    return CrossDeviceConfig(**base)


def _crc(tree) -> int:
    return tree_crc(jax.tree.map(np.asarray, tree))


def _stage_threads():
    return [t for t in threading.enumerate()
            if t.name.startswith("fedml-stage")]


def _record_takes(eng):
    """Every `_take_staged` of the engine: True for a hit."""
    takes, take = [], eng._take_staged

    def recording(ids, pad_to):
        rows = take(ids, pad_to)
        takes.append(rows is not None)
        return rows

    eng._take_staged = recording
    return takes


def _engine(workload, data, cfg, stage_ahead, **kw):
    """An engine, the global's CRC after every round it will run (the
    publish seam sees each), and its hits."""
    crcs = []
    eng = CrossDevice(workload, data, cfg, stage_ahead=stage_ahead,
                      publish=lambda params, version: crcs.append(
                          _crc(params)), **kw)
    return eng, crcs, _record_takes(eng)


def _run(workload, data, cfg, stage_ahead, pipelined=False):
    ingest = (IngestPipeline(num_shards=1, depth=8,
                             registry=TelemetryRegistry())
              if pipelined else None)
    eng, crcs, takes = _engine(workload, data, cfg, stage_ahead,
                               ingest=ingest)
    try:
        eng.run()
    finally:
        if ingest is not None:
            ingest.stop()
    return crcs, eng, takes


# ---------------------------------------------------------------------------
# (a) the same bits with staging ahead on and off
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pipelined", [False, True],
                         ids=["inline", "ingest_pipeline"])
@pytest.mark.parametrize("local_alg", ["sgd", "scaffold"])
@pytest.mark.parametrize("wave_size", [10, 4],
                         ids=["one_wave", "three_waves_last_padded"])
def test_global_crc_after_every_round_is_the_same_on_and_off(
        workload, data, wave_size, local_alg, pipelined):
    cfg = _cfg(wave_size=wave_size, local_alg=local_alg)
    on, eng_on, hits = _run(workload, data, cfg, True, pipelined)
    off, eng_off, misses = _run(workload, data, cfg, False, pipelined)
    assert len(on) == 3 and on == off
    assert len(set(on)) == 3            # the global moved every round
    # ... and the comparison is of the two paths: all but the run's first
    # wave staged ahead on one side, none on the other
    waves = 3 * -(-10 // wave_size)
    assert hits == [False] + [True] * (waves - 1)
    assert misses == [False] * waves
    if local_alg == "scaffold":
        assert _crc(eng_on.c_global) == _crc(eng_off.c_global)
        assert _crc(eng_on.c_locals) == _crc(eng_off.c_locals)


# ---------------------------------------------------------------------------
# (b) hits and misses
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("wave_size,per_round", [(10, 10), (4, 10), (5, 8)])
def test_default_sampler_hits_every_wave_but_the_first(
        workload, data, wave_size, per_round, tmp_path):
    """The cohort is a function of the round index, so what the worker
    drew for round r + 1 is what round r + 1 asks for: `wave.dispatch`
    says so with ``slots_prefetched == slots``."""
    perf = PerfRecorder(str(tmp_path / "perf.jsonl"))
    try:
        CrossDevice(workload, data,
                    _cfg(wave_size=wave_size,
                         client_num_per_round=per_round), perf=perf).run()
    finally:
        perf.close()
    dispatches = sorted((s for s in perf.tracer.spans
                         if s["name"] == "wave.dispatch"),
                        key=lambda s: s["t0_ns"])
    assert len(dispatches) == 3 * -(-per_round // wave_size)
    assert all(s["args"]["slots"] == wave_size for s in dispatches)
    assert [s["args"]["slots_prefetched"] for s in dispatches] \
        == [0] + [wave_size] * (len(dispatches) - 1)


def _staged_then(eng, act):
    """Run ``act`` once what the engine is staging has been staged: the
    worker has drawn the next round's cohort before the state that draw
    reads is changed, so the miss below is no race."""
    if eng._staged is not None:
        eng._staged.result(timeout=60)
    act()


class _WidensAfterRoundZero:
    """A cohort controller whose verdict after round 0 is a wider cohort
    (the real one needs a health alarm to say so)."""
    eng = None      # the engine it steers

    def __init__(self, cohort, wide):
        self.cohort, self._wide = cohort, wide

    def decide(self, round_idx, health_line, **kw):
        if round_idx == 0:
            _staged_then(self.eng,
                         lambda: setattr(self, "cohort", self._wide))


def _controlled(workload, data, stage_ahead):
    controller = _WidensAfterRoundZero(4, 8)
    eng, crcs, takes = _engine(
        workload, data, _cfg(client_num_per_round=4, wave_size=8),
        stage_ahead, health=HealthAccumulator(), controller=controller)
    controller.eng = eng
    eng.run()
    return crcs, takes


def test_a_widened_cohort_is_a_miss_that_equals_the_inline_run(workload,
                                                               data):
    on, hits = _controlled(workload, data, True)
    off, _ = _controlled(workload, data, False)
    assert on == off and len(set(on)) == 3
    # one wave of 8 slots a round.  Round 1 asks for 8 clients where the
    # worker, beside round 0, drew 4; beside round 1 it drew 8
    assert hits == [False, False, True]


def _indebted(workload, data, stage_ahead):
    """Client 7 falls into participation debt between rounds 0 and 1
    (keyed client id + 1 in the tracker), after round 1's first wave was
    staged without it."""
    tracker = ReliabilityTracker(N)
    eng, crcs, takes = _engine(workload, data,
                               _cfg(client_num_per_round=4), stage_ahead,
                               degrade=tracker)
    record = eng.publish

    def publish(params, version):
        record(params, version)
        if version == 1:
            _staged_then(eng, lambda: tracker.note_drop(7 + 1))

    eng.publish = publish
    eng.run()
    return crcs, takes


def test_a_debt_carrying_client_at_the_head_is_a_miss_that_equals_inline(
        workload, data):
    assert sample_clients(1, N, 4)[0] != 7
    on, hits = _indebted(workload, data, True)
    off, _ = _indebted(workload, data, False)
    assert on == off and len(set(on)) == 3
    # round 1 puts client 7 at its head, which the staged wave lacks.
    # (Round 2 is staged while round 1's wave runs, before or after the
    # debt is repaid: either, and the bits are the same.)
    assert hits[:2] == [False, False]


def _driven(workload, data, stage_ahead, cohorts):
    """A caller that drives `_run_round` itself, with cohorts of its own."""
    eng = CrossDevice(workload, data, _cfg(comm_round=len(cohorts)),
                      stage_ahead=stage_ahead)
    takes = _record_takes(eng)
    params = jax.tree.map(jax.numpy.asarray, workload.init(
        jax.random.key(0), jax.tree.map(
            lambda v: v[0, 0],
            {k: data.train[k] for k in ("x", "y", "mask")})))
    crcs = []
    try:
        for r, ids in enumerate(cohorts):
            params, _ = eng._run_round(params, np.asarray(ids),
                                       jax.random.key(r + 1), r)
            crcs.append(_crc(params))
    finally:
        eng._stop_staging()
    return crcs, takes


def test_a_caller_passing_other_ids_is_a_miss_that_equals_inline(
        workload, data):
    cohorts = [[3, 1, 2, 0, 5, 4],               # nobody's draw
               [11, 10, 9, 8],
               sample_clients(2, N, 10)]         # the sampler's own
    on, hits = _driven(workload, data, True, cohorts)
    off, misses = _driven(workload, data, False, cohorts)
    assert on == off and len(set(on)) == 3
    assert hits == [False, True,        # wave 2 of the same cohort: a hit
                    False,              # round 1's draw was staged: miss
                    True, True, True]   # round 2 asks for that draw
    assert not any(misses)
    assert not _stage_threads()


def _churning(workload, data, stage_ahead):
    """Twelve rounds in which the tracker's debts change while the worker
    reads them: after every round but the last two some client is marked
    dropped, whenever that round's staging happens to run."""
    tracker = ReliabilityTracker(N)
    eng, crcs, takes = _engine(
        workload, data, _cfg(comm_round=12, client_num_per_round=6),
        stage_ahead, degrade=tracker)
    record = eng.publish

    def publish(params, version):
        record(params, version)
        if version <= 10:
            tracker.note_drop(1 + (5 * version) % N)

    eng.publish = publish
    eng.run()
    return crcs, takes


def test_sampler_state_that_changes_under_the_worker_changes_no_bit(
        workload, data):
    """The one state both threads touch is what `_sample_round` reads
    (the tracker's debts, a controller's cohort): the worker only reads
    it, and whatever it read, the loop checks the ids before it takes the
    rows.  Threads switched every 10 us; the bits are the inline run's."""
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        on, takes = _churning(workload, data, True)
    finally:
        sys.setswitchinterval(interval)
    off, _ = _churning(workload, data, False)
    assert on == off and len(set(on)) == 12
    assert len(takes) == 12 * 2 and takes[0] is False
    # within a round the next wave is always the staged one; the last
    # two rounds carry no debt, so the last round's draw was foreseen
    assert all(takes[1::2]) and takes[-2] is True
    assert not _stage_threads()


# ---------------------------------------------------------------------------
# (c) the worker's life
# ---------------------------------------------------------------------------

def _count_gathers(monkeypatch):
    calls = []
    gather = cross_device.gather_cohort

    def counting(stacked, ids, pad_to=None):
        calls.append((threading.current_thread().name, list(ids)))
        return gather(stacked, ids, pad_to=pad_to)

    # the name the engine calls it by (and the harness patches)
    monkeypatch.setattr(cross_device, "gather_cohort", counting)
    return calls


def test_nothing_is_staged_past_the_last_round(workload, data, monkeypatch):
    calls = _count_gathers(monkeypatch)
    _, eng, hits = _run(workload, data, _cfg(), True)
    # one gather a wave and no more: 3 rounds of 3 waves, the first on
    # the main thread and the rest on the one worker
    assert len(calls) == 9 == len(hits)
    assert calls[0][0] == "MainThread"
    assert {name for name, _ in calls[1:]} == {"fedml-stage_0"}
    assert eng._staged is None and eng._stage_pool is None
    assert not _stage_threads()


def test_one_round_stages_nothing_and_starts_no_worker(workload, data,
                                                       monkeypatch):
    calls = _count_gathers(monkeypatch)
    _run(workload, data, _cfg(comm_round=1, wave_size=10), True)
    assert [name for name, _ in calls] == ["MainThread"]


def test_no_worker_is_alive_after_a_round_raises(workload, data):
    eng = CrossDevice(workload, data, _cfg())
    fold = eng._fold_one

    def failing(round_idx, *a):
        if round_idx == 1:
            raise RuntimeError("fold broke")
        return fold(round_idx, *a)

    eng._fold_one = failing
    with pytest.raises(RuntimeError, match="fold broke"):
        eng.run()
    assert eng._staged is None and eng._stage_pool is None
    assert not _stage_threads()


def test_a_gather_that_raises_on_the_worker_raises_on_the_main_thread(
        workload, data, monkeypatch):
    gather = cross_device.gather_cohort
    main_waves = []

    def failing(stacked, ids, pad_to=None):
        if threading.current_thread() is not threading.main_thread():
            raise OSError("the corpus went away")
        main_waves.append(list(ids))
        return gather(stacked, ids, pad_to=pad_to)

    monkeypatch.setattr(cross_device, "gather_cohort", failing)
    eng = CrossDevice(workload, data, _cfg())
    dispatched = []
    wave_fn = eng._wave_fn
    eng._wave_fn = lambda *a: dispatched.append(1) or wave_fn(*a)
    with pytest.raises(OSError, match="the corpus went away"):
        eng.run()
    # raised at the wave that would have consumed the rows: the first
    # wave ran, the second was never dispatched or gathered again
    assert len(dispatched) == 1 and len(main_waves) == 1
    assert not _stage_threads()


# ---------------------------------------------------------------------------
# (d) staged rows are the staged wave's alone
# ---------------------------------------------------------------------------

def test_staged_rows_are_never_written_between_staging_and_use(
        workload, data, monkeypatch):
    """On the CPU `jnp.asarray` may alias the numpy memory it is given:
    a stager that reused or wrote a host buffer would change rows a wave
    program is reading.  Every wave's rows are copied when they are
    staged and compared when they are used, and again once every later
    wave has been staged and used."""
    gather = cross_device.gather_cohort
    staged = []         # (what gather_cohort returned, a copy of it then)

    def copying(stacked, ids, pad_to=None):
        out = gather(stacked, ids, pad_to=pad_to)
        staged.append((out, {k: np.array(v) for k, v in out.items()},
                       threading.current_thread().name))
        return out

    monkeypatch.setattr(cross_device, "gather_cohort", copying)
    eng = CrossDevice(workload, data, _cfg())
    used = []
    wave_fn = eng._wave_fn

    def checking(params, wave_data, *a):
        mine = [s for s in staged if s[0] is wave_data]
        assert len(mine) == 1       # used once, and as it was returned
        used.append(mine[0])
        for out, then, _ in used:   # this wave's and every earlier one's
            for k in then:
                assert np.array_equal(np.asarray(out[k]), then[k]), k
        return wave_fn(params, wave_data, *a)

    eng._wave_fn = checking
    eng.run()
    assert len(used) == len(staged) == 9
    assert [name for _, _, name in used] \
        == ["MainThread"] + ["fedml-stage_0"] * 8
    for out, then, _ in staged:
        for k in then:
            assert np.array_equal(np.asarray(out[k]), then[k]), k
            # its own memory, not the population's
            assert not np.shares_memory(np.asarray(out[k]), data.train[k])
