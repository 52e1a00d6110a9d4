"""Sustained-degradation survivability (ISSUE 19): the reliability
tracker's adaptive deadline / quorum-partition verdict / participation
debt, the closed fault-attribution vocabulary with its hard invariant
(only PAYLOAD verdicts may strike trust), the dead-letter attribution
feed, checkpointed determinism of every derivation, and the resume-path
straggler-timer audit.

Fast tier only — the full chaos + partition + kill soak is
scripts/degrade_soak.py, which exits 1 on its own gates.
"""

import dataclasses

import jax
import numpy as np
import pytest

from fedml_tpu.algorithms.cross_silo import (FedAvgClientActor,
                                             FedAvgServerActor)
from fedml_tpu.comm.local import LocalHub
from fedml_tpu.core.stream_agg import StreamingAggregator
from fedml_tpu.experiments.config import ExperimentConfig
from fedml_tpu.experiments.main import _degrade_setup
from fedml_tpu.robust import AdmissionPipeline, TrustTracker
from fedml_tpu.robust.degrade import (FaultClass, ReliabilityTracker,
                                      classify_admission_reason,
                                      merge_priority)
from fedml_tpu.robust.faultline import ActorKilled, CrashSpec, Faultline
from fedml_tpu.utils.checkpoint import RoundCheckpointer
from fedml_tpu.utils.journal import RoundJournal


def _params(seed=3):
    rng = np.random.RandomState(seed)
    return {"dense": {"kernel": rng.randn(4, 3).astype(np.float32),
                      "bias": rng.randn(3).astype(np.float32)}}


def _train_fn(silo):
    def fn(params, client_idx, round_idx):
        rng = np.random.RandomState(1000 * silo + int(round_idx or 0))
        return jax.tree.map(
            lambda v: v + rng.randn(*np.shape(v)).astype(np.float32) * 0.1,
            params), 10 + silo
    return fn


def _tracker(n=4, **kw):
    base = dict(min_quorum=0.5, adaptive_deadline=True,
                deadline_floor_s=0.2, deadline_quantile=0.9,
                deadline_slack=1.5, partition_frac=0.5,
                partition_max_holds=2, min_history=2)
    base.update(kw)
    return ReliabilityTracker(n, **base)


# ---------------------------------------------------------------------------
# fault-attribution vocabulary + the strike invariant
# ---------------------------------------------------------------------------

class TestFaultAttribution:
    def test_vocabulary_is_closed(self):
        assert FaultClass.ALL == ("network", "payload", "unknown")
        t = _tracker()
        with pytest.raises(ValueError, match="closed"):
            t.note_fault("cosmic_ray")

    def test_admission_reasons_all_classify_payload(self):
        from fedml_tpu.robust.admission import REASONS
        for reason in REASONS:
            assert classify_admission_reason(reason) == FaultClass.PAYLOAD

    def test_only_payload_may_strike(self):
        """THE invariant: a network- or unknown-attributed verdict
        reaching TrustTracker.strike is a programming error, raised at
        the call site — a chaotic link must never walk an honest silo
        into Byzantine quarantine."""
        trust = TrustTracker(strikes_to_quarantine=1)
        for fault in (FaultClass.NETWORK, FaultClass.UNKNOWN):
            with pytest.raises(ValueError, match="only payload"):
                trust.strike(2, 0, "flaky_link", fault=fault)
        # the refused strikes left no trace: no quarantine, no counts
        assert trust.state(2, 1) == TrustTracker.TRUSTED
        assert trust.strike_fault_totals() == {"network": 0, "payload": 0,
                                               "unknown": 0}
        with pytest.raises(ValueError, match="closed"):
            trust.strike(2, 0, "bad", fault="gamma_burst")
        # a payload strike lands normally
        assert trust.strike(2, 0, "nonfinite") is True
        assert trust.state(2, 1) == TrustTracker.QUARANTINED
        assert trust.strike_fault_totals()["payload"] == 1

    def test_network_faults_route_to_tracker_not_trust(self):
        t = _tracker()
        t.round_start(0, {1, 2, 3, 4})
        t.note_drop(3)
        t.note_dead_letter("deadline", silo=2)
        led = t.as_ledger()
        assert led["faults"]["network"] == 2
        assert led["faults"]["payload"] == 0
        assert led["dead_letters"] == 1


class TestStrikeReasonsState:
    def test_roundtrip(self):
        trust = TrustTracker(strikes_to_quarantine=3)
        trust.strike(1, 0, "nonfinite")
        trust.strike(1, 1, "norm_outlier")
        trust.strike(3, 1, "fingerprint")
        state = trust.state_dict(4)
        sr = state["strike_reasons"]
        assert sr.shape == (4, len(FaultClass.ALL))
        fresh = TrustTracker(strikes_to_quarantine=3)
        fresh.load_state_dict(state)
        assert fresh.strike_fault_totals() == trust.strike_fault_totals()
        assert fresh.strike_fault_totals()["payload"] == 3

    def test_pre19_snapshot_restores_tolerantly(self, caplog):
        """A checkpoint written before the attribution matrix existed
        restores with a warning, never a refused resume."""
        trust = TrustTracker()
        trust.strike(2, 0, "nonfinite")
        state = dict(trust.state_dict(3))
        state.pop("strike_reasons")
        fresh = TrustTracker()
        with caplog.at_level("WARNING"):
            fresh.load_state_dict(state)
        assert "pre-19" in caplog.text
        assert fresh.strike_fault_totals()["payload"] == 0
        # the sentence itself still restored
        assert fresh._strikes == trust._strikes

    def test_foreign_shape_matrix_restores_tolerantly(self, caplog):
        trust = TrustTracker()
        state = dict(trust.state_dict(3))
        state["strike_reasons"] = np.zeros((3, 7), np.int64)
        with caplog.at_level("WARNING"):
            TrustTracker().load_state_dict(state)
        assert "fault vocabulary" in caplog.text


# ---------------------------------------------------------------------------
# adaptive deadline
# ---------------------------------------------------------------------------

class TestAdaptiveDeadline:
    def test_static_when_disabled_and_none_when_uncapped(self):
        t = _tracker(adaptive_deadline=False)
        assert t.deadline_s({1, 2}, 7.0) == 7.0
        assert _tracker().deadline_s({1, 2}, None) is None

    def test_cold_start_any_unmeasured_silo_falls_back_to_cap(self):
        """The bootstrap trap: a deadline derived from only the measured
        (fast) silos would drop an unmeasured slow-but-honest silo
        before it ever got a completion on record — and its late
        uploads, discarded as stale, could never grow its history.  Cap
        until EVERY expected silo has min_history observations."""
        t = _tracker(min_history=2)
        for _ in range(5):
            t.observe_completion(1, 0.1)
            t.observe_completion(2, 0.1)
        # silo 3 has one observation — still cold
        t.observe_completion(3, 0.9)
        assert t.deadline_s({1, 2, 3}, 10.0) == 10.0
        t.observe_completion(3, 0.9)
        d = t.deadline_s({1, 2, 3}, 10.0)
        assert d == pytest.approx(0.9 * 1.5)  # slowest silo's q90 * slack

    def test_clamps_to_floor_and_cap(self):
        t = _tracker(min_history=1, deadline_floor_s=0.5)
        t.observe_completion(1, 0.01)
        assert t.deadline_s({1}, 10.0) == 0.5
        t2 = _tracker(min_history=1)
        t2.observe_completion(1, 100.0)
        assert t2.deadline_s({1}, 3.0) == 3.0

    def test_bad_observations_ignored(self):
        t = _tracker(min_history=1)
        t.observe_completion(1, float("nan"))
        t.observe_completion(1, float("inf"))
        t.observe_completion(1, -0.5)
        t.observe_completion(99, 0.2)   # not this tracker's cohort
        assert t.deadline_s({1}, 5.0) == 5.0  # still cold: nothing stuck

    def test_derivation_is_pure_in_checkpointed_state(self):
        """The resume-determinism contract: restoring state_dict into a
        fresh tracker re-derives the crashed process's deadline
        EXACTLY (same floats in, same float out)."""
        rng = np.random.RandomState(7)
        t = _tracker(min_history=2)
        for silo in (1, 2, 3, 4):
            for lat in rng.uniform(0.05, 1.2, size=9):
                t.observe_completion(silo, float(lat))
        want = t.deadline_s({1, 2, 3, 4}, 30.0)
        assert want is not None and want < 30.0
        fresh = _tracker(min_history=2)
        fresh.load_state_dict(t.state_dict())
        assert fresh.deadline_s({1, 2, 3, 4}, 30.0) == want

    def test_suspicion_grows_with_silence(self):
        t = _tracker()
        assert t.suspicion(1, 10.0) == 0.0  # no history, nothing to suspect
        t.observe_completion(1, 0.5)
        assert t.suspicion(1, 0.5) < t.suspicion(1, 5.0)


# ---------------------------------------------------------------------------
# quorum-aware closure + partition discrimination
# ---------------------------------------------------------------------------

class TestQuorumPartition:
    def test_quorum_for(self):
        assert _tracker(min_quorum=0.0).quorum_for(10) is None
        assert _tracker(min_quorum=0.5).quorum_for(5) == 3
        assert _tracker(min_quorum=1.0).quorum_for(4) == 4

    def test_close_at_quorum_wait_below(self):
        t = _tracker(partition_frac=0.0)
        t.round_start(0, {1, 2, 3, 4})
        v = t.assess_timeout(0, {1, 2, 3, 4}, {1, 2}, quorum=2)
        assert v.action == "close" and v.missing == (3, 4)
        v = t.assess_timeout(0, {1, 2, 3, 4}, {1}, quorum=2)
        assert v.action == "wait"

    def test_correlated_miss_with_dead_letters_holds_then_abandons(self):
        t = _tracker(partition_frac=0.5, partition_max_holds=2)
        t.round_start(3, {1, 2, 3, 4})
        t.note_dead_letter("send_failed")
        verdicts = [t.assess_timeout(3, {1, 2, 3, 4}, {1, 2}, quorum=2)
                    for _ in range(3)]
        assert [v.action for v in verdicts] == ["hold", "hold", "abandon"]
        assert all(v.partition_suspected for v in verdicts)
        assert t.holds_total == 2

    def test_detector_states_are_evidence(self):
        """No dead letters, but every missing silo is non-ALIVE per the
        failure detector: still a partition."""
        t = _tracker(partition_frac=0.5)
        t.round_start(0, {1, 2, 3, 4})
        v = t.assess_timeout(0, {1, 2, 3, 4}, {1, 2}, quorum=2,
                             detector_states={3: "suspect", 4: "dead"})
        assert v.action == "hold" and v.partition_suspected

    def test_mass_miss_without_evidence_is_not_a_partition(self):
        """Silos alive, links clean, uploads simply absent: close under
        the quorum rule — holding would stall on non-network failures."""
        t = _tracker(partition_frac=0.5)
        t.round_start(0, {1, 2, 3, 4})
        v = t.assess_timeout(0, {1, 2, 3, 4}, {1, 2}, quorum=2,
                             detector_states={3: "alive", 4: "suspect"})
        assert v.action == "close" and not v.partition_suspected
        assert "without network evidence" in v.reason

    def test_hold_budget_and_evidence_are_per_round(self):
        t = _tracker(partition_frac=0.5, partition_max_holds=1)
        t.round_start(0, {1, 2})
        t.note_dead_letter("send_failed")
        assert t.assess_timeout(0, {1, 2}, set(), 1).action == "hold"
        assert t.assess_timeout(0, {1, 2}, set(), 1).action == "abandon"
        t.round_start(1, {1, 2})
        # fresh round: dead-letter evidence gone, budget reset
        v = t.assess_timeout(1, {1, 2}, {1}, quorum=1)
        assert v.action == "close" and not v.partition_suspected


# ---------------------------------------------------------------------------
# participation debt + priority re-tasking
# ---------------------------------------------------------------------------

class TestDebtPriority:
    def test_drop_accrues_accept_repays(self):
        t = _tracker()
        t.round_start(0, {1, 2, 3, 4})
        t.note_drop(2)
        t.note_drop(2)
        t.note_drop(3)
        assert t.debt(2) == 2 and t.max_debt() == 2
        assert t.priority([1, 2, 3, 4]) == [2, 3, 1, 4]
        assert t.priority_clients() == [2, 3]
        t.note_accept(2)
        assert t.debt(2) == 0
        assert t.drops_total == 3

    def test_merge_priority_deterministic_no_duplicates(self):
        assert merge_priority([5, 1, 2, 3], [2, 7], 4) == [2, 7, 5, 1]
        assert merge_priority([1, 2], [], 2) == [1, 2]  # zero debt: untouched
        assert merge_priority([1, 2, 3], [9, 9, 8], 2) == [9, 8]


# ---------------------------------------------------------------------------
# ledger + checkpointed state
# ---------------------------------------------------------------------------

class TestLedgerAndState:
    def test_ledger_schema(self):
        t = _tracker(min_history=1)
        t.round_start(5, {1, 2, 3})
        t.observe_completion(1, 0.4)
        t.note_accept(1)
        t.note_drop(3)
        t.deadline_s({1, 2, 3}, 9.0)
        led = t.as_ledger()
        assert led["accepted"] == [1] and led["dropped"] == [3]
        assert set(led) >= {"deadline_s", "holds", "dead_letters",
                            "debt_max", "faults"}

    def test_state_dict_roundtrip(self):
        t = _tracker()
        t.round_start(0, {1, 2, 3, 4})
        t.observe_completion(1, 0.3)
        t.observe_completion(1, 0.5)
        t.note_drop(4)
        t.note_dead_letter("deadline")
        t.assess_timeout(0, {1, 2, 3, 4}, {1}, quorum=1)
        state = t.state_dict()
        assert state["lat"].shape == (4, t.window)
        fresh = _tracker()
        fresh.load_state_dict(state)
        assert fresh.debt(4) == 1
        assert fresh.drops_total == t.drops_total
        assert fresh.holds_total == t.holds_total
        assert fresh._fault_counts == t._fault_counts
        assert list(fresh._lat[1]) == [0.3, 0.5]

    def test_foreign_shape_restores_tolerantly(self, caplog):
        fresh = _tracker(4)
        state = _tracker(7).state_dict()
        with caplog.at_level("WARNING"):
            fresh.load_state_dict(state)
        assert "starting reliability history fresh" in caplog.text

    def test_constructor_validates(self):
        with pytest.raises(ValueError, match="min_quorum"):
            ReliabilityTracker(3, min_quorum=1.5)
        with pytest.raises(ValueError, match="deadline_quantile"):
            ReliabilityTracker(3, deadline_quantile=0.0)


# ---------------------------------------------------------------------------
# dead-letter feed (comm/resilient -> tracker attribution)
# ---------------------------------------------------------------------------

class TestDeadLetterFeed:
    def test_dead_letter_feeds_tracker_never_trust(self):
        """A dead-lettered send books network evidence on the tracker
        (labeled fedml_comm_dead_letter_total{reason}) and leaves the
        trust ledger untouched."""
        import time

        from fedml_tpu.comm.message import Message
        from fedml_tpu.comm.resilient import ResilientTransport, RetryPolicy
        from fedml_tpu.comm.transport import Transport

        class _Down(Transport):
            def send_message(self, msg):
                raise ConnectionError("wire down")

            def run(self):
                pass

            def stop(self):
                pass

        t = _tracker()
        trust = TrustTracker(strikes_to_quarantine=1)
        t.round_start(0, {1, 2})
        rt = ResilientTransport(
            _Down(), RetryPolicy(max_attempts=1, send_deadline_s=5.0),
            fault_feed=lambda reason, msg: t.note_dead_letter(reason))
        try:
            rt.send_message(Message("m", 0, 1))
            deadline = time.monotonic() + 5.0
            while rt.dead_letters < 1 and time.monotonic() < deadline:
                time.sleep(0.005)
        finally:
            rt.stop()
        assert rt.dead_letters == 1
        assert t._round_dead_letters == 1
        assert t._fault_counts["network"] == 1
        # the wire failure produced zero strikes anywhere
        assert trust.strike_fault_totals()["payload"] == 0
        # and the labeled counter carries the reason
        assert "send_failed" in rt._m_dead_by_reason


# ---------------------------------------------------------------------------
# config gates (experiments/main._degrade_setup)
# ---------------------------------------------------------------------------

class TestConfigGates:
    def _cfg(self, **kw):
        base = dict(straggler_policy="drop", round_timeout_s=5.0)
        base.update(kw)
        return ExperimentConfig(**base)

    def test_off_by_default(self):
        assert _degrade_setup(ExperimentConfig(), 4) is None

    def test_sync_happy_path(self):
        t = _degrade_setup(self._cfg(min_quorum=0.5, adaptive_deadline=True,
                                     partition_frac=0.3), 4)
        assert isinstance(t, ReliabilityTracker)
        assert t.quorum_for(4) == 2

    @pytest.mark.parametrize("kw,match", [
        (dict(min_quorum=1.5), "min_quorum"),
        (dict(min_quorum=0.5, straggler_policy="wait"), "drop"),
        (dict(adaptive_deadline=True, round_timeout_s=0.0),
         "round_timeout_s"),
        (dict(partition_frac=2.0), "partition_frac"),
        (dict(min_quorum=0.8, partition_frac=0.5), "quorum gap"),
    ])
    def test_misconfigurations_fail_loud(self, kw, match):
        with pytest.raises(ValueError, match=match):
            _degrade_setup(self._cfg(**kw), 4)

    def test_async_refuses_barrier_flags(self):
        with pytest.raises(ValueError, match="no barrier"):
            _degrade_setup(self._cfg(min_quorum=0.5), 4, mode="async")
        with pytest.raises(ValueError, match="retask_timeout_s"):
            _degrade_setup(self._cfg(adaptive_deadline=True,
                                     retask_timeout_s=0.0), 4,
                           mode="async")


# ---------------------------------------------------------------------------
# engine integration (LocalHub pump) + the resume-path timer audit
# ---------------------------------------------------------------------------

def _run_degrade(init, rounds, *, n=3, degrade=None, ck=None, jr=None,
                 fl=None, extra_state=None, arm_log=None,
                 timeout_s=300.0):
    hub = LocalHub(codec_roundtrip=True)
    stream = StreamingAggregator(init, method="mean", kind="params",
                                 norm_clip=1.0, seed=0)
    server = FedAvgServerActor(
        hub.transport(0), init, n, n, rounds, checkpointer=ck,
        journal=jr, faultline=fl, stream_agg=stream, degrade=degrade,
        extra_state=extra_state, straggler_policy="drop",
        round_timeout_s=timeout_s, min_silo_frac=0.5)
    if arm_log is not None:
        orig = server._timer.arm

        def spy(delay_s, fire, _orig=orig, _log=arm_log):
            _log.append((server.round_idx, delay_s))
            _orig(delay_s, fire)
        server._timer.arm = spy
    silos = [FedAvgClientActor(i, hub.transport(i), _train_fn(i))
             for i in range(1, n + 1)]
    server.register_handlers()
    for s in silos:
        s.register_handlers()
    server.start()
    if arm_log is not None:
        # the audit point: start() ran recovery + broadcast, nothing
        # else has pumped yet
        server._start_arms = list(arm_log)
    try:
        hub.pump()
    except ActorKilled:
        # a killed actor never reaches finish(): reap the straggler
        # timer its process would have taken with it, or the 300 s Timer
        # thread outlives this test into whatever shares its worker
        server._cancel_timer(join=True)
        raise
    return server


class TestEngineIntegration:
    def test_degrade_ledger_and_adaptive_deadline_live(self, tmp_path):
        """Pump-mode federation with the spine on: the perf row carries
        the degrade ledger, and once every silo is measured the armed
        deadline adapts below the static cap."""
        from fedml_tpu.obs.perf import PerfRecorder
        from fedml_tpu.obs.trend import load_ledger
        init = _params(3)
        pp = str(tmp_path / "perf.jsonl")
        hub = LocalHub(codec_roundtrip=True)
        perf = PerfRecorder(pp, strict_recompiles=False)
        stream = StreamingAggregator(init, method="mean", kind="params",
                                     norm_clip=1.0, seed=0)
        degrade = ReliabilityTracker(
            3, min_quorum=0.5, adaptive_deadline=True,
            deadline_floor_s=1e-4, deadline_quantile=0.9,
            deadline_slack=1.5, partition_frac=0.3, min_history=1)
        server = FedAvgServerActor(
            hub.transport(0), init, 3, 3, 4, stream_agg=stream,
            degrade=degrade, perf=perf, straggler_policy="drop",
            round_timeout_s=300.0, min_silo_frac=0.5)
        silos = [FedAvgClientActor(i, hub.transport(i), _train_fn(i))
                 for i in range(1, 4)]
        server.register_handlers()
        for s in silos:
            s.register_handlers()
        server.start()
        hub.pump()
        perf.close()
        assert server.round_idx == 4
        rows = load_ledger(pp)
        assert len(rows) == 4
        for r in rows:
            dg = r["degrade"]
            assert dg["accepted"] == [1, 2, 3]
            assert dg["faults"]["payload"] == 0
        # round 0 is cold (cap); later rounds derive from history
        assert rows[0]["degrade"]["deadline_s"] == 300.0
        assert rows[-1]["degrade"]["deadline_s"] < 300.0

    def test_resumed_midround_rearms_exactly_one_timer(self, tmp_path):
        """The resume-path straggler-timer audit (ISSUE 19 satellite):
        a server resumed MID-ROUND from the journal re-arms exactly one
        ROUND_TIMEOUT timer for the re-tasked remainder — no stale
        pre-crash timer semantics, and never a drop-policy round with
        zero timers."""
        init = _params(3)
        fl = Faultline(crashes=[CrashSpec(point="post_fold_pre_ack",
                                          hit=1, round_idx=1)])
        with pytest.raises(ActorKilled):
            _run_degrade(init, 3,
                         ck=RoundCheckpointer(str(tmp_path / "ck"),
                                              save_every=1),
                         jr=RoundJournal(str(tmp_path / "j"),
                                         snapshot_every=1), fl=fl)
        arms = []
        resumed = _run_degrade(
            init, 3,
            ck=RoundCheckpointer(str(tmp_path / "ck"), save_every=1),
            jr=RoundJournal(str(tmp_path / "j"), snapshot_every=1),
            arm_log=arms)
        # start() = journal recovery + the resumed round's broadcast:
        # exactly ONE timer armed, for the resumed round
        assert resumed._start_arms == [(1, 300.0)]
        # and the federation then completed normally (one arm per round)
        assert resumed.round_idx == 3
        assert [r for r, _ in arms] == [1, 2]

    def test_resume_replays_latency_history(self, tmp_path):
        """The deadline's determinism across a crash rides the journal:
        accept records carry lat_s, and the resumed broadcast replays
        them into the tracker so the NEXT derivation sees the same
        history the crashed process had."""
        init = _params(3)

        def mk_degrade():
            return ReliabilityTracker(
                3, min_quorum=0.5, adaptive_deadline=True,
                deadline_floor_s=1e-4, deadline_quantile=0.9,
                deadline_slack=1.5, partition_frac=0.3, min_history=1)
        d1 = mk_degrade()
        fl = Faultline(crashes=[CrashSpec(point="post_fold_pre_ack",
                                          hit=2, round_idx=2)])
        with pytest.raises(ActorKilled):
            _run_degrade(init, 4, degrade=d1,
                         ck=RoundCheckpointer(str(tmp_path / "ck"),
                                              save_every=1),
                         jr=RoundJournal(str(tmp_path / "j"),
                                         snapshot_every=1), fl=fl,
                         extra_state=(d1.state_dict, d1.load_state_dict))
        d2 = mk_degrade()
        resumed = _run_degrade(
            init, 4, degrade=d2,
            ck=RoundCheckpointer(str(tmp_path / "ck"), save_every=1),
            jr=RoundJournal(str(tmp_path / "j"), snapshot_every=1),
            extra_state=(d2.state_dict, d2.load_state_dict))
        assert resumed.round_idx == 4
        # every silo's history covers every completed round: the
        # checkpointed matrix plus the journal replay left no gap
        for silo in (1, 2, 3):
            assert len(d2._lat[silo]) == 4

    def test_attacker_strikes_payload_honest_drop_does_not(self):
        """End-to-end attribution: a NaN attacker strikes (payload), and
        the strike totals show zero network/unknown — the invariant the
        soak pins at scale."""
        init = _params(3)
        hub = LocalHub(codec_roundtrip=True)
        stream = StreamingAggregator(init, method="mean", kind="params",
                                     norm_clip=1.0, seed=0)
        adm = AdmissionPipeline(init, kind="params",
                                trust=TrustTracker(strikes_to_quarantine=1))
        degrade = ReliabilityTracker(3, min_quorum=0.5, partition_frac=0.4)
        server = FedAvgServerActor(
            hub.transport(0), init, 3, 3, 2, stream_agg=stream,
            admission=adm, degrade=degrade, straggler_policy="drop",
            round_timeout_s=300.0, min_silo_frac=0.5)

        def nan_train(params, client_idx, round_idx):
            return jax.tree.map(
                lambda v: np.full_like(np.asarray(v), np.nan), params), 10

        silos = [FedAvgClientActor(1, hub.transport(1), _train_fn(1)),
                 FedAvgClientActor(2, hub.transport(2), _train_fn(2)),
                 FedAvgClientActor(3, hub.transport(3), nan_train)]
        server.register_handlers()
        for s in silos:
            s.register_handlers()
        server.start()
        hub.pump()
        sft = adm.trust.strike_fault_totals()
        assert sft["payload"] >= 1
        assert sft["network"] == 0 and sft["unknown"] == 0
        assert degrade._fault_counts["payload"] >= 1


# the CLI wiring sanity: every degrade flag the README documents exists
def test_config_has_degrade_fields():
    names = {f.name for f in dataclasses.fields(ExperimentConfig)}
    assert {"min_quorum", "adaptive_deadline", "deadline_floor_s",
            "deadline_quantile", "deadline_slack", "partition_frac",
            "partition_max_holds"} <= names
