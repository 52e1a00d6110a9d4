"""Release-gate contracts (ISSUE 16): the canary state machine in the
registry, the three promotion signals and their verdict matrix, shadow
determinism, cooldown/backoff, crash-consistent promote/rollback, the
checkpoint-manifest torn-file guard, wave-summary poisoning, and the
end-to-end poisoned-round containment story.

The load-bearing invariant everywhere: a canary NEVER occupies the live
slot — promotion is the only way in, so a failed (or crashed) release
can never have served a non-shadow response.
"""

import json
import os

import jax
import numpy as np
import pytest

from fedml_tpu.robust.faultline import (ActorKilled, CrashSpec,
                                        DiskFaultInjector, DiskFaultSpec,
                                        Faultline)
from fedml_tpu.serve.batcher import MicroBatcher
from fedml_tpu.serve.registry import CheckpointWatcher, ModelRegistry
from fedml_tpu.serve.release import (ReleaseController, ShadowSampler,
                                     _divergence)

DIM, CLASSES = 6, 4


def _linear_apply():
    return jax.jit(lambda p, x: x.reshape(x.shape[0], -1) @ p["w"] + p["b"])


def _params(version: int):
    """Version-fingerprinted params (the test_serve.py convention): any
    probe response names which version produced it."""
    w = np.zeros((DIM, CLASSES), np.float32)
    w[0, :] = float(version)
    b = np.zeros(CLASSES, np.float32)
    b[version % CLASSES] = 1.0
    return {"w": w, "b": b}


def _registry(*promoted):
    reg = ModelRegistry(_linear_apply(), history=8)
    for v in promoted:
        reg.publish(_params(v), v)
    return reg


def _controller(reg, **kw):
    kw.setdefault("cooldown_s", 0.0)
    kw.setdefault("max_cooldown_s", 0.0)
    return ReleaseController(reg, **kw)


# -- registry canary state machine -------------------------------------------

class TestRegistryCanaryStates:
    def test_canary_publish_never_swaps_live(self):
        reg = _registry(1)
        assert reg.publish(_params(2), 2, canary=True)
        assert reg.version == 1           # live never moved
        assert reg.state(2) == "canary"
        assert reg.canaries() == [2]
        assert reg.get(2).version == 2    # but shadow replay can read it

    def test_promote_swaps_live_and_pins(self):
        reg = _registry(1)
        reg.publish(_params(2), 2, canary=True)
        assert reg.promote(2) == 2
        assert reg.version == 2 and reg.pinned == 2
        assert reg.state(2) == "promoted"
        # idempotent re-drive (the crash-at-post respawn path)
        assert reg.promote(2) == 2

    def test_promote_promoted_but_not_live_refuses(self):
        reg = _registry(1, 2)
        reg.pin(1)
        with pytest.raises(RuntimeError, match="promoted but not live"):
            reg.promote(2)

    def test_discard_removes_canary_only(self):
        reg = _registry(1)
        reg.publish(_params(2), 2, canary=True)
        reg.discard(2)
        assert reg.versions() == [1] and reg.canaries() == []
        with pytest.raises(RuntimeError, match="promoted"):
            reg.discard(1)
        with pytest.raises(KeyError):
            reg.discard(99)

    def test_discarded_version_number_can_be_republished(self):
        """Monotonicity compares against the newest REMAINING entry, so
        a rolled-back version number is offerable again after a retrain."""
        reg = _registry(1)
        reg.publish(_params(2), 2, canary=True)
        reg.discard(2)
        assert reg.publish(_params(2), 2, canary=True)

    def test_rollback_skips_canaries_to_previous_promoted(self):
        reg = _registry(1, 2)
        # wedge an unvetted canary between the promoted versions: it
        # must be invisible to rollback
        reg.publish(_params(3), 3, canary=True)
        reg.publish(_params(4), 4)
        assert reg.version == 4
        assert reg.rollback() == 2
        assert reg.version == 2

    def test_rollback_past_promoted_horizon_fails_loudly(self):
        reg = ModelRegistry(_linear_apply(), history=8)
        reg.publish(_params(1), 1, canary=True)
        reg.publish(_params(2), 2)        # the only promoted version
        with pytest.raises(RuntimeError, match="promoted horizon"):
            reg.rollback()
        assert reg.version == 2           # serving never moved

    def test_pin_refuses_canary(self):
        reg = _registry(1)
        reg.publish(_params(2), 2, canary=True)
        with pytest.raises(RuntimeError, match="unvetted canary"):
            reg.pin(2)

    def test_unpin_follows_newest_promoted_not_canary(self):
        reg = _registry(1, 2)
        reg.pin(1)
        reg.publish(_params(3), 3, canary=True)
        reg.unpin()
        assert reg.version == 2

    def test_eviction_protects_pending_canaries(self):
        reg = ModelRegistry(_linear_apply(), history=2)
        reg.publish(_params(1), 1, canary=True)
        for v in (2, 3, 4, 5):
            reg.publish(_params(v), v)
        assert 1 in reg.versions()        # canary outlived retention
        reg.discard(1)
        reg.publish(_params(6), 6)
        assert 1 not in reg.versions()


# -- shadow sampler ----------------------------------------------------------

class TestShadowSampler:
    def test_validates(self):
        with pytest.raises(ValueError):
            ShadowSampler(every=0)
        with pytest.raises(ValueError):
            ShadowSampler(slots=0)

    def test_every_nth_and_determinism(self):
        def run():
            s = ShadowSampler(every=3, slots=4)
            for i in range(20):
                s.offer(np.full(2, float(i), np.float32))
            return [r[0] for r in s.snapshot()]
        a, b = run(), run()
        assert a == b                     # same arrivals, same slice
        # every 3rd arrival (0, 3, 6, ...), newest 4 kept, ring order
        assert sorted(a) == [9.0, 12.0, 15.0, 18.0]

    def test_snapshot_copies_are_owned(self):
        s = ShadowSampler(every=1, slots=2)
        x = np.zeros(2, np.float32)
        s.offer(x)
        x[:] = 7.0                        # caller reuses its buffer
        assert s.snapshot()[0][0] == 0.0

    def test_batcher_taps_admitted_traffic(self):
        reg = _registry(1)
        shadow = ShadowSampler(every=2, slots=8)
        b = MicroBatcher(reg, buckets=(1, 2, 4), shadow=shadow,
                         max_delay_s=0.01)
        b.start()
        try:
            futs = [b.submit(np.full(DIM, float(i), np.float32))
                    for i in range(6)]
            for f in futs:
                f.result(10)
        finally:
            b.stop()
        rows = shadow.snapshot()
        assert len(rows) == 3             # arrivals 0, 2, 4


# -- divergence --------------------------------------------------------------

class TestDivergence:
    def test_argmax_heads(self):
        y1 = np.eye(4, dtype=np.float32)
        y2 = y1.copy()
        y2[0] = [0, 9, 0, 0]              # one row's argmax flips
        assert _divergence(y1, y1) == 0.0
        assert _divergence(y1, y2) == 0.25

    def test_tie_flips_are_not_divergence(self):
        """A row both models score as a tie (the bias alone, ~1e-3 apart)
        has no prediction to change: its argmax is last-ulp arithmetic.
        One model deciding the pair is enough to count the flip."""
        live = np.tile(np.float32([5e-4, 1e-3, 0, 0]), (4, 1))
        canary = np.tile(np.float32([1e-3, 5e-4, 0, 0]), (4, 1))
        assert live.argmax(-1)[0] != canary.argmax(-1)[0]
        assert _divergence(live, canary) == 0.0
        canary[0] = [3.0, 0, 0, 0]        # the canary now DECIDES row 0
        assert _divergence(live, canary) == 0.25
        canary[1] = np.nan                # NaN argmax is 0: still counts
        assert _divergence(live, canary) == 0.5

    def test_scalar_outputs_use_relative_tolerance(self):
        y1 = np.ones((8, 1), np.float32) * 100
        assert _divergence(y1, y1 * (1 + 1e-6)) == 0.0
        assert _divergence(y1, y1 * 1.5) == 1.0

    def test_nonfinite_canary_rows_count_as_divergent(self):
        y1 = np.ones((4, 1), np.float32)
        y2 = y1.copy()
        y2[1] = np.nan
        assert _divergence(y1, y2) == 0.25


# -- the verdict matrix: each signal failing ALONE ---------------------------

class _FakeHealth:
    def __init__(self, round_idx, ok):
        self._h = {"round": round_idx,
                   "alarms": {"drift": {"value": 1.0, "threshold": 2.0,
                                        "ok": ok}}}

    def healthz(self):
        return self._h


class TestVerdictMatrix:
    def _shadowed(self, reg, rows=8):
        shadow = ShadowSampler(every=1, slots=rows)
        for i in range(rows):
            x = np.zeros(DIM, np.float32)
            x[0] = float(i + 1)
            shadow.offer(x)
        return shadow

    def test_all_pass_promotes(self):
        reg = _registry(1)
        rc = _controller(reg, shadow=self._shadowed(reg),
                         health=_FakeHealth(2, ok=True),
                         eval_fn=lambda p: 0.9)
        # same weights as live under a new version: zero divergence
        v = rc.offer(_params(1), 2, round_idx=2)
        assert v["decision"] == "promote" and reg.version == 2
        assert not any(s["vacuous"] for s in v["signals"].values())
        assert v["signals"]["shadow"]["divergence"] == 0.0

    def test_shadow_fails_alone(self):
        reg = _registry(1)
        rc = _controller(reg, shadow=self._shadowed(reg),
                         health=_FakeHealth(2, ok=True),
                         eval_fn=lambda p: 0.9, divergence_budget=0.0)
        # version-fingerprinted params argmax a different class per
        # version, so every shadow row diverges
        v = rc.offer(_params(2), 2, round_idx=2)
        assert v["decision"] == "rollback"
        assert v["failed_signals"] == ["shadow"]
        assert v["signals"]["shadow"]["divergence"] == 1.0
        assert reg.version == 1 and 2 not in reg.versions()

    def test_health_fails_alone(self):
        reg = _registry(1)
        rc = _controller(reg, health=_FakeHealth(2, ok=False),
                         eval_fn=lambda p: 0.9)
        v = rc.offer(_params(2), 2, round_idx=2)
        assert v["failed_signals"] == ["health"]
        assert reg.version == 1

    def test_eval_fails_alone(self):
        reg = _registry(1)
        scores = iter([0.9, 0.5])
        rc = _controller(reg, health=_FakeHealth(2, ok=True),
                         eval_fn=lambda p: next(scores))
        rc.offer(_params(2), 2, round_idx=2)     # promotes, baseline 0.9
        v = rc.offer(_params(3), 3, round_idx=3)
        assert v["failed_signals"] == ["eval"]
        assert v["signals"]["eval"]["baseline"] == 0.9
        assert reg.version == 2

    def test_eval_within_tolerance_promotes(self):
        reg = _registry(1)
        scores = iter([0.9, 0.89])
        rc = _controller(reg, eval_fn=lambda p: next(scores),
                         eval_tolerance=0.02)
        rc.offer(_params(2), 2, round_idx=2)
        v = rc.offer(_params(3), 3, round_idx=3)
        assert v["decision"] == "promote"

    def test_nonfinite_eval_fails(self):
        reg = _registry(1)
        rc = _controller(reg, eval_fn=lambda p: float("nan"))
        v = rc.offer(_params(2), 2, round_idx=2)
        assert v["failed_signals"] == ["eval"]

    def test_vacuous_passes_are_named(self):
        """No shadow traffic, no health record, no eval_fn: the gate
        degrades to availability but every vacuous pass is visible."""
        reg = _registry(1)
        rc = _controller(reg)
        v = rc.offer(_params(2), 2, round_idx=2)
        assert v["decision"] == "promote"
        assert all(s["vacuous"] for s in v["signals"].values())

    def test_health_round_mismatch_is_vacuous_and_named(self):
        reg = _registry(1)
        rc = _controller(reg, health=_FakeHealth(7, ok=False))
        v = rc.offer(_params(2), 2, round_idx=2)
        assert v["decision"] == "promote"   # alarm is for another round
        assert v["signals"]["health"]["vacuous"]
        assert v["signals"]["health"]["expected_round"] == 2

    def test_first_release_has_no_live_model_shadow_vacuous(self):
        reg = ModelRegistry(_linear_apply(), history=8)
        shadow = ShadowSampler(every=1, slots=4)
        shadow.offer(np.ones(DIM, np.float32))
        rc = _controller(reg, shadow=shadow)
        v = rc.offer(_params(1), 1, round_idx=1)
        assert v["decision"] == "promote"
        assert v["signals"]["shadow"]["vacuous"]  # nothing to diverge FROM

    def test_stale_version_is_refused(self):
        reg = _registry(1, 2)
        rc = _controller(reg)
        v = rc.offer(_params(2), 2, round_idx=2)
        assert v["decision"] == "stale" and reg.version == 2


# -- cooldown / backoff ------------------------------------------------------

class TestCooldownBackoff:
    def test_exponential_backoff_caps_and_resets(self):
        reg = _registry(1)
        clock = [0.0]
        rc = ReleaseController(reg, eval_fn=lambda p: float("nan"),
                               cooldown_s=5.0, backoff=2.0,
                               max_cooldown_s=15.0,
                               clock=lambda: clock[0])
        cooldowns = []
        for i, v in enumerate(range(2, 6)):
            verdict = rc.offer(_params(v), v, round_idx=v)
            assert verdict["decision"] == "rollback"
            cooldowns.append(verdict["cooldown_s"])
            clock[0] += 100.0             # wait out each cooldown
        assert cooldowns == [5.0, 10.0, 15.0, 15.0]   # 2x, capped

        rc.eval_fn = lambda p: 0.9
        clock[0] += 100.0
        assert rc.offer(_params(9), 9, round_idx=9)["decision"] == "promote"
        rc.eval_fn = lambda p: float("nan")
        v = rc.offer(_params(10), 10, round_idx=10)
        assert v["cooldown_s"] == 5.0     # success reset the ladder

    def test_cooldown_refuses_offers_without_publishing(self):
        reg = _registry(1)
        clock = [0.0]
        rc = ReleaseController(reg, eval_fn=lambda p: float("nan"),
                               cooldown_s=30.0, backoff=2.0,
                               max_cooldown_s=60.0,
                               clock=lambda: clock[0])
        rc.offer(_params(2), 2, round_idx=2)           # rollback, arms it
        rc.eval_fn = lambda p: 0.9
        v = rc.offer(_params(3), 3, round_idx=3)
        assert v["decision"] == "cooldown"
        assert 3 not in reg.versions()    # refused BEFORE canary publish
        clock[0] = 31.0
        assert rc.offer(_params(3), 3, round_idx=3)["decision"] == "promote"

    def test_invalid_config_refused(self):
        reg = _registry(1)
        with pytest.raises(ValueError):
            ReleaseController(reg, divergence_budget=1.5)
        with pytest.raises(ValueError):
            ReleaseController(reg, backoff=0.5)
        with pytest.raises(ValueError):
            ReleaseController(reg, cooldown_s=10.0, max_cooldown_s=1.0)


# -- crash consistency -------------------------------------------------------

class TestCrashConsistency:
    def _crc(self, reg):
        from fedml_tpu.utils.journal import tree_crc
        return tree_crc(reg.current().params)

    def test_kill_pre_promote_recovers_to_pre_state(self):
        reg = _registry(1)
        pre = self._crc(reg)
        fl = Faultline([CrashSpec("canary_promote", hit=1)])
        rc = _controller(reg, faultline=fl)
        with pytest.raises(ActorKilled):
            rc.offer(_params(2), 2, round_idx=2)
        # killed between verdict and swap: live is EXACTLY pre-state,
        # the canary lingers unresolved
        assert self._crc(reg) == pre and reg.canaries() == [2]
        fl.respawn()
        rc2 = _controller(reg, faultline=fl)
        r = rc2.recover()
        assert r["discarded"] == [2] and reg.canaries() == []
        assert self._crc(reg) == pre
        # the re-driven offer promotes (the spec fired once)
        assert rc2.offer(_params(2), 2,
                         round_idx=2)["decision"] == "promote"

    def test_kill_post_promote_recovers_to_post_state(self):
        reg = _registry(1)
        fl = Faultline([CrashSpec("canary_promote", hit=2)])
        rc = _controller(reg, faultline=fl)
        with pytest.raises(ActorKilled):
            rc.offer(_params(2), 2, round_idx=2)
        post = self._crc(reg)
        assert reg.version == 2           # swap landed before the kill
        from fedml_tpu.utils.journal import tree_crc
        assert post == tree_crc(_params(2))
        fl.respawn()
        rc2 = _controller(reg, faultline=fl)
        assert rc2.recover()["discarded"] == []   # nothing half-done
        # re-driving the same verdict is idempotent
        assert rc2.offer(_params(2), 2,
                         round_idx=2)["decision"] == "stale"
        assert self._crc(reg) == post

    def test_kill_around_rollback_never_serves_canary(self):
        reg = _registry(1)
        pre = self._crc(reg)
        for hit in (1, 2):
            fl = Faultline([CrashSpec("canary_rollback", hit=hit)])
            rc = _controller(reg, eval_fn=lambda p: float("nan"),
                             faultline=fl)
            with pytest.raises(ActorKilled):
                rc.offer(_params(2), 2, round_idx=2)
            assert self._crc(reg) == pre  # live never moved either way
            fl.respawn()
            _controller(reg).recover()
            assert reg.canaries() == []

    def test_release_journal_survives_disk_fault(self, tmp_path):
        reg = _registry(1)
        path = str(tmp_path / "release.jsonl")
        inj = DiskFaultInjector(
            [DiskFaultSpec("release_journal", hit=2, torn=True)]).install()
        try:
            rc = _controller(reg, journal_path=path)
            rc.offer(_params(2), 2, round_idx=2)
            rc.offer(_params(3), 3, round_idx=3)   # torn write: disables
            rc.offer(_params(4), 4, round_idx=4)
        finally:
            inj.remove()
        assert [v["decision"] for v in rc.verdicts] == ["promote"] * 3
        with open(path) as f:
            lines = f.read().splitlines()
        assert json.loads(lines[0])["version"] == 2
        assert len(lines) == 2            # line 2 is the torn tail
        with pytest.raises(json.JSONDecodeError):
            json.loads(lines[1])


# -- checkpoint watcher: torn/partial file hardening -------------------------

def _ck_state(i):
    rng = np.random.RandomState(i)
    return {"params": {"w": rng.randn(DIM, CLASSES).astype(np.float32),
                       "b": rng.randn(CLASSES).astype(np.float32)},
            "round_idx": np.asarray(i, np.int64)}


class TestWatcherManifest:
    def test_save_writes_manifest_and_watcher_verifies(self, tmp_path):
        from fedml_tpu.utils.checkpoint import (RoundCheckpointer,
                                                manifest_path)
        ck_dir = str(tmp_path / "ck")
        ck = RoundCheckpointer(ck_dir, save_every=1)
        ck.save(0, _ck_state(0))
        ck.close()
        m = json.load(open(manifest_path(ck_dir, 0)))
        assert m["step"] == 0 and m["algo"] == "crc32" and "params" in m["crc"]
        reg = ModelRegistry(_linear_apply(), history=8)
        w = CheckpointWatcher(reg, ck_dir, poll_s=0.05)
        assert w.poll_once() == 1 and reg.version == 0

    def test_crc_mismatch_skips_and_warns(self, tmp_path):
        from fedml_tpu.utils.checkpoint import (RoundCheckpointer,
                                                manifest_path)
        ck_dir = str(tmp_path / "ck")
        ck = RoundCheckpointer(ck_dir, save_every=1)
        ck.save(0, _ck_state(0))
        ck.save(1, _ck_state(1))
        ck.close()
        m = json.load(open(manifest_path(ck_dir, 1)))
        m["crc"]["params"] += 1           # simulate torn orbax payload
        with open(manifest_path(ck_dir, 1), "w") as f:
            json.dump(m, f)
        reg = ModelRegistry(_linear_apply(), history=8)
        w = CheckpointWatcher(reg, ck_dir, poll_s=0.05)
        assert w.poll_once() == 1         # step 1 skipped, step 0 served
        assert reg.version == 0
        assert w.poll_once() == 0         # skip is sticky, no spin

    def test_torn_manifest_skips_step(self, tmp_path):
        from fedml_tpu.utils.checkpoint import (RoundCheckpointer,
                                                manifest_path)
        ck_dir = str(tmp_path / "ck")
        ck = RoundCheckpointer(ck_dir, save_every=1)
        ck.save(0, _ck_state(0))
        ck.close()
        with open(manifest_path(ck_dir, 0), "w") as f:
            f.write('{"step": 0, "algo": "crc32", "crc": {"par')  # torn
        reg = ModelRegistry(_linear_apply(), history=8)
        w = CheckpointWatcher(reg, ck_dir, poll_s=0.05)
        assert w.poll_once() == 0 and reg.version is None

    def test_manifest_write_fault_falls_back_to_unverified(self, tmp_path):
        """ENOSPC on the manifest channel: the checkpoint itself stays
        durable and the watcher serves it on the legacy unverified path."""
        from fedml_tpu.utils.checkpoint import (RoundCheckpointer,
                                                manifest_path)
        ck_dir = str(tmp_path / "ck")
        inj = DiskFaultInjector(
            [DiskFaultSpec("checkpoint_manifest", hit=1)]).install()
        try:
            ck = RoundCheckpointer(ck_dir, save_every=1)
            ck.save(0, _ck_state(0))
            ck.close()
        finally:
            inj.remove()
        assert not os.path.exists(manifest_path(ck_dir, 0))
        reg = ModelRegistry(_linear_apply(), history=8)
        w = CheckpointWatcher(reg, ck_dir, poll_s=0.05)
        assert w.poll_once() == 1 and reg.version == 0

    def test_manifests_pruned_with_retention_gc(self, tmp_path):
        from fedml_tpu.utils.checkpoint import (MANIFEST_DIRNAME,
                                                RoundCheckpointer)
        ck_dir = str(tmp_path / "ck")
        ck = RoundCheckpointer(ck_dir, save_every=1, keep_last_n=2)
        for i in range(5):
            ck.save(i, _ck_state(i))
        ck.close()
        stems = sorted(int(n[:-5]) for n in
                       os.listdir(os.path.join(ck_dir, MANIFEST_DIRNAME)))
        assert stems == [3, 4]


# -- wave-summary poisoning (robust/adversary.py) ----------------------------

class TestWaveAdversary:
    def test_parse_spec(self):
        from fedml_tpu.robust.adversary import parse_wave_adversary_spec
        atks = parse_wave_adversary_spec("0:1:sign_flip,2:0:scale:50")
        assert set(atks) == {(0, 1), (2, 0)}
        assert atks[(2, 0)].kind == "scale" and atks[(2, 0)].param == 50.0
        for bad in ("1:sign_flip", "0:0:nope", "0:0:scale:x",
                    "0:0:scale,0:0:scale"):
            with pytest.raises(ValueError):
                parse_wave_adversary_spec(bad)

    def test_poison_kinds(self):
        from fedml_tpu.robust.adversary import (WaveAttack,
                                                poison_wave_summary)
        g = {"w": np.zeros(4, np.float32)}
        m = {"w": np.ones(4, np.float32)}
        flip = poison_wave_summary(WaveAttack(0, 0, "sign_flip", 1.0), m, g)
        np.testing.assert_allclose(flip["w"], -1.0)
        scale = poison_wave_summary(WaveAttack(0, 0, "scale", 10.0), m, g)
        np.testing.assert_allclose(scale["w"], 10.0)
        nan = poison_wave_summary(WaveAttack(0, 0, "nan_bomb", 1.0), m, g)
        assert np.isnan(nan["w"]).any()

    def test_gauss_is_seeded(self):
        from fedml_tpu.robust.adversary import (WaveAttack,
                                                poison_wave_summary)
        g = {"w": np.zeros(8, np.float32)}
        m = {"w": np.ones(8, np.float32)}
        atk = WaveAttack(1, 2, "gauss", 0.5)
        a = poison_wave_summary(atk, m, g, seed=3)
        b = poison_wave_summary(atk, m, g, seed=3)
        c = poison_wave_summary(atk, m, g, seed=4)
        np.testing.assert_array_equal(a["w"], b["w"])
        assert not np.array_equal(a["w"], c["w"])


# -- end-to-end: poisoned round contained before serving ---------------------

def _cross_device_fixture(**cfg_kw):
    from fedml_tpu.algorithms.cross_device import (CrossDevice,
                                                   CrossDeviceConfig)
    from fedml_tpu.data import load_data
    from fedml_tpu.experiments.models import create_workload, sample_shape_of
    data = load_data("mnist", data_dir=None, batch_size=4, num_clients=24,
                     seed=0)
    wl = create_workload("lr", "mnist", data.class_num,
                         sample_shape_of(data))
    cfg_kw.setdefault("comm_round", 3)
    cfg_kw.setdefault("client_num_per_round", 12)
    cfg_kw.setdefault("epochs", 1)
    cfg_kw.setdefault("batch_size", 4)
    cfg_kw.setdefault("wave_size", 6)
    cfg_kw.setdefault("seed", 0)
    cfg_kw.setdefault("frequency_of_the_test", 10)
    return data, wl, CrossDevice, CrossDeviceConfig(**cfg_kw)


def test_poisoned_round_rolled_back_before_serving():
    """The ISSUE 16 containment story, in miniature: a cross-device run
    publishes every round through the gate with real shadow traffic;
    the seeded poisoned round's version must never reach the live slot,
    and the clean rounds around it must promote.  (Clean rounds move
    none of the 64 shadow argmaxes on this seed; the scale:1e6 poison
    moves 94% — the 0.1 budget separates them with margin either way.)"""
    data, wl, CrossDevice, cfg = _cross_device_fixture(
        comm_round=4, wave_adversary="3:0:scale:1000000",
        admission="off")
    apply_fn = jax.jit(lambda p, x: wl.apply(p, x))
    reg = ModelRegistry(apply_fn, history=8)
    shadow = ShadowSampler(every=1, slots=64)
    # shadow traffic is REAL requests: the stacked test split pads every
    # client to a whole batch with all-zero rows (mask 0), which no user
    # sends — and which every model scores as a tie (the bias alone)
    xt = np.asarray(data.test["x"])
    real = np.asarray(data.test["mask"]).reshape(-1) > 0
    for row in xt.reshape(-1, xt.shape[-1])[real][:64]:
        shadow.offer(row)

    rc = ReleaseController(reg, shadow=shadow, divergence_budget=0.1,
                           cooldown_s=0.0, max_cooldown_s=0.0)
    engine = CrossDevice(wl, data, cfg,
                         publish=lambda p, v: rc.offer(
                             jax.tree.map(np.asarray, p), v,
                             round_idx=v - 1))
    engine.run()
    decisions = {v["version"]: v["decision"] for v in rc.verdicts}
    assert decisions == {1: "promote", 2: "promote", 3: "promote",
                         4: "rollback"}, rc.verdicts
    poisoned = rc.verdicts[-1]
    assert poisoned["failed_signals"] == ["shadow"]
    assert poisoned["signals"]["shadow"]["divergence"] > 0.5
    assert 4 not in reg.versions()        # the poisoned global is GONE
    assert reg.version == 3               # serving stayed on clean
    for v in rc.verdicts:
        assert v.get("live_version") != 4  # never live, not for a moment


def test_wave_poison_requires_flag_and_is_exact_when_clean():
    """Without --wave_adversary the engine byte-matches the pre-ISSUE
    path (no attacks parsed, fold_wave untouched)."""
    data, wl, CrossDevice, cfg = _cross_device_fixture(comm_round=1)
    e = CrossDevice(wl, data, cfg)
    assert e._wave_attacks == {}
    import dataclasses as dc
    cfg2 = dc.replace(cfg, wave_adversary="0:0:sign_flip")
    e2 = CrossDevice(wl, data, cfg2)
    assert set(e2._wave_attacks) == {(0, 0)}


# -- config gates ------------------------------------------------------------

class TestConfigGates:
    def test_release_gate_requires_serve_port(self):
        from fedml_tpu.experiments.main import main
        with pytest.raises(ValueError, match="--release_gate"):
            main(["--release_gate", "true"])

    def test_release_shadow_params_validated(self):
        from fedml_tpu.experiments.main import main
        with pytest.raises(ValueError, match="release_shadow"):
            main(["--release_gate", "true", "--serve_port", "18099",
                  "--algo", "cross_silo", "--release_shadow_every", "0"])

    def test_wave_adversary_requires_cross_device(self):
        from fedml_tpu.experiments.main import main
        with pytest.raises(ValueError, match="--wave_adversary"):
            main(["--wave_adversary", "0:0:sign_flip"])

    def test_adversary_on_cross_device_points_at_wave_adversary(self):
        from fedml_tpu.experiments.main import main
        with pytest.raises(ValueError, match="--wave_adversary"):
            main(["--algo", "cross_device", "--adversary", "1:sign_flip"])
