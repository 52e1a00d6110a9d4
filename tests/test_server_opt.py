"""Server-optimizer spine tests (ISSUE 18).

The seam contract, pinned:

* ``--server_opt plain`` is BIT-IDENTICAL to today's mean finalize —
  ``apply`` returns the finalized tree itself, on the replicated AND
  the sharded wire (no silent behavior change for every existing run).
* The seam's momentum/adam match the standalone optax trajectories on a
  fixed pseudo-gradient sequence (tolerance stated per test); fedac
  matches a NumPy transcription of Yuan & Ma '20 Alg. 1's server form
  and collapses to plain SGD at (alpha=1, beta=1, gamma=lr).
* Optimizer state round-trips ``state_dict``/``load_state_dict``
  bit-exactly — replicated and laid out along a PR 14 shard plan — and
  every foreign snapshot (different optimizer, different
  hyperparameters, different shard plan, sharded<->replicated) is
  refused with the named ``ServerOptMismatchError``.
* Kill -> resume with live momentum/adam/fedac state is bit-identical
  to the uncrashed run (the PR 12 recovery contract extends to the
  optimizer slots).
* The adaptive controller is a deterministic pure function of the
  health-line trace, and its state resumes mid-trajectory.
* Every incompatible flag combination fails loudly at config time.
"""

import json

import jax
import numpy as np
import optax
import pytest

from fedml_tpu.algorithms.cross_silo import (FedAvgClientActor,
                                             FedAvgServerActor)
from fedml_tpu.comm.local import LocalHub
from fedml_tpu.core.stream_agg import StreamingAggregator
from fedml_tpu.robust.faultline import ActorKilled, CrashSpec, Faultline
from fedml_tpu.server_opt import (SERVER_OPT_NAMES, AdaptiveController,
                                  ServerOptConfigError,
                                  ServerOptMismatchError, ServerOptimizer)
from fedml_tpu.shard_spine import build_shard_spine
from fedml_tpu.utils.checkpoint import RoundCheckpointer
from fedml_tpu.utils.journal import RoundJournal


def _params(seed=3, shape=(4, 3)):
    rng = np.random.RandomState(seed)
    return {"dense": {"kernel": rng.randn(*shape).astype(np.float32),
                      "bias": rng.randn(shape[-1]).astype(np.float32)}}


def _deltas(template, steps, seed=7):
    """A fixed pseudo-gradient sequence, deterministic in seed."""
    rng = np.random.RandomState(seed)
    return [jax.tree.map(
        lambda v: rng.randn(*np.shape(v)).astype(np.float32) * 0.1,
        template) for _ in range(steps)]


def _leaves_equal(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _train_fn(silo):
    """Deterministic in (silo, round): replayed rounds reproduce the
    exact bytes (the recovery contract's silo half)."""
    def fn(params, client_idx, round_idx):
        rng = np.random.RandomState(1000 * silo + int(round_idx or 0))
        return jax.tree.map(
            lambda v: v + rng.randn(*np.shape(v)).astype(np.float32) * 0.1,
            params), 10 + silo
    return fn


def _run_stream(init, rounds, n=3, server_opt=None, ck=None, jr=None,
                fl=None, spine=None, extra_state=None):
    """One pump-mode stream federation (test_crash_recovery harness),
    with the server-optimizer seam on the wire."""
    hub = LocalHub(codec_roundtrip=True)
    agg = spine.agg if spine is not None else StreamingAggregator(
        init, method="mean", kind="params", norm_clip=1.0, seed=0,
        reservoir_k=8)
    server = FedAvgServerActor(
        hub.transport(0), init, n, n, rounds, checkpointer=ck,
        stream_agg=agg, shard_wire=spine, journal=jr, faultline=fl,
        server_opt=server_opt, extra_state=extra_state)
    silos = [FedAvgClientActor(i, hub.transport(i), _train_fn(i))
             for i in range(1, n + 1)]
    server.register_handlers()
    for s in silos:
        s.register_handlers()
    server.start()
    hub.pump()
    return server


# ---------------------------------------------------------------------------
# the seam, unit-level: each optimizer against its reference math
# ---------------------------------------------------------------------------

class TestSeamUnit:
    def test_plain_apply_returns_finalized_itself(self):
        init = _params()
        opt = ServerOptimizer("plain", init)
        finalized = _params(5)
        assert opt.apply(init, finalized, 0) is finalized

    def test_plain_apply_delta_is_exact_sgd(self):
        init = _params()
        opt = ServerOptimizer("plain", init, lr=0.5)
        delta = _deltas(init, 1)[0]
        got = opt.apply_delta(init, delta, 0)
        want = jax.tree.map(lambda w, d: w - np.float32(0.5) * d,
                            init, delta)
        assert _leaves_equal(got, want)

    def test_momentum_matches_optax(self):
        init = _params()
        opt = ServerOptimizer("momentum", init, lr=0.3, momentum=0.9)
        ref_opt = optax.sgd(0.3, momentum=0.9)
        ref_state, ref_w = ref_opt.init(init), init
        w = init
        for d in _deltas(init, 5):
            w = opt.apply_delta(w, d, 0)
            upd, ref_state = ref_opt.update(d, ref_state, ref_w)
            ref_w = optax.apply_updates(ref_w, upd)
            for a, b in zip(jax.tree.leaves(w), jax.tree.leaves(ref_w)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-6, atol=1e-7)

    def test_adam_matches_optax(self):
        init = _params()
        opt = ServerOptimizer("adam", init, lr=0.05, beta1=0.9,
                              beta2=0.999, eps=1e-8)
        ref_opt = optax.adam(0.05, b1=0.9, b2=0.999, eps=1e-8)
        ref_state, ref_w = ref_opt.init(init), init
        w = init
        for d in _deltas(init, 5):
            w = opt.apply_delta(w, d, 0)
            upd, ref_state = ref_opt.update(d, ref_state, ref_w)
            ref_w = optax.apply_updates(ref_w, upd)
            for a, b in zip(jax.tree.leaves(w), jax.tree.leaves(ref_w)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=2e-5, atol=1e-6)

    def test_fedac_default_knobs_collapse_to_plain_sgd(self):
        """(alpha=1, beta=1, gamma=lr): x_md == x == w inductively, so
        apply() lands exactly on the finalized tree — the fedac.py
        collapse, at the seam."""
        init = _params()
        opt = ServerOptimizer("fedac", init, lr=1.0)
        w = init
        for seed in (5, 6):
            finalized = _params(seed)
            w = opt.apply(w, finalized, 0)
            for a, b in zip(jax.tree.leaves(w),
                            jax.tree.leaves(finalized)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-6, atol=1e-7)

    def test_fedac_matches_numpy_reference(self):
        init = _params()
        lr, gamma, alpha, beta = 0.4, 0.6, 2.0, 3.0
        opt = ServerOptimizer("fedac", init, lr=lr, fedac_gamma=gamma,
                              fedac_alpha=alpha, fedac_beta=beta)
        w = init
        for d in _deltas(init, 4):
            w = opt.apply_delta(w, d, 0)
        # NumPy transcription, run independently (x^0 = x^ag,0)
        w_ag = jax.tree.map(np.asarray, init)
        x = jax.tree.map(np.asarray, init)
        for d in _deltas(init, 4):
            x_md = jax.tree.map(
                lambda xi, ai: (xi / beta
                                + (1 - 1 / beta) * ai).astype(np.float32),
                x, w_ag)
            w_ag = jax.tree.map(
                lambda m, di: (m - lr * di).astype(np.float32), x_md, d)
            x = jax.tree.map(
                lambda xi, m, di: ((1 - 1 / alpha) * xi + m / alpha
                                   - gamma * di).astype(np.float32),
                x, x_md, d)
        for a, b in zip(jax.tree.leaves(w), jax.tree.leaves(w_ag)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)

    def test_fedac_mu_derives_the_paper_coupling(self):
        from fedml_tpu.algorithms.fedac import fedac_coupling
        init = _params()
        opt = ServerOptimizer("fedac", init, lr=0.1, fedac_mu=0.5,
                              local_steps=4)
        gamma, alpha, beta = fedac_coupling(0.1, 0.5, 4)
        assert opt.coupling == {"gamma": gamma, "alpha": alpha,
                                "beta": beta}

    def test_fedac_refuses_invalid_coupling(self):
        with pytest.raises(ServerOptConfigError, match="alpha >= 1"):
            ServerOptimizer("fedac", _params(), lr=0.1,
                            fedac_alpha=0.5, fedac_gamma=0.1)

    def test_unknown_name_refused(self):
        with pytest.raises(ServerOptConfigError, match="unknown"):
            ServerOptimizer("sgdx", _params())


# ---------------------------------------------------------------------------
# state round-trip: bit-exact, refusal-guarded, replicated AND sharded
# ---------------------------------------------------------------------------

class TestStateRoundtrip:
    @pytest.mark.parametrize("name", ["momentum", "adam", "fedac"])
    def test_roundtrip_bit_exact_and_same_next_step(self, name):
        init = _params()
        kw = dict(lr=0.3, fedac_gamma=0.2, fedac_alpha=2.0,
                  fedac_beta=3.0)
        opt = ServerOptimizer(name, init, **kw)
        w = init
        for d in _deltas(init, 2):
            w = opt.apply_delta(w, d, 0)
        snap = opt.state_dict()
        opt2 = ServerOptimizer(name, init, **kw)
        opt2.load_state_dict(snap)
        assert _leaves_equal(opt2.state_dict(), snap)
        nxt = _deltas(init, 1, seed=11)[0]
        assert _leaves_equal(opt.apply_delta(w, nxt, 0),
                             opt2.apply_delta(w, nxt, 0))
        assert _leaves_equal(opt.state_dict(), opt2.state_dict())

    def test_cross_optimizer_snapshot_refused(self):
        init = _params()
        snap = ServerOptimizer("momentum", init).state_dict()
        with pytest.raises(ServerOptMismatchError,
                           match="--server_opt 'momentum'"):
            ServerOptimizer("adam", init).load_state_dict(snap)

    def test_hyperparameter_fingerprint_refused(self):
        init = _params()
        snap = ServerOptimizer("adam", init, lr=0.1).state_dict()
        with pytest.raises(ServerOptMismatchError, match="fingerprint"):
            ServerOptimizer("adam", init, lr=0.2).load_state_dict(snap)

    def test_sharded_roundtrip_and_layout_refusals(self):
        init = {"w": np.random.RandomState(0).randn(16, 16)
                .astype(np.float32)}
        spine = build_shard_spine(init, num_shards=2, min_split_elems=64,
                                  mesh=None)
        opt = ServerOptimizer("adam", init, lr=0.1, plan=spine.plan)
        w = init
        for d in _deltas(init, 2):
            w = opt.apply_delta(w, d, 0)
        snap = opt.state_dict()
        assert "shard_fp" in snap
        opt2 = ServerOptimizer("adam", init, lr=0.1, plan=spine.plan)
        opt2.load_state_dict(snap)
        nxt = _deltas(init, 1, seed=11)[0]
        assert _leaves_equal(opt.apply_delta(w, nxt, 0),
                             opt2.apply_delta(w, nxt, 0))
        assert _leaves_equal(opt.state_dict(), opt2.state_dict())
        # sharded snapshot into a replicated run: refused
        with pytest.raises(ServerOptMismatchError, match="replicated"):
            ServerOptimizer("adam", init, lr=0.1).load_state_dict(snap)
        # replicated snapshot into the sharded spine: refused
        rsnap = ServerOptimizer("adam", init, lr=0.1).state_dict()
        with pytest.raises(ServerOptMismatchError,
                           match="no shard-plan"):
            ServerOptimizer("adam", init, lr=0.1,
                            plan=spine.plan).load_state_dict(rsnap)


# ---------------------------------------------------------------------------
# plain parity, end-to-end: the seam's presence must not move one bit
# ---------------------------------------------------------------------------

class TestPlainParityE2E:
    def test_plain_bit_identical_on_replicated_wire(self):
        init = _params()
        ref = _run_stream(init, 3)
        got = _run_stream(init, 3,
                          server_opt=ServerOptimizer("plain", init))
        assert ref.round_idx == got.round_idx == 3
        assert _leaves_equal(ref.params, got.params)

    def test_plain_bit_identical_on_sharded_wire(self):
        init = {"w": np.random.RandomState(0).randn(16, 16)
                .astype(np.float32)}
        ref = _run_stream(
            init, 3, spine=build_shard_spine(init, num_shards=2,
                                             min_split_elems=64,
                                             mesh=None))
        got = _run_stream(
            init, 3, spine=build_shard_spine(init, num_shards=2,
                                             min_split_elems=64,
                                             mesh=None),
            server_opt=ServerOptimizer("plain", init))
        assert _leaves_equal(ref.params, got.params)

    def test_non_plain_actually_moves_the_global(self):
        init = _params()
        ref = _run_stream(init, 3)
        got = _run_stream(init, 3,
                          server_opt=ServerOptimizer("adam", init,
                                                     lr=0.1))
        assert not _leaves_equal(ref.params, got.params)


# ---------------------------------------------------------------------------
# crash recovery: optimizer slots ride the PR 12 kill -> resume contract
# ---------------------------------------------------------------------------

class TestCrashResume:
    @pytest.mark.parametrize("name", ["momentum", "adam", "fedac"])
    def test_kill_at_checkpoint_write_resumes_bit_identical(
            self, tmp_path, name):
        """Kill mid-checkpoint-write in round 1 of 3 with live optimizer
        state: the resumed run must land bit-identical to the uncrashed
        run — params AND every optimizer slot."""
        init = _params()
        kw = dict(lr=0.3, fedac_gamma=0.2, fedac_alpha=2.0,
                  fedac_beta=3.0)
        opt_ref = ServerOptimizer(name, init, **kw)
        ref = _run_stream(init, 3, server_opt=opt_ref)
        assert ref.round_idx == 3

        opt1 = ServerOptimizer(name, init, **kw)
        fl = Faultline(crashes=[CrashSpec(point="mid_checkpoint_write",
                                          hit=1, round_idx=1)])
        with pytest.raises(ActorKilled):
            _run_stream(
                init, 3, server_opt=opt1,
                ck=RoundCheckpointer(str(tmp_path / "ck"), save_every=1),
                jr=RoundJournal(str(tmp_path / "j"), snapshot_every=1),
                fl=fl,
                extra_state=(lambda: {"srv_opt": opt1.state_dict()},
                             lambda t: opt1.load_state_dict(
                                 t["srv_opt"])))

        opt2 = ServerOptimizer(name, init, **kw)
        resumed = _run_stream(
            init, 3, server_opt=opt2,
            ck=RoundCheckpointer(str(tmp_path / "ck"), save_every=1),
            jr=RoundJournal(str(tmp_path / "j"), snapshot_every=1),
            extra_state=(lambda: {"srv_opt": opt2.state_dict()},
                         lambda t: opt2.load_state_dict(t["srv_opt"])))
        assert resumed.round_idx == 3
        assert _leaves_equal(resumed.params, ref.params)
        assert _leaves_equal(opt2.state_dict(), opt_ref.state_dict())


# ---------------------------------------------------------------------------
# the adaptive controller: deterministic policy, resumable state
# ---------------------------------------------------------------------------

def _line(misaligned=False, blowup=False, starved=False, sev=1.5):
    def alarm(fired):
        return {"ok": not fired, "value": sev if fired else 0.1,
                "threshold": 1.0}
    return {"alarms": {"alignment_collapse": alarm(misaligned),
                       "norm_variance_blowup": alarm(blowup),
                       "participation_starvation": alarm(starved)}}


_TRACE = [_line(), _line(misaligned=True), _line(blowup=True), _line(),
          _line(), _line(), _line(starved=True), _line(), _line(),
          _line(misaligned=True, sev=2.5), _line(), _line()]


class TestController:
    def _mk(self):
        return AdaptiveController(cohort=8, epochs=3, wave_size=4,
                                  min_cohort=2, max_cohort=16,
                                  patience=2)

    def test_same_trace_same_decisions(self):
        a, b = self._mk(), self._mk()
        da = [a.decide(i, l).as_ledger() for i, l in enumerate(_TRACE)]
        db = [b.decide(i, l).as_ledger() for i, l in enumerate(_TRACE)]
        assert da == db
        # the trace actually exercises the policy: growth, cut, decay
        assert any("cohort+" in r for d in da for r in d["reasons"])
        assert any("epochs->" in r for d in da for r in d["reasons"])
        assert any(r.startswith("calm:") for d in da for r in d["reasons"])

    def test_resume_continues_the_same_trajectory(self):
        full, half = self._mk(), self._mk()
        want = [full.decide(i, l).as_ledger()
                for i, l in enumerate(_TRACE)]
        got = [half.decide(i, l).as_ledger()
               for i, l in enumerate(_TRACE[:6])]
        snap = half.state_dict()
        resumed = self._mk()
        resumed.load_state_dict(snap)
        got += [resumed.decide(i + 6, l).as_ledger()
                for i, l in enumerate(_TRACE[6:])]
        assert got == want

    def test_cohort_never_drops_below_baseline(self):
        c = self._mk()
        for i, l in enumerate(_TRACE * 3):
            d = c.decide(i, l)
            assert d.cohort >= 8

    def test_epoch_cuts_are_named_pinned_on_compiled_engines(self):
        c = self._mk()
        c.decide(0, _line(blowup=True))
        d = c.decide(1, _line(blowup=True))
        assert any("epochs" in r and "[pinned:static-shape]" in r
                   for r in d.reasons), d.reasons

    def test_cohort_growth_clamps_at_max_and_names_the_clamp(self):
        c = AdaptiveController(cohort=8, epochs=1, max_cohort=8)
        d = c.decide(0, _line(misaligned=True))
        assert d.cohort == 8
        assert any("clamped" in r for r in d.reasons), d.reasons

    def test_missing_health_line_holds(self):
        c = self._mk()
        d = c.decide(0, None)
        assert d.as_ledger()["reasons"] == ["hold"]
        assert d.cohort == 8 and d.epochs == 3


# ---------------------------------------------------------------------------
# config gates: every bad combination refuses at config time, by name
# ---------------------------------------------------------------------------

class TestConfigGates:
    def _cfg(self, **kw):
        from fedml_tpu.experiments.config import ExperimentConfig
        return ExperimentConfig(**kw)

    @pytest.mark.parametrize("kw,match", [
        (dict(server_opt="sgdx"), "unknown --server_opt"),
        (dict(server_opt="adam", algo="fedopt"),
         "applies to --algo cross_silo"),
        (dict(server_opt="adam", algo="cross_silo", robust_agg="median"),
         "order-statistic finalize"),
        (dict(server_opt="adam", algo="cross_silo", agg_mode="stream",
              secagg="pairwise"), "masked-sum protocol"),
        (dict(server_opt="adam", algo="cross_device",
              local_alg="fednova"), "fednova"),
        (dict(adaptive=True, algo="cross_silo"), "requires --health"),
        (dict(adaptive=True, health=True, algo="async_fl"),
         "no round cohort to pace"),
        (dict(adapt_min_cohort=0), "--adapt_min_cohort must be"),
        (dict(adapt_patience=0), "--adapt_patience must be"),
    ])
    def test_bad_combo_fails_loudly(self, kw, match):
        from fedml_tpu.experiments.main import main
        with pytest.raises(ServerOptConfigError, match=match):
            main(self._cfg(**kw))

    def test_actor_gate_secagg(self):
        from fedml_tpu.secure.protocol import (SecAggServer,
                                               masked_template)
        from fedml_tpu.robust import AdmissionPipeline
        init = _params()
        hub = LocalHub()
        with pytest.raises(ValueError, match="masked-sum"):
            FedAvgServerActor(
                hub.transport(0), init, 2, 2, 1,
                admission=AdmissionPipeline(masked_template(init),
                                            kind="masked"),
                secagg=SecAggServer(threshold=0, clip=64.0,
                                    weight_cap=10.0),
                server_opt=ServerOptimizer("adam", init))

    def test_actor_gate_controller_requires_health(self):
        hub = LocalHub()
        with pytest.raises(ValueError, match="--health"):
            FedAvgServerActor(
                hub.transport(0), _params(), 2, 2, 1,
                stream_agg=StreamingAggregator(_params(), method="mean",
                                               kind="params"),
                controller=AdaptiveController(cohort=2))

    def test_journal_mode_names_the_optimizer(self, tmp_path):
        """A journal written under a non-plain seam must refuse replay
        into a plain run: the optimizer is part of the round mode."""
        init = _params()
        jr = RoundJournal(str(tmp_path / "j"), snapshot_every=1)
        opt = ServerOptimizer("adam", init, lr=0.1)
        server = _run_stream(init, 2, server_opt=opt, jr=jr)
        assert server.round_idx == 2
        assert "srvopt=adam" in server._journal_mode()


# ---------------------------------------------------------------------------
# the CLI: the seam and the controller in the ledger, round for round
# ---------------------------------------------------------------------------

def _cli_arm(run_dir, rounds, *extra):
    """One `--algo cross_silo --agg_mode stream` run on the synthetic(0.5,
    0.5) twin: its accuracy curve and its perf ledger."""
    from fedml_tpu.experiments.main import main
    main(["--algo", "cross_silo", "--agg_mode", "stream", "--model", "lr",
          "--dataset", "synthetic", "--lr", "0.003", "--epochs", "1",
          "--batch_size", "10", "--client_num_in_total", "8",
          "--client_num_per_round", "8", "--comm_round", str(rounds),
          "--frequency_of_the_test", "1", "--seed", "0",
          "--log_stdout", "false", "--perf", "true",
          "--perf_strict", "true", "--run_dir", str(run_dir), *extra])
    with open(run_dir / "metrics.jsonl") as f:
        curve = sorted((r["round"], r["test_acc"])
                       for r in map(json.loads, f) if "test_acc" in r)
    with open(run_dir / "perf.jsonl") as f:
        return curve, [json.loads(line) for line in f]


@pytest.mark.parametrize("rounds", [
    3, pytest.param(30, marks=pytest.mark.slow)])
def test_cli_ledger_names_the_optimizer_and_every_pacing_decision(
        rounds, tmp_path):
    """Server adam with the adaptive controller against plain FedAvg, same
    seed and data: every ledger line of the optimizer arm names the
    optimizer and carries the controller's decision with its reasons,
    neither arm recompiles after round 0, and over 30 rounds the optimizer
    arm ends no less accurate and reaches 45 % no later."""
    plain, plain_rows = _cli_arm(tmp_path / "plain", rounds)
    adam, rows = _cli_arm(tmp_path / "adam", rounds, "--server_opt", "adam",
                          "--server_lr", "0.1", "--adaptive", "true",
                          "--health", "true")
    assert len(rows) == len(plain_rows) == rounds
    for r in rows:
        assert r["server_opt"] == "adam"
        assert r["adapt"]["reasons"]
    assert not any(r["recompiles"] for r in rows[1:] + plain_rows[1:])
    if rounds == 30:
        def rounds_to(curve, target=0.45):
            return next(r + 1 for r, acc in curve if acc >= target)

        assert adam[-1][1] >= plain[-1][1] - 0.02
        assert rounds_to(adam) <= rounds_to(plain)
