"""Federation health observatory (obs/health.py) — ISSUE 9.

The load-bearing pins:

* Welford moments agree with numpy on random streams, and Chan's merge
  (the per-edge rollup combine) agrees with one pass over the union;
* stream and stack agg modes emit IDENTICAL health lines on the
  defended-mean path (same stats from the scan and the fold);
* per-silo fairness counters track quarantine and straggler drops;
* the edge topology's per-frame rollups merge to the flat run's norm
  moments, and the tree stays one-frame-per-round;
* the ledger keeps the torn-tail-tolerant O_APPEND contract and the
  trend gate rejects a malformed ledger;
* alarm threshold edges (breach strictly-above, ok at the threshold);
* the health path is host-side numpy — no jitted stat exists to
  recompile (pinned against the recompile sentry's registry).
"""

import json
import math

import numpy as np
import pytest

import jax

from fedml_tpu.algorithms.cross_silo import (FedAvgClientActor,
                                             FedAvgServerActor, MsgType)
from fedml_tpu.algorithms.hierarchical import EdgeAggregatorActor
from fedml_tpu.comm.local import LocalHub
from fedml_tpu.comm.message import Message
from fedml_tpu.core.stream_agg import StreamingAggregator
from fedml_tpu.obs.health import (HEALTH_SLOS, HealthAccumulator, Welford,
                                  _sketch_f32, merge_moments)
from fedml_tpu.robust import (AdmissionPipeline, Attack, TrustTracker,
                              make_defended_aggregate,
                              make_malicious_train_fn)


def _params(seed=0):
    rng = np.random.RandomState(seed)
    return {"dense": {"kernel": rng.randn(4, 3).astype(np.float32),
                      "bias": rng.randn(3).astype(np.float32)}}


# ---------------------------------------------------------------------------
# the moments themselves
# ---------------------------------------------------------------------------

class TestWelford:
    @pytest.mark.parametrize("seed,n", [(0, 1), (1, 2), (2, 50), (3, 997)])
    def test_matches_numpy_on_random_streams(self, seed, n):
        vals = np.random.RandomState(seed).lognormal(0, 2, n)
        w = Welford()
        for v in vals:
            w.push(float(v))
        assert w.count == n
        assert w.mean == pytest.approx(vals.mean(), rel=1e-12)
        assert w.var == pytest.approx(vals.var(), rel=1e-9, abs=1e-12)
        assert w.std == pytest.approx(vals.std(), rel=1e-9, abs=1e-12)
        assert w.min == vals.min() and w.max == vals.max()

    def test_empty_summary_is_nulls(self):
        s = Welford().summary()
        assert s == {"count": 0, "mean": None, "std": None,
                     "min": None, "max": None}

    def test_merge_moments_equals_one_pass_over_the_union(self):
        rng = np.random.RandomState(7)
        chunks = [rng.rand(n) * 10 for n in (5, 1, 17, 40)]
        summaries = []
        for c in chunks:
            w = Welford()
            for v in c:
                w.push(float(v))
            summaries.append(w.summary())
        merged = merge_moments(summaries)
        union = np.concatenate(chunks)
        assert merged["count"] == union.size
        assert merged["mean"] == pytest.approx(union.mean(), rel=1e-12)
        assert merged["std"] == pytest.approx(union.std(), rel=1e-9)
        assert merged["min"] == union.min()
        assert merged["max"] == union.max()
        # empty / null summaries merge as absence, not as zeros
        assert merge_moments(summaries + [Welford().summary(), {}]) == merged


def test_sketch_is_deterministic_and_rescales_norms():
    rng = np.random.RandomState(3)
    tree = {"a": rng.randn(1000).astype(np.float32),
            "b": rng.randn(3000).astype(np.float32)}
    full, s_full = _sketch_f32(tree, 0)
    assert s_full == 1.0 and full.size == 4000
    sk1, scale = _sketch_f32(tree, 400)
    sk2, scale2 = _sketch_f32(tree, 400)
    np.testing.assert_array_equal(sk1, sk2)
    assert scale == scale2 > 1.0
    # proportional prefixes: each leaf contributes ~size*cap/total
    assert sk1.size == 1000 * 400 // 4000 + 3000 * 400 // 4000
    # rescaled sketch norm estimates the full norm (generic vector)
    est = float(np.linalg.norm(sk1)) * scale
    true = float(np.linalg.norm(full))
    assert est == pytest.approx(true, rel=0.15)


# ---------------------------------------------------------------------------
# the accumulator unit protocol
# ---------------------------------------------------------------------------

def _obs(h, silo, tree, w, **kw):
    h.observe_admitted(silo, tree, w, **kw)


class TestAccumulator:
    def test_norm_moments_and_alignment(self, tmp_path):
        h = HealthAccumulator(ledger_path=str(tmp_path / "health.jsonl"))
        ref = {"a": np.zeros(8, np.float32)}
        h.round_start(0, ref, expected=[1, 2, 3])
        d1 = {"a": np.ones(8, np.float32)}
        d2 = {"a": np.full(8, 2.0, np.float32)}       # same direction
        d3 = {"a": -np.ones(8, np.float32)}           # anti-aligned
        _obs(h, 1, d1, 10.0)
        _obs(h, 2, d2, 10.0)
        _obs(h, 3, d3, 10.0)
        line = h.round_end(0, new_global=d1)
        norms = [math.sqrt(8), 2 * math.sqrt(8), math.sqrt(8)]
        assert line["norm"]["count"] == 3
        assert line["norm"]["mean"] == pytest.approx(np.mean(norms))
        assert line["norm"]["std"] == pytest.approx(np.std(norms))
        # alignment observed from the 2nd upload on: cos(d2, d1)=1,
        # cos(d3, d1*10+d2*10)=-1
        assert line["alignment"]["count"] == 2
        assert line["alignment"]["mean"] == pytest.approx(0.0, abs=1e-6)
        assert line["alignment"]["min"] == pytest.approx(-1.0)
        assert line["global_delta_norm"] == pytest.approx(math.sqrt(8))
        assert line["weight"] == pytest.approx(30.0)
        # the admission-verdict norm is banked verbatim, not recomputed
        h.round_start(1, ref, expected=[1])
        _obs(h, 1, d1, 1.0, norm=123.5)
        line = h.round_end(1, new_global=ref)
        assert line["norm"]["mean"] == pytest.approx(123.5)

    def test_delta_kind_reads_uploads_raw(self):
        h = HealthAccumulator(kind="delta", alarms=False)
        h.round_start(0, {"a": np.full(4, 7.0, np.float32)})
        _obs(h, 1, {"a": np.ones(4, np.float32)}, 5.0, staleness=2)
        line = h.round_end(0, new_global={"a": np.full(4, 7.5, np.float32)})
        assert line["norm"]["mean"] == pytest.approx(2.0)  # ||ones(4)||
        assert line["staleness"]["mean"] == 2.0
        # the reference still anchors the round-over-round delta norm
        assert line["global_delta_norm"] == pytest.approx(1.0)

    def test_fairness_counters_under_drop_reject_exclusion(self):
        h = HealthAccumulator(alarms=False)
        ref = {"a": np.zeros(2, np.float32)}
        up = {"a": np.ones(2, np.float32)}
        for r in range(3):
            h.round_start(r, ref, expected=[1, 2, 3], excluded=[4])
            _obs(h, 1, up, 1.0)
            h.observe_rejected(2, "nonfinite")
            # silo 3 never reports (straggler drop)
            h.round_end(r, new_global=ref)
        silos = h.per_silo()
        assert silos[1]["accepted"] == 3 and silos[1]["rounds_since_accept"] == 0
        assert silos[2]["rejected"] == 3 and silos[2]["accepted"] == 0
        assert silos[2]["rounds_since_accept"] == 3
        assert silos[3]["dropped"] == 3 and silos[3]["tasked"] == 3
        assert silos[4]["excluded"] == 3 and silos[4]["tasked"] == 0
        # starvation: 3 of 4 known silos (2 rejected, 3 dropped,
        # 4 excluded) have gone starve_after=3 rounds without an accept
        line = h.last_line
        assert line["alarms"]["participation_starvation"]["value"] \
            == pytest.approx(0.75)

    def test_alarm_threshold_edges(self):
        # at the threshold = ok; strictly above = breach (and only
        # breaches tick the counter)
        from fedml_tpu.obs.telemetry import TelemetryRegistry
        reg = TelemetryRegistry()
        h = HealthAccumulator(thresholds={"health_starvation_ratio": 0.5},
                              starve_after=1, registry=reg)
        ref = {"a": np.zeros(2, np.float32)}
        up = {"a": np.ones(2, np.float32)}
        h.round_start(0, ref, expected=[1, 2])
        _obs(h, 1, up, 1.0)
        _obs(h, 2, up, 1.0)
        h.round_end(0, new_global=ref)       # starvation 0/2 -> ok
        h.round_start(1, ref, expected=[1, 2])
        _obs(h, 1, up, 1.0)
        line = h.round_end(1, new_global=ref)  # 1/2 == threshold -> ok
        assert line["alarms"]["participation_starvation"]["value"] == 0.5
        assert line["alarms"]["participation_starvation"]["ok"]
        h.round_start(2, ref, expected=[1, 2])
        line = h.round_end(2, new_global=ref)  # 2/2 > threshold -> breach
        assert not line["alarms"]["participation_starvation"]["ok"]
        snap = reg.snapshot()
        breaches = {k: v for k, v in snap["counters"].items()
                    if k.startswith("fedml_health_breaches_total")}
        assert breaches[
            'fedml_health_breaches_total{alarm="participation_starvation"}'
        ] == 1

    def test_unknown_threshold_name_fails_loudly(self):
        with pytest.raises(ValueError, match="unknown health"):
            HealthAccumulator(thresholds={"typo_ratio": 1.0})
        assert set(HEALTH_SLOS) == {
            "health_misalignment_ratio", "health_norm_cv_ratio",
            "health_starvation_ratio"}

    def test_nonfinite_values_ledger_as_null_not_nan(self, tmp_path):
        path = tmp_path / "health.jsonl"
        h = HealthAccumulator(ledger_path=str(path), alarms=False)
        h.round_start(0, {"a": np.zeros(2, np.float32)}, expected=[1])
        _obs(h, 1, {"a": np.ones(2, np.float32)}, 1.0, norm=float("inf"))
        h.round_end(0)
        line = json.loads(path.read_text())
        assert line["norm"]["count"] == 0  # the inf norm never banked
        json.dumps(line, allow_nan=False)  # strictly valid JSON

    def test_ledger_rotates_prev_run_aside(self, tmp_path):
        path = tmp_path / "health.jsonl"
        path.write_text('{"round": 99}\n')
        h = HealthAccumulator(ledger_path=str(path))
        h.round_start(0, {"a": np.zeros(2, np.float32)})
        h.round_end(0)
        assert (tmp_path / "health.jsonl.prev").read_text() \
            == '{"round": 99}\n'
        assert json.loads(path.read_text())["round"] == 0

    def test_no_jitted_stat_exists_to_recompile(self):
        """The health path is host-side numpy by design: it exposes no
        _cache_size probe, so the recompile sentry has nothing to watch
        — and a full round protocol triggers zero jax compilation."""
        h = HealthAccumulator(alarms=False)
        assert not hasattr(h, "_cache_size")
        ref = {"a": np.zeros(64, np.float32)}
        with jax.checking_leaks():
            for r in range(3):
                h.round_start(r, ref, expected=[1])
                _obs(h, 1, {"a": np.ones(64, np.float32)}, 1.0)
                h.round_end(r, new_global=ref)
        from fedml_tpu.obs.perf import RecompileSentry
        assert RecompileSentry().register("health", h) is False


# ---------------------------------------------------------------------------
# torn tail + schema gate
# ---------------------------------------------------------------------------

class TestLedgerContracts:
    def _lines(self, tmp_path, rounds=3):
        path = tmp_path / "health.jsonl"
        h = HealthAccumulator(ledger_path=str(path), alarms=False)
        ref = {"a": np.zeros(4, np.float32)}
        for r in range(rounds):
            h.round_start(r, ref, expected=[1, 2])
            _obs(h, 1, {"a": np.ones(4, np.float32)}, 1.0)
            _obs(h, 2, {"a": np.full(4, 1.5, np.float32)}, 2.0)
            h.round_end(r, new_global=ref)
        return path

    def test_torn_tail_is_tolerated_by_every_reader(self, tmp_path):
        from fedml_tpu.obs.report import load_jsonl
        from fedml_tpu.obs.trend import load_ledger, validate_health_ledger
        path = self._lines(tmp_path)
        with open(path, "a") as f:
            f.write('{"round": 3, "uploads": 2, "torn...')
        assert len(load_jsonl(str(path))) == 3
        rows = load_ledger(str(path))
        assert len(rows) == 3
        assert validate_health_ledger(rows) == []

    def test_malformed_mid_ledger_fails_loudly(self, tmp_path):
        from fedml_tpu.obs.trend import load_ledger
        path = self._lines(tmp_path)
        lines = path.read_text().splitlines()
        lines.insert(1, "{broken")
        path.write_text("\n".join(lines) + "\n")
        with pytest.raises(ValueError, match="malformed"):
            load_ledger(str(path))

    def test_schema_gate_names_missing_fields(self, tmp_path):
        from fedml_tpu.obs.trend import load_ledger, validate_health_ledger
        path = self._lines(tmp_path)
        rows = load_ledger(str(path))
        del rows[1]["norm"]
        rows[2]["alarms"] = {"x": "not-a-verdict"}
        problems = validate_health_ledger(rows)
        assert any("missing 'norm'" in p for p in problems)
        assert any("without ok/threshold" in p for p in problems)
        assert validate_health_ledger([]) == ["health ledger is empty"]

    def test_trend_cli_gates_health_ledger(self, tmp_path, capsys):
        from fedml_tpu.obs import trend
        path = self._lines(tmp_path)
        assert trend.main(["--health_ledger", str(path)]) == 0
        rows = [json.loads(l) for l in path.read_text().splitlines()]
        del rows[0]["alarms"]
        bad = tmp_path / "bad.jsonl"
        bad.write_text("".join(json.dumps(r) + "\n" for r in rows))
        assert trend.main(["--health_ledger", str(bad)]) == 1
        assert trend.main(["--health_ledger",
                           str(tmp_path / "nope.jsonl")]) == 2


# ---------------------------------------------------------------------------
# live federation: stream == stack health lines, quarantine fairness
# ---------------------------------------------------------------------------

def _drift_train_fn(scale=0.01):
    def fn(params, client_idx, round_idx):
        return (jax.tree.map(
            lambda v: np.asarray(v)
            + np.float32(scale * (client_idx + 1)), params),
            10 * (client_idx + 1))
    return fn


def _run_sync(mode, tmp_path, name, n_silos=4, n_rounds=3, admission=None,
              attack=None, attacker=2, deaf=(), norm_clip=5.0,
              observed=True):
    hub = LocalHub(codec_roundtrip=True)
    init = _params()
    health = HealthAccumulator(
        ledger_path=str(tmp_path / f"{name}.jsonl")) if observed else None
    kw = {}
    if mode == "stream":
        kw["stream_agg"] = StreamingAggregator(init, method="mean",
                                               norm_clip=norm_clip)
    else:
        kw["aggregate_fn"] = make_defended_aggregate("mean",
                                                     norm_clip=norm_clip)
    server = FedAvgServerActor(
        hub.transport(0), init, client_num_in_total=n_silos,
        client_num_per_round=n_silos, num_rounds=n_rounds,
        admission=admission, health=health,
        straggler_policy="drop" if deaf else "wait",
        round_timeout_s=3600 if deaf else None, min_silo_frac=0.5, **kw)
    server.register_handlers()
    silos = []
    for i in range(1, n_silos + 1):
        fn = _drift_train_fn()
        if attack is not None and i == attacker:
            fn = make_malicious_train_fn(attack, fn, silo=i, seed=0)
        if i in deaf:
            class Deaf(FedAvgClientActor):
                def register_handlers(self):
                    self.register_handler(MsgType.S2C_FINISH,
                                          lambda m: self.finish())
            silos.append(Deaf(i, hub.transport(i), fn))
        else:
            silos.append(FedAvgClientActor(i, hub.transport(i), fn))
    for s in silos:
        s.register_handlers()
    server.start()
    hub.pump()
    while deaf and server.round_idx < n_rounds:
        server.send(MsgType.ROUND_TIMEOUT, 0,
                    **{Message.ARG_ROUND: server.round_idx})
        hub.pump()
    return server, health


def _lines(tmp_path, name):
    rows = [json.loads(l)
            for l in (tmp_path / f"{name}.jsonl").read_text().splitlines()]
    for r in rows:
        r.pop("ts")  # the only field allowed to differ between modes
    return rows


class TestLiveHealthEquivalence:
    def test_stream_and_stack_emit_identical_lines(self, tmp_path):
        _run_sync("stack", tmp_path, "stack")
        _run_sync("stream", tmp_path, "stream")
        stack, stream = _lines(tmp_path, "stack"), _lines(tmp_path, "stream")
        assert len(stack) == len(stream) == 3
        assert stack == stream

    @pytest.mark.parametrize("mode", ["stack", "stream"])
    def test_health_observes_and_never_perturbs(self, mode, tmp_path):
        """The same global, bit for bit, with the observatory on and off."""
        on, _ = _run_sync(mode, tmp_path, "on")
        off, _ = _run_sync(mode, tmp_path, "off", observed=False)
        assert on.round_idx == off.round_idx == 3
        for a, b in zip(jax.tree.leaves(on.params),
                        jax.tree.leaves(off.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_identical_lines_with_dropped_straggler(self, tmp_path):
        _run_sync("stack", tmp_path, "stack", deaf=(4,))
        _run_sync("stream", tmp_path, "stream", deaf=(4,))
        stack, stream = _lines(tmp_path, "stack"), _lines(tmp_path, "stream")
        assert stack == stream
        assert stack[-1]["dropped"] == 1
        assert stack[-1]["silos"]["4"]["dropped"] == 3

    def test_quarantined_attacker_fairness_accounting(self, tmp_path):
        admission = AdmissionPipeline(
            _params(), norm_min_history=3,
            trust=TrustTracker(strikes_to_quarantine=2,
                               quarantine_rounds=10))
        server, health = _run_sync(
            "stream", tmp_path, "quar", n_rounds=6, admission=admission,
            attack=Attack("scale", 100.0))
        rows = _lines(tmp_path, "quar")
        silos = health.per_silo()
        # the attacker struck out, then was excluded from later quorums
        # (at most its round-0 upload landed, while the norm screen was
        # still warming up — screens arm on history, not on faith)
        assert silos[2]["rejected"] >= 2
        assert silos[2]["excluded"] >= 1
        assert silos[2]["accepted"] <= 1
        # once quarantined it is EXCLUDED (ticked at broadcast), and the
        # round line accounts it there, not as a drop
        assert rows[-1]["excluded"] == 1
        assert rows[-1]["accepted"] == 3
        # honest silos never starve
        for s in (1, 3, 4):
            assert silos[s]["rounds_since_accept"] == 0
        # ... and the starvation alarm names the frozen-out minority
        assert rows[-1]["alarms"]["participation_starvation"]["value"] \
            == pytest.approx(0.25)
        # the attacker's norm never polluted the banked moments: round 0
        # (pre-quarantine, norm screen warming) sees its 100x upload
        # REJECTED only after history arms; by the last round only
        # honest norms remain
        assert rows[-1]["norm"]["count"] == 3

    def test_async_rotation_never_reads_as_starvation(self, tmp_path):
        """The starvation clock ticks per VERSION on the async path, but
        a healthy rotation only accepts ~goal of n_silos silos per
        version — the server scales starve_after by the rotation period
        so a healthy deployment with n_silos/goal > starve_after never
        alarms (the review-caught false-positive)."""
        from fedml_tpu.algorithms.async_fl import (AsyncFedServerActor,
                                                   delta_encoder)
        hub = LocalHub(codec_roundtrip=True)
        init = _params()
        health = HealthAccumulator(
            kind="delta", ledger_path=str(tmp_path / "async.jsonl"))
        assert health.starve_after == 3
        server = AsyncFedServerActor(
            hub.transport(0), init, client_num_in_total=8, n_silos=8,
            num_versions=6, aggregation_goal=2, health=health)
        assert health.starve_after == 3 * 4  # ceil(8/2) rotation periods
        server.register_handlers()
        silos = [FedAvgClientActor(i, hub.transport(i), _drift_train_fn(),
                                   encode_upload=delta_encoder)
                 for i in range(1, 9)]
        for s in silos:
            s.register_handlers()
        server.start()
        hub.pump()
        rows = [json.loads(l) for l in
                (tmp_path / "async.jsonl").read_text().splitlines()]
        assert len(rows) == 6
        for r in rows:
            assert r["alarms"]["participation_starvation"]["ok"], r
            assert r["kind"] == "delta"

    def test_health_rides_the_perf_ledger_as_its_own_phase(self, tmp_path):
        from fedml_tpu.obs.perf import PerfRecorder
        hub = LocalHub(codec_roundtrip=True)
        init = _params()
        rec = PerfRecorder(str(tmp_path / "perf.jsonl"))
        health = HealthAccumulator(alarms=False)
        server = FedAvgServerActor(
            hub.transport(0), init, client_num_in_total=2,
            client_num_per_round=2, num_rounds=2, perf=rec, health=health,
            stream_agg=StreamingAggregator(init, method="mean"))
        server.register_handlers()
        silos = [FedAvgClientActor(i, hub.transport(i), _drift_train_fn())
                 for i in (1, 2)]
        for s in silos:
            s.register_handlers()
        server.start()
        hub.pump()
        rec.close()
        rows = [json.loads(l) for l in
                (tmp_path / "perf.jsonl").read_text().splitlines()]
        assert len(rows) == 2
        for r in rows:
            assert r["phases"]["health"] > 0


# ---------------------------------------------------------------------------
# the multi-level topology: per-edge rollups, one frame per round
# ---------------------------------------------------------------------------

def _edge_federation(tmp_path, n_edges=2, n_silos=4, n_rounds=3):
    hub = LocalHub(codec_roundtrip=True)
    init = _params()
    health = HealthAccumulator(
        ledger_path=str(tmp_path / "root.jsonl"))
    server = FedAvgServerActor(
        hub.transport(0), init, client_num_in_total=n_silos,
        client_num_per_round=n_edges, num_rounds=n_rounds,
        stream_agg=StreamingAggregator(init, method="mean"),
        health=health)
    server.register_handlers()
    blocks = np.array_split(np.arange(1, n_silos + 1), n_edges)
    edges = []
    for e, block in enumerate(blocks, start=1):
        edges.append(EdgeAggregatorActor(
            e, hub.transport(e),
            {n_edges + int(g): int(g) for g in block},
            cohort_total=n_silos, client_num_in_total=n_silos,
            stream_agg=StreamingAggregator(init, method="mean"),
            health=HealthAccumulator(kind="params", node=f"edge{e}",
                                     alarms=False)))
    edge_of = {int(g): e for e, block in enumerate(blocks, start=1)
               for g in block}
    silos = [FedAvgClientActor(n_edges + g, hub.transport(n_edges + g),
                               _drift_train_fn(), server_id=edge_of[g])
             for g in range(1, n_silos + 1)]
    for a in edges + silos:
        a.register_handlers()
    return hub, server, edges, silos, health


class TestEdgeHealthRollup:
    def test_rollup_matches_flat_norm_moments(self, tmp_path):
        hub, server, edges, silos, health = _edge_federation(tmp_path)
        server.start()
        hub.pump()
        root = _lines(tmp_path, "root")
        assert len(root) == 3
        _run_sync("stream", tmp_path, "flat", norm_clip=0.0)
        flat = _lines(tmp_path, "flat")
        for edge_row, flat_row in zip(root, flat):
            # the root's own tier sees 2 edge means; each frame carried
            # its block's rollup, and the merged moments equal the flat
            # topology's one-pass moments over the same 4 uploads
            assert set(edge_row["edges"]) == {"1", "2"}
            rollup = edge_row["edge_rollup"]
            assert rollup["count"] == flat_row["norm"]["count"] == 4
            assert rollup["mean"] == pytest.approx(
                flat_row["norm"]["mean"], rel=1e-6)
            assert rollup["std"] == pytest.approx(
                flat_row["norm"]["std"], rel=1e-5, abs=1e-9)
            assert rollup["min"] == pytest.approx(
                flat_row["norm"]["min"], rel=1e-6)
            assert rollup["max"] == pytest.approx(
                flat_row["norm"]["max"], rel=1e-6)
            # per-edge accounting: every silo accepted at its edge
            for s in edge_row["edges"].values():
                assert s["accepted"] == 2 and s["rejected"] == 0

    def test_tree_stays_one_frame_per_round(self, tmp_path):
        hub, server, edges, silos, health = _edge_federation(
            tmp_path, n_rounds=1)
        got = []
        orig = server._on_model

        def spy(msg):
            got.append((msg.sender_id, msg.get(Message.ARG_HEALTH)))
            orig(msg)
        server.register_handler(MsgType.C2S_MODEL, spy)
        server.start()
        hub.pump()
        # exactly E frames reached the root, each carrying its compact
        # rollup INSIDE the existing frame — no extra health messages
        assert sorted(s for s, _ in got) == [1, 2]
        for _, summary in got:
            assert summary["uploads"] == 2
            assert summary["norm"]["count"] == 2
            assert "silos" not in summary  # compact: no per-silo dump


# ---------------------------------------------------------------------------
# SLO / deep healthz / report integration
# ---------------------------------------------------------------------------

class TestIntegration:
    def test_slo_evaluator_gates_on_health_gauges(self):
        from fedml_tpu.obs.perf import DEFAULT_SLOS, SloEvaluator
        from fedml_tpu.obs.telemetry import TelemetryRegistry
        assert set(HEALTH_SLOS) <= set(DEFAULT_SLOS)
        reg = TelemetryRegistry()
        ev = SloEvaluator(registry=reg)
        # absent gauges: vacuously healthy (health off)
        verdict = ev.evaluate(count_breaches=False)
        assert verdict["health_norm_cv_ratio"]["value"] is None
        assert verdict["health_norm_cv_ratio"]["ok"]
        # a health round that blows the variance budget breaches (three
        # norms: a 2-value cv is bounded by 1.0 and could never breach)
        h = HealthAccumulator(registry=reg)
        ref = {"a": np.zeros(4, np.float32)}
        h.round_start(0, ref, expected=[1, 2, 3])
        _obs(h, 1, {"a": np.ones(4, np.float32)}, 1.0, norm=1.0)
        _obs(h, 2, {"a": np.ones(4, np.float32)}, 1.0, norm=1.0)
        _obs(h, 3, {"a": np.ones(4, np.float32)}, 1.0, norm=500.0)
        h.round_end(0, new_global=ref)
        verdict = ev.evaluate()
        assert not verdict["health_norm_cv_ratio"]["ok"]
        snap = reg.snapshot()
        assert snap["gauges"]["fedml_slo_health_norm_cv_ratio"] > 1.0
        assert any(k.startswith("fedml_slo_breaches_total")
                   and "health_norm_cv_ratio" in k and v >= 1
                   for k, v in snap["counters"].items())

    def test_parse_slo_spec_accepts_health_thresholds(self):
        from fedml_tpu.obs.perf import parse_slo_spec
        spec = parse_slo_spec("health_norm_cv_ratio=0.8,"
                              "health_misalignment_ratio=1.9")
        assert spec == {"health_norm_cv_ratio": 0.8,
                        "health_misalignment_ratio": 1.9}

    def test_deep_healthz_carries_the_health_verdict(self):
        import http.client
        from fedml_tpu.obs.perf import SloEvaluator
        from fedml_tpu.obs.telemetry import TelemetryRegistry
        from fedml_tpu.serve import (MicroBatcher, ModelRegistry,
                                     ServeFrontend)
        reg = TelemetryRegistry()
        slo = SloEvaluator(registry=reg)
        h = HealthAccumulator(registry=reg)
        registry = ModelRegistry(lambda p, x: x, history=8)
        batcher = MicroBatcher(registry, buckets=(1,))
        frontend = ServeFrontend(registry, batcher, port=0, slo=slo,
                                 health=h).start()
        try:
            registry.publish({"w": np.ones(2, np.float32)}, 0)
            ref = {"a": np.zeros(4, np.float32)}
            h.round_start(0, ref, expected=[1, 2, 3])
            _obs(h, 1, {"a": np.ones(4, np.float32)}, 1.0, norm=1.0)
            _obs(h, 2, {"a": np.ones(4, np.float32)}, 1.0, norm=1.0)
            _obs(h, 3, {"a": np.ones(4, np.float32)}, 1.0, norm=500.0)
            h.round_end(0, new_global=ref)
            conn = http.client.HTTPConnection("127.0.0.1", frontend.port,
                                              timeout=10)
            conn.request("GET", "/healthz?deep=1")
            resp = conn.getresponse()
            body = json.loads(resp.read())
            conn.close()
            assert resp.status == 503
            assert body["status"] == "slo_breach"
            assert not body["slo"]["health_norm_cv_ratio"]["ok"]
            assert not body["health"]["alarms"]["norm_variance_blowup"]["ok"]
            assert body["health"]["round"] == 0
        finally:
            frontend.stop(drain=False)

    def test_report_renders_health_section(self, tmp_path):
        from fedml_tpu.obs.report import render_report
        h = HealthAccumulator(
            ledger_path=str(tmp_path / "health.jsonl"),
            thresholds={"health_norm_cv_ratio": 0.1})
        ref = {"a": np.zeros(4, np.float32)}
        h.round_start(0, ref, expected=[1, 2])
        _obs(h, 1, {"a": np.ones(4, np.float32)}, 1.0, norm=1.0)
        _obs(h, 2, {"a": np.ones(4, np.float32)}, 1.0, norm=9.0)
        h.round_end(0, new_global=ref)
        out = render_report(str(tmp_path))
        assert "learning health" in out
        assert "norm_variance_blowup" in out
        assert "DRIFT ALARMS fired 1 time(s)" in out

    def test_perf_only_run_dir_renders_cleanly(self, tmp_path):
        """ISSUE 9 bugfix pin: a run dir holding perf.jsonl (or
        health.jsonl) but no metrics.jsonl must render its ledger
        sections AND say why the rounds table is absent — never an
        empty/misleading report."""
        from fedml_tpu.obs.report import render_report
        (tmp_path / "perf.jsonl").write_text(json.dumps(
            {"round": 0, "ts": 1, "node": "node0", "round_s": 0.5,
             "phases": {"aggregate": 0.1}, "wire": {"bytes_out": 1,
                                                    "bytes_in": 1},
             "rss": None, "recompiles": 0, "jit_cache_sizes": {}}) + "\n")
        out = render_report(str(tmp_path))
        assert "perf ledger" in out
        assert "perf/health-only run" in out
        assert "no artifacts found" not in out
        # health-only: same contract
        (tmp_path / "perf.jsonl").unlink()
        h = HealthAccumulator(
            ledger_path=str(tmp_path / "health.jsonl"), alarms=False)
        h.round_start(0, {"a": np.zeros(2, np.float32)}, expected=[1])
        _obs(h, 1, {"a": np.ones(2, np.float32)}, 1.0)
        h.round_end(0)
        out = render_report(str(tmp_path))
        assert "learning health" in out
        assert "no artifacts found" not in out
