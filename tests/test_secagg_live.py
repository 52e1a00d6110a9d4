"""Live secure aggregation (secure/protocol.py, ISSUE 11).

Covers the tentpole contracts over the REAL transport: mask cancellation
bit-exactness in uint32, t-of-N share reconstruction for tolerated
dropouts + loud failure beyond, quantize/dequantize round-trip bounds,
the admission pre/post-mask ordering pin, edge-grouped vs flat parity,
stream-fold-of-masked-uploads == stack parity, and the privacy probe —
no individual plaintext update ever appears in any wire frame.
"""

import functools

import numpy as np
import pytest

from fedml_tpu.algorithms.cross_silo import (FedAvgClientActor,
                                             FedAvgServerActor, MsgType)
from fedml_tpu.comm.local import LocalHub
from fedml_tpu.comm.message import Message
from fedml_tpu.robust.admission import AdmissionPipeline, params_fingerprint
from fedml_tpu.secure.protocol import (MSG_SECAGG_UNMASK, SecAggClient,
                                       SecAggError, SecAggServer,
                                       dequantize_np, masked_template,
                                       quantize_np)

CLIP = 64.0


# ---------------------------------------------------------------------------
# protocol-level helpers
# ---------------------------------------------------------------------------

def _run_agreement(server, clients, round_idx, ids):
    """Drive advert -> roster in memory (no transport)."""
    server.round_start(round_idx, ids)
    info = server.sync_info()
    adverts = {i: clients[i].begin_round(round_idx, info) for i in ids}
    for i in ids:
        server.note_advert(i, adverts[i])
    rosters = server.flush_roster()
    for i in ids:
        assert clients[i].on_roster(round_idx, rosters[i])


def _mk(ids, threshold=0, weight_cap=10.0, seed=0, **kw):
    server = SecAggServer(threshold=threshold, clip=CLIP,
                          weight_cap=weight_cap, **kw)
    clients = {i: SecAggClient(i, rng=np.random.RandomState(seed + i))
               for i in ids}
    return server, clients


def _updates(ids, shape=(7,), seed=3):
    rng = np.random.RandomState(seed)
    return {i: {"w": rng.randn(*shape).astype(np.float32),
                "b": rng.randn(3).astype(np.float32)} for i in ids}


class TestProtocolCore:
    def test_mask_cancellation_bit_exact_uint32(self):
        """All-zero updates: every pairwise mask and every reconstructed
        self-mask must cancel WORD FOR WORD — the unmasked mean is
        exactly 0.0, not merely small."""
        ids = [1, 2, 3, 4, 5]
        server, clients = _mk(ids)
        _run_agreement(server, clients, 0, ids)
        zero = {"w": np.zeros(11, np.float32)}
        for i in ids:
            server.fold(i, clients[i].mask(0, zero, 5.0), 5.0)
        survivors, dead = server.unmask_request()
        assert dead == []
        for i in survivors:
            server.note_reveal(i, clients[i].reveal(0, survivors, dead))
        mean, den = server.finalize()
        assert den > 0
        # quantize(0) == 0 and masks cancel exactly, so any nonzero word
        # would surface here verbatim
        assert np.all(np.asarray(mean["w"]) == 0.0)

    def test_weighted_mean_within_quantization_tolerance(self):
        ids = [1, 2, 3]
        server, clients = _mk(ids)
        _run_agreement(server, clients, 0, ids)
        ups = _updates(ids)
        ns = {1: 4.0, 2: 8.0, 3: 2.0}
        for i in ids:
            server.fold(i, clients[i].mask(0, ups[i], ns[i]), ns[i])
        survivors, dead = server.unmask_request()
        for i in survivors:
            server.note_reveal(i, clients[i].reveal(0, survivors, dead))
        mean, _ = server.finalize()
        tot = sum(ns.values())
        for k in ("w", "b"):
            want = sum(np.asarray(ups[i][k], np.float64) * ns[i]
                       for i in ids) / tot
            np.testing.assert_allclose(np.asarray(mean[k]), want, atol=1e-3)

    def test_dropout_recovery_within_tolerance(self):
        """<= N - t dropouts: dead silos' stray pairwise masks are
        reconstructed away and the mean equals the survivors' mean."""
        ids = [1, 2, 3, 4, 5]
        server, clients = _mk(ids, threshold=3)
        _run_agreement(server, clients, 0, ids)
        ups = _updates(ids)
        alive = [1, 3, 5]  # 2 dropouts, tolerance is 5 - 3 = 2
        for i in alive:
            server.fold(i, clients[i].mask(0, ups[i], 5.0), 5.0)
        survivors, dead = server.unmask_request()
        assert dead == [2, 4]
        for i in survivors:
            server.note_reveal(i, clients[i].reveal(0, survivors, dead))
        mean, _ = server.finalize()
        for k in ("w", "b"):
            want = sum(np.asarray(ups[i][k], np.float64)
                       for i in alive) / len(alive)
            np.testing.assert_allclose(np.asarray(mean[k]), want, atol=1e-3)

    def test_beyond_tolerance_fails_loudly(self):
        """> N - t dropouts leave < t revealers: SecAggError, never a
        silently-wrong aggregate."""
        ids = [1, 2, 3, 4]
        server, clients = _mk(ids, threshold=3)
        _run_agreement(server, clients, 0, ids)
        ups = _updates(ids)
        for i in (1, 2):  # 2 survivors < t=3
            server.fold(i, clients[i].mask(0, ups[i], 5.0), 5.0)
        survivors, dead = server.unmask_request()
        for i in survivors:
            server.note_reveal(i, clients[i].reveal(0, survivors, dead))
        assert not server.can_finalize()
        with pytest.raises(SecAggError, match="threshold"):
            server.finalize()

    def test_reveal_refuses_survivor_and_dead_overlap(self):
        """The client-side security invariant: sk and b shares for the
        same silo never leave together (that pair unmasks a live
        upload)."""
        ids = [1, 2, 3]
        server, clients = _mk(ids)
        _run_agreement(server, clients, 0, ids)
        with pytest.raises(SecAggError, match="BOTH"):
            clients[1].reveal(0, survivors=[1, 2], dead=[2, 3])

    def test_reveal_refuses_flip_across_requests(self):
        """Review pin: the never-both invariant is stateful per round —
        two sequential, individually well-formed unmask requests that
        flip a peer between the survivor and dead sets must be refused
        (a compromised server could otherwise collect b AND sk and
        expose a live upload), while a legitimate RE-request of the same
        snapshot still answers."""
        ids = [1, 2, 3]
        server, clients = _mk(ids)
        _run_agreement(server, clients, 0, ids)
        first = clients[1].reveal(0, survivors=[1, 2], dead=[3])
        # the same snapshot re-requested (a lost SHARES frame): fine
        again = clients[1].reveal(0, survivors=[1, 2], dead=[3])
        assert again == first
        with pytest.raises(SecAggError, match="flips"):
            clients[1].reveal(0, survivors=[1, 3], dead=[2])

    def test_roster_below_threshold_refused(self):
        ids = [1, 2, 3, 4]
        server, clients = _mk(ids, threshold=3)
        server.round_start(0, ids)
        info = server.sync_info()
        for i in (1, 2):  # only 2 adverts < t=3
            server.note_advert(i, clients[i].begin_round(0, info))
        with pytest.raises(SecAggError, match="threshold"):
            server.flush_roster()

    def test_duplicate_sync_does_not_rekey(self):
        """A chaos-duplicated sync must return the SAME advert — fresh
        keys behind a banked advert would desynchronize the masks."""
        ids = [1, 2]
        server, clients = _mk(ids)
        server.round_start(0, ids)
        info = server.sync_info()
        a1 = clients[1].begin_round(0, info)
        a2 = clients[1].begin_round(0, info)
        assert a1 is a2

    def test_stream_fold_of_masked_uploads_equals_stack(self):
        """Ring addition at arrival == stacking every masked upload and
        summing in uint32, bit for bit (the PR 7 fold-vs-stack parity
        pin, in the ring)."""
        ids = [1, 2, 3, 4]
        server, clients = _mk(ids)
        _run_agreement(server, clients, 0, ids)
        ups = _updates(ids)
        payloads = [clients[i].mask(0, ups[i], 5.0) for i in ids]
        for i, p in zip(ids, payloads):
            server.fold(i, p, 5.0)
        acc = server._round.acc
        for key in ("w", "b"):
            stacked = np.stack([np.asarray(p["q"][key], np.uint32)
                                for p in payloads])
            want = functools.reduce(np.add, stacked)  # uint32 ring sum
            np.testing.assert_array_equal(np.asarray(acc["q"][key]), want)
        w_want = functools.reduce(
            np.add, [np.asarray(p["w"], np.uint32) for p in payloads])
        np.testing.assert_array_equal(np.asarray(acc["w"]), w_want)


class TestQuantization:
    def test_sub_one_clip_keeps_weight_channel_in_budget(self):
        """Review pin: the payload's weight scalar is bounded by 1.0, so
        a clip < 1 must not buy a scale large enough for N full weights
        to wrap the ring — the shared scale budgets max(clip, 1)."""
        from fedml_tpu.secure.protocol import payload_scale
        n = 8
        s = payload_scale(n, 0.5)
        assert n * 1.0 * s < 2.0**31  # the weight channel's budget
        # and a full round at that clip recovers a POSITIVE weight sum
        ids = list(range(1, n + 1))
        server = SecAggServer(threshold=0, clip=0.5, weight_cap=10.0)
        clients = {i: SecAggClient(i, rng=np.random.RandomState(i))
                   for i in ids}
        _run_agreement(server, clients, 0, ids)
        upd = {"w": np.full(4, 0.25, np.float32)}
        for i in ids:
            server.fold(i, clients[i].mask(0, upd, 10.0), 10.0)
        survivors, dead = server.unmask_request()
        for i in survivors:
            server.note_reveal(i, clients[i].reveal(0, survivors, dead))
        mean, den = server.finalize()
        assert den > 0
        np.testing.assert_allclose(np.asarray(mean["w"]), 0.25, atol=1e-3)

    def test_round_trip_error_bound(self):
        rng = np.random.RandomState(0)
        x = rng.uniform(-CLIP, CLIP, 500)
        scale = 2.0**20
        back = dequantize_np(quantize_np(x, scale, CLIP), scale)
        assert np.max(np.abs(back - x)) <= 0.5 / scale + 1e-12

    def test_clips_beyond_range(self):
        scale = 2.0**16
        back = dequantize_np(
            quantize_np(np.asarray([CLIP * 3, -CLIP * 3]), scale, CLIP),
            scale)
        np.testing.assert_allclose(back, [CLIP, -CLIP])

    def test_negatives_ride_twos_complement(self):
        q = quantize_np(np.asarray([-1.0]), 2.0**10, CLIP)
        assert q.dtype == np.uint32 and q[0] > 2**31  # wrapped negative
        assert dequantize_np(q, 2.0**10)[0] == -1.0


class TestMaskedAdmission:
    def test_fingerprint_screens_pre_mask_removal(self):
        """The ordering pin: the pipeline's template IS the masked
        structure, so screening happens on ciphertext BEFORE any unmask
        — a plaintext upload (or any wrong structure) rejects without
        the protocol ever seeing it."""
        params = {"w": np.zeros(5, np.float32)}
        pipe = AdmissionPipeline(masked_template(params), kind="masked")
        ids = [1, 2]
        server, clients = _mk(ids)
        _run_agreement(server, clients, 0, ids)
        masked = clients[1].mask(0, {"w": np.ones(5, np.float32)}, 5.0)
        v = pipe.admit(1, masked, 5.0, None, 0)
        assert v.ok and v.norm is None  # no norm on ciphertext
        # a PLAINTEXT upload must fingerprint-reject against the masked
        # template: the screen runs pre-mask-removal by construction
        v2 = pipe.admit(2, params, 5.0, None, 0)
        assert not v2.ok and v2.reason == "fingerprint"
        assert pipe.rejected["fingerprint"] == 1

    def test_num_samples_screen_still_applies(self):
        params = {"w": np.zeros(3, np.float32)}
        pipe = AdmissionPipeline(masked_template(params), kind="masked",
                                 max_num_samples=10)
        ids = [1, 2]
        server, clients = _mk(ids)
        _run_agreement(server, clients, 0, ids)
        masked = clients[1].mask(0, params, 5.0)
        assert not pipe.admit(1, masked, 1e9, None, 0).ok
        assert pipe.rejected["bad_num_samples"] == 1

    def test_masked_template_fingerprint_matches_masked_payload(self):
        params = {"a": {"w": np.zeros((2, 3), np.float32)},
                  "b": np.zeros(4, np.float32)}
        ids = [1, 2]
        server, clients = _mk(ids)
        _run_agreement(server, clients, 0, ids)
        masked = clients[1].mask(0, params, 3.0)
        assert params_fingerprint(masked_template(params)) == \
            params_fingerprint(masked)


# ---------------------------------------------------------------------------
# live transport (LocalHub pump — deterministic)
# ---------------------------------------------------------------------------

def _make_train_fn(silo_id):
    def train_fn(params, client_idx, round_idx):
        new = {k: np.asarray(v, np.float32) + np.float32(0.1 * silo_id)
               for k, v in params.items()}
        return new, 4.0 + silo_id
    return train_fn


class _SpyTransport:
    """Record every outbound message of one node (pre-encode: exactly
    the payload the wire frame serializes)."""

    def __init__(self, inner, log):
        self._inner = inner
        self._log = log

    def send_message(self, msg):
        self._log.append(msg)
        self._inner.send_message(msg)

    def send_many(self, messages):
        self._log.extend(messages)
        self._inner.send_many(messages)

    def __getattr__(self, name):
        return getattr(self._inner, name)


class _SwallowUploads:
    """Drop a silo's C2S_MODEL frames on the floor: the deterministic
    'silo killed mid-round after the mask agreement' fault.  ``held``
    (when given) CAPTURES the frame instead, so a test can re-deliver it
    later — the 'straggler lands mid-unmask' fault."""

    def __init__(self, inner, held=None):
        self._inner = inner
        self._held = held

    def send_message(self, msg):
        if msg.type == MsgType.C2S_MODEL:
            if self._held is not None:
                self._held.append(msg)
            return
        self._inner.send_message(msg)

    def __getattr__(self, name):
        return getattr(self._inner, name)


def _live_federation(n=4, rounds=1, swallow=None, spy=None,
                     straggler_policy="wait", min_silo_frac=0.5,
                     weight_cap=10.0, held=None):
    init = {"w": np.zeros(6, np.float32), "v": np.zeros(2, np.float32)}
    hub = LocalHub(codec_roundtrip=True)
    secagg = SecAggServer(threshold=0, clip=CLIP, weight_cap=weight_cap)
    admission = AdmissionPipeline(masked_template(init), kind="masked")
    server_t = hub.transport(0)
    if spy is not None:
        server_t = _SpyTransport(server_t, spy)
    server = FedAvgServerActor(
        server_t, init, client_num_in_total=n, client_num_per_round=n,
        num_rounds=rounds, straggler_policy=straggler_policy,
        # a wall-clock timer the test never waits for: the timeout is
        # driven DETERMINISTICALLY by enqueuing ROUND_TIMEOUT by hand
        round_timeout_s=(120.0 if straggler_policy == "drop" else None),
        min_silo_frac=min_silo_frac, admission=admission, secagg=secagg)
    server.register_handlers()
    silos = []
    for i in range(1, n + 1):
        t = hub.transport(i)
        if swallow is not None and i in swallow:
            t = _SwallowUploads(t, held=held)
        if spy is not None:
            t = _SpyTransport(t, spy)
        c = FedAvgClientActor(i, t, _make_train_fn(i),
                              secagg=SecAggClient(i))
        c.register_handlers()
        silos.append(c)
    return hub, server, silos, init


def _expected_mean(init, ids):
    w = {i: 4.0 + i for i in ids}
    tot = sum(w.values())
    return {k: sum((np.asarray(v, np.float64) + 0.1 * i) * w[i]
                   for i in ids) / tot for k, v in init.items()}


class TestLiveRounds:
    def test_clean_round_matches_plaintext_mean(self):
        hub, server, silos, init = _live_federation(n=4)
        server.start()
        hub.pump()
        want = _expected_mean(init, [1, 2, 3, 4])
        for k in init:
            np.testing.assert_allclose(np.asarray(server.params[k]),
                                       want[k], atol=1e-3)

    def test_dropout_mid_round_recovers_via_shares(self):
        """Silo 3 completes the mask agreement then its upload is lost:
        the drop policy closes the barrier, the unmask phase
        reconstructs its pairwise secret from surviving shares, and the
        published global is the survivors' exact weighted mean."""
        from fedml_tpu.obs import telemetry
        reg = telemetry.enable()
        try:
            hub, server, silos, init = _live_federation(
                n=4, swallow={3}, straggler_policy="drop")
            server.start()
            hub.pump()  # barrier stuck waiting on silo 3
            assert server._secagg_stage == "upload"
            # deterministic straggler timeout (no wall clock in pump mode)
            server.send(MsgType.ROUND_TIMEOUT, 0,
                        **{Message.ARG_ROUND: server.round_idx})
            hub.pump()  # drop -> unmask -> reveals -> finalize -> FINISH
            want = _expected_mean(init, [1, 2, 4])
            for k in init:
                np.testing.assert_allclose(np.asarray(server.params[k]),
                                           want[k], atol=1e-3)
            snap = reg.snapshot()["counters"]
            recon = {k: v for k, v in snap.items()
                     if k.startswith("fedml_secagg_unmask_reconstructions")}
            assert any("pair_key" in k and v >= 1 for k, v in recon.items()), \
                recon  # the dead silo's pairwise secret WAS reconstructed
        finally:
            telemetry.disable()

    def test_straggler_landing_mid_unmask_is_discarded(self):
        """Review pin: a masked upload arriving AFTER the barrier closed
        (stage == unmask) must not mutate the fold — the unmask request
        already snapshotted survivors/dead, and folding the straggler
        would demand self-mask shares nobody was asked for, abandoning a
        round that had quorum."""
        held = []
        hub, server, silos, init = _live_federation(
            n=4, swallow={3}, straggler_policy="drop", held=held)
        server.start()
        hub.pump()  # barrier stuck on silo 3; its upload is HELD
        assert len(held) == 1 and server._secagg_stage == "upload"
        # close the barrier synchronously (handler call, not pump): the
        # unmask request is now queued and the stage is 'unmask'
        tmo = Message(MsgType.ROUND_TIMEOUT, 0, 0)
        tmo.add(Message.ARG_ROUND, server.round_idx)
        server.receive_message(MsgType.ROUND_TIMEOUT, tmo)
        assert server._secagg_stage == "unmask"
        # the straggler lands mid-unmask
        server.receive_message(MsgType.C2S_MODEL, held[0])
        assert 3 not in server.secagg.folded_silos()
        hub.pump()  # reveals arrive; the round completes over [1, 2, 4]
        want = _expected_mean(init, [1, 2, 4])
        for k in init:
            np.testing.assert_allclose(np.asarray(server.params[k]),
                                       want[k], atol=1e-3)

    def test_privacy_probe_no_plaintext_update_on_any_frame(self):
        """The acceptance probe: decode every frame either direction —
        no silo's true plaintext update (nor anything within tolerance
        of it) ever crosses the wire; uploads are uint32 ring words."""
        spy = []
        hub, server, silos, init = _live_federation(n=4, spy=spy)
        server.start()
        hub.pump()
        true_updates = {
            i: {k: np.asarray(v, np.float64) + 0.1 * i
                for k, v in init.items()} for i in range(1, 5)}
        uploads = [m for m in spy if m.type == MsgType.C2S_MODEL]
        assert len(uploads) == 4
        for m in uploads:
            payload = m.get(Message.ARG_MODEL_PARAMS)
            assert set(payload) == {"q", "w"}
            leaves = [np.asarray(l) for l in
                      [payload["q"]["v"], payload["q"]["w"], payload["w"]]]
            assert all(l.dtype == np.uint32 for l in leaves)
            # dequantizing the masked words yields PRG noise, nowhere
            # near the silo's true update
            true = true_updates[m.sender_id]
            for k in ("w", "v"):
                deq = dequantize_np(np.asarray(payload["q"][k]), 2.0**20)
                assert not np.allclose(deq, true[k], atol=0.5)
        # sweep EVERY frame (sync broadcasts included): no float payload
        # equals any individual update
        for m in spy:
            payload = m.get(Message.ARG_MODEL_PARAMS)
            if not isinstance(payload, dict):
                continue
            for i, true in true_updates.items():
                for k in ("w", "v"):
                    leaf = payload.get(k) if "q" not in payload \
                        else payload["q"].get(k)
                    if leaf is None:
                        continue
                    arr = np.asarray(leaf)
                    if arr.dtype == np.uint32:
                        continue  # ciphertext
                    assert not np.allclose(arr.astype(np.float64), true[k],
                                           atol=1e-6), \
                        f"plaintext update of silo {i} leaked in {m}"
        # and every unmask request kept the survivor/dead sets disjoint
        for m in spy:
            if m.type == MSG_SECAGG_UNMASK:
                info = m.get(Message.ARG_SECAGG)
                assert not (set(info["survivors"]) & set(info["dead"]))

    def test_sync_without_masking_params_never_uploads_plaintext(self):
        """The rejoin-warmup guard: a secagg client receiving a sync
        frame WITHOUT masking parameters must not fall back to a
        plaintext upload."""
        spy = []
        hub = LocalHub(codec_roundtrip=True)
        server_inbox = hub.transport(0)  # absorbs anything sent

        class _Sink:
            def receive_message(self, t, m):
                pass
        server_inbox.add_observer(_Sink())
        t = _SpyTransport(hub.transport(1), spy)
        c = FedAvgClientActor(1, t, _make_train_fn(1),
                              secagg=SecAggClient(1))
        c.register_handlers()
        msg = Message(MsgType.S2C_SYNC, 0, 1)
        msg.add(Message.ARG_MODEL_PARAMS, {"w": np.zeros(6, np.float32),
                                           "v": np.zeros(2, np.float32)})
        msg.add(Message.ARG_CLIENT_INDEX, 0)
        msg.add(Message.ARG_ROUND, 3)
        c.receive_message(MsgType.S2C_SYNC, msg)
        assert not any(m.type == MsgType.C2S_MODEL for m in spy)


# ---------------------------------------------------------------------------
# CLI-level parity (flat vs grouped vs plaintext)
# ---------------------------------------------------------------------------

def _cli(*extra):
    from fedml_tpu.experiments.main import main
    base = ["--algo", "cross_silo", "--model", "lr", "--dataset", "mnist",
            "--client_num_in_total", "4", "--client_num_per_round", "4",
            "--comm_round", "2", "--frequency_of_the_test", "2",
            "--batch_size", "4", "--log_stdout", "false"]
    return main(base + list(extra))


class _CorruptUpload:
    """Replace one silo's masked upload with a wrong-structure payload
    (the edge's masked fingerprint must reject it)."""

    def __init__(self, inner):
        self._inner = inner

    def send_message(self, msg):
        if msg.type == MsgType.C2S_MODEL:
            msg.params[Message.ARG_MODEL_PARAMS] = {
                "bogus": np.zeros(3, np.uint32)}
        self._inner.send_message(msg)

    def __getattr__(self, name):
        return getattr(self._inner, name)


class TestGroupedEdge:
    def test_rejected_masked_upload_does_not_wedge_the_block(self):
        """Review pin: the masked edge barrier closes over REPORTS (like
        the flat root), so a reported-but-rejected upload must not stall
        the block — under the wait policy (no timers at all) the round
        still completes over the admissible uploads."""
        from fedml_tpu.algorithms.hierarchical import EdgeAggregatorActor
        init = {"w": np.zeros(6, np.float32)}
        hub = LocalHub(codec_roundtrip=True)
        root = FedAvgServerActor(
            hub.transport(0), init, client_num_in_total=3,
            client_num_per_round=1, num_rounds=1)
        root.register_handlers()
        edge = EdgeAggregatorActor(
            1, hub.transport(1), {2: 1, 3: 2, 4: 3}, cohort_total=3,
            client_num_in_total=3, stream_agg=None,
            admission=AdmissionPipeline(masked_template(init),
                                        kind="masked"),
            secagg=SecAggServer(threshold=2, clip=CLIP, weight_cap=10.0))
        edge.register_handlers()
        silos = []
        for g in (1, 2, 3):
            t = hub.transport(1 + g)
            if g == 3:
                t = _CorruptUpload(t)
            c = FedAvgClientActor(1 + g, t, _make_train_fn(g),
                                  server_id=1,
                                  secagg=SecAggClient(1 + g))
            c.register_handlers()
            silos.append(c)
        root.start()
        hub.pump()
        # NO timer fired anywhere: the rejected upload closed the
        # barrier by report, the block unmasked over the two admissible
        # uploads, and the root's round completed
        assert root.round_idx == 1
        want = _expected_mean(init, [1, 2])
        np.testing.assert_allclose(np.asarray(root.params["w"]),
                                   want["w"], atol=1e-3)


class TestCliParity:
    def test_pairwise_grouped_and_plaintext_agree(self):
        plain = _cli()
        pairwise = _cli("--secagg", "pairwise", "--agg_mode", "stream")
        grouped = _cli("--secagg", "grouped", "--agg_mode", "stream",
                       "--edge_aggregators", "2")
        # quantization is the ONLY divergence: the three trajectories
        # agree to well under any training-relevant tolerance
        assert abs(pairwise["test_loss"] - plain["test_loss"]) < 1e-3
        assert abs(grouped["test_loss"] - plain["test_loss"]) < 1e-3
        assert abs(pairwise["train_acc"] - plain["train_acc"]) < 1e-6

    def test_grouped_masking_relays_fewer_share_envelopes(self):
        """Agreement traffic is O(N^2) pairwise shares flat and O(N^2/E)
        grouped (frames are O(N) either way and cannot show it)."""
        from fedml_tpu.obs import telemetry

        def envelopes(*extra):
            reg = telemetry.enable()
            try:
                _cli("--secagg", *extra, "--agg_mode", "stream")
                return reg.snapshot()["counters"][
                    "fedml_secagg_share_envelopes_total"]
            finally:
                telemetry.disable()

        flat = envelopes("pairwise")
        grouped = envelopes("grouped", "--edge_aggregators", "2")
        assert 0 < grouped < flat

    def test_incompatible_combos_fail_at_config_time(self):
        with pytest.raises(ValueError, match="async"):
            _cli("--secagg", "pairwise", "--agg_mode", "stream",
                 "--algo", "async_fl")
        with pytest.raises(ValueError, match="ring"):
            _cli("--secagg", "pairwise", "--agg_mode", "stream",
                 "--wire_compression", "topk")
        with pytest.raises(ValueError, match="order-statistic"):
            _cli("--secagg", "pairwise", "--agg_mode", "stream",
                 "--robust_agg", "krum")
        with pytest.raises(ValueError, match="stream"):
            _cli("--secagg", "pairwise")
        with pytest.raises(ValueError, match="edge_aggregators"):
            _cli("--secagg", "grouped", "--agg_mode", "stream")
        with pytest.raises(ValueError, match="grouped"):
            _cli("--secagg", "pairwise", "--agg_mode", "stream",
                 "--edge_aggregators", "2")
        # a threshold the masking group could never satisfy — or one
        # that voids privacy — fails at config time, never a silent clamp
        with pytest.raises(ValueError, match="exceeds the smallest"):
            _cli("--secagg", "pairwise", "--agg_mode", "stream",
                 "--secagg_threshold", "5")
        with pytest.raises(ValueError, match="privacy"):
            _cli("--secagg", "pairwise", "--agg_mode", "stream",
                 "--secagg_threshold", "1")
        with pytest.raises(ValueError, match="exceeds the smallest"):
            _cli("--secagg", "grouped", "--agg_mode", "stream",
                 "--edge_aggregators", "2", "--secagg_threshold", "3")


class TestHealthSuppression:
    def test_suppressed_stats_named_not_zeroed(self):
        from fedml_tpu.obs.health import HealthAccumulator
        from fedml_tpu.obs.trend import validate_health_ledger
        h = HealthAccumulator(kind="params", alarms=False,
                              suppress_payload="secagg_pairwise_masking")
        h.round_start(0, None, expected=[1, 2])
        h.observe_admitted(1, object(), 4.0)  # payload is never touched
        h.observe_admitted(2, object(), 6.0)
        line = h.round_end(0)
        assert line["suppressed"] == {
            "fields": ["norm", "alignment"],
            "reason": "secagg_pairwise_masking"}
        assert line["norm"]["count"] == 0     # absent, not fabricated
        assert line["accepted"] == 2          # fairness still counts
        assert line["weight"] == 10.0
        assert validate_health_ledger([line]) == []
