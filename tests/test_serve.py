"""Serving-layer contracts (ISSUE 3): bucket-padding invariance, torn-
read-free hot swaps under concurrent load, deadline shedding, drain-on-
shutdown, checkpoint watching across retention GC, and the HTTP surface.

The core invariants mirror the training side's: padding must be
bit-invisible (test_padding_invariance.py for cohorts, here for request
batches), and a reader must never observe half of a model swap (the
checkpointer's torn-save contract, now at serve time).
"""

import http.client
import json
import threading
import time

import jax
import numpy as np
import pytest

from fedml_tpu.serve.batcher import MicroBatcher, ShedError
from fedml_tpu.serve.registry import CheckpointWatcher, ModelRegistry
from fedml_tpu.serve.server import ServeFrontend

DIM, CLASSES = 6, 4


def _linear_apply():
    return jax.jit(lambda p, x: x.reshape(x.shape[0], -1) @ p["w"] + p["b"])


def _params(version: int):
    """Version-fingerprinted params: row-0 kernel weight == version and
    bias == onehot(version % CLASSES), so a torn kernel/bias mix is
    detectable from any response (the serve_bench probe)."""
    w = np.zeros((DIM, CLASSES), np.float32)
    w[0, :] = float(version)
    b = np.zeros(CLASSES, np.float32)
    b[version % CLASSES] = 1.0
    return {"w": w, "b": b}


def _consistent(y: np.ndarray, version: int) -> bool:
    return (int(round(float(y.min()))) == version
            and int(np.argmax(y)) == version % CLASSES)


def _probe_x():
    x = np.zeros(DIM, np.float32)
    x[0] = 1.0
    return x


def _stack(buckets=(1, 2, 4, 8), version=0, **kw):
    registry = ModelRegistry(_linear_apply(), history=64)
    registry.publish(_params(version), version)
    batcher = MicroBatcher(registry, buckets=buckets, **kw)
    return registry, batcher


# -- bucket padding ----------------------------------------------------------

def test_bucket_padding_invariance():
    """3 live requests padded up to the 8-bucket must return EXACTLY the
    logits of an unpadded direct apply — padded rows are invisible."""
    registry, batcher = _stack(buckets=(8,), max_delay_s=0.05)
    batcher.start()
    rng = np.random.RandomState(0)
    xs = [rng.randn(DIM).astype(np.float32) for _ in range(3)]
    futs = [batcher.submit(x) for x in xs]
    outs = [f.result(10) for f in futs]
    m = registry.current()
    direct = np.asarray(m.apply_fn(m.params, np.stack(xs)))
    for i, out in enumerate(outs):
        np.testing.assert_allclose(np.asarray(out.y), direct[i], atol=1e-6)
        assert out.version == 0
    batcher.stop()


def test_requests_coalesce_into_one_bucket():
    """A burst lands in few, large batches (occupancy histogram moves),
    not one batch per request."""
    from fedml_tpu.obs import telemetry
    telemetry.enable()
    try:
        registry, batcher = _stack(buckets=(1, 2, 4, 8), max_delay_s=0.02)
        futs = [batcher.submit(_probe_x()) for _ in range(8)]  # queued:
        batcher.start()                              # worker not yet live
        for f in futs:
            f.result(10)
        stats = batcher._h_occupancy.stats()
        assert stats["max"] == 8.0, f"burst never coalesced: {stats}"
        batcher.stop()
    finally:
        telemetry.disable()


# -- hot swap under load -----------------------------------------------------

def test_hot_swap_no_torn_reads_and_monotone_versions():
    """4 reader threads hammer predict while versions 1..15 publish
    mid-load: every response must be internally consistent with the
    version that served it, and each reader's observed version sequence
    must be non-decreasing (the registry only moves forward)."""
    registry, batcher = _stack(max_delay_s=0.001, queue_depth=512)
    batcher.start()
    batcher.warmup(_probe_x())
    stop = threading.Event()
    errors, seqs = [], []

    def reader():
        seq = []
        while not stop.is_set():
            try:
                r = batcher.predict(_probe_x(), timeout=10)
            except ShedError:
                continue
            if not _consistent(np.asarray(r.y), r.version):
                errors.append((np.asarray(r.y), r.version))
            seq.append(r.version)
        seqs.append(seq)

    readers = [threading.Thread(target=reader) for _ in range(4)]
    for t in readers:
        t.start()
    for v in range(1, 16):
        time.sleep(0.01)
        registry.publish(_params(v), v)
    time.sleep(0.02)
    stop.set()
    for t in readers:
        t.join(timeout=10)
    batcher.stop()
    assert not errors, f"torn reads: {errors[:3]}"
    for seq in seqs:
        assert seq == sorted(seq), "reader observed a version regression"
    assert max(max(s) for s in seqs if s) == 15, "swaps never became live"


def test_registry_pin_rollback_and_stale_publish():
    registry = ModelRegistry(_linear_apply(), history=8)
    assert registry.current() is None
    registry.publish(_params(0), 0)
    registry.publish(_params(1), 1)
    assert registry.version == 1
    assert registry.rollback() == 0          # live back to 0, pinned
    assert registry.version == 0 and registry.pinned == 0
    assert registry.publish(_params(2), 2)   # lands in history only
    assert registry.version == 0
    registry.unpin()
    assert registry.version == 2 and registry.pinned is None
    registry.pin(1)
    assert registry.version == 1
    assert not registry.publish(_params(1), 1), "stale publish accepted"
    with pytest.raises(KeyError):
        registry.pin(99)


def test_history_eviction_never_drops_pinned_version():
    """Serve-while-train keeps publishing past a pin: eviction must skip
    the pinned/live version so it stays rollback-able/pin-able."""
    registry = ModelRegistry(_linear_apply(), history=3)
    for v in range(3):
        registry.publish(_params(v), v)
    registry.rollback()                       # live+pinned = 1
    for v in range(3, 10):                    # publishes keep landing
        registry.publish(_params(v), v)
    assert 1 in registry.versions(), "pinned version evicted"
    assert registry.version == 1
    with pytest.raises(RuntimeError):
        registry.rollback()  # nothing older than the pin survives: loud,
        #                      not a ValueError from a missing dict key
    registry.unpin()
    assert registry.version == 9


# -- shedding ----------------------------------------------------------------

def test_deadline_shedding():
    """A request whose deadline expires while queued is shed at dequeue,
    not served late; fresh requests still get answers."""
    registry = ModelRegistry(
        lambda p, x: (time.sleep(0.08), x @ p["w"] + p["b"])[1])
    registry.publish(_params(0), 0)
    batcher = MicroBatcher(registry, buckets=(1,), max_delay_s=0.0)
    batcher.start()
    blocker = batcher.submit(_probe_x())          # occupies the worker
    doomed = batcher.submit(_probe_x(), deadline_s=0.01)
    with pytest.raises(ShedError, match="deadline"):
        doomed.result(10)
    assert blocker.result(10).version == 0
    ok = batcher.submit(_probe_x(), deadline_s=5.0)
    assert ok.result(10).version == 0
    batcher.stop()


def test_queue_full_sheds_at_submit():
    registry, batcher = _stack(queue_depth=2)  # worker NOT started
    batcher.submit(_probe_x())
    batcher.submit(_probe_x())
    with pytest.raises(ShedError, match="queue_full"):
        batcher.submit(_probe_x())
    batcher.stop(drain=False)


def test_no_model_sheds():
    registry = ModelRegistry(_linear_apply())
    batcher = MicroBatcher(registry, buckets=(1,)).start()
    with pytest.raises(ShedError, match="no_model"):
        batcher.predict(_probe_x(), timeout=10)
    batcher.stop()


# -- shutdown ----------------------------------------------------------------

def test_drain_on_shutdown_answers_queued_requests():
    registry, batcher = _stack(buckets=(1, 2, 4), max_delay_s=0.001)
    futs = [batcher.submit(_probe_x()) for _ in range(10)]  # queued
    batcher.start()
    batcher.stop(drain=True)
    for f in futs:
        assert _consistent(np.asarray(f.result(0).y), 0)
    with pytest.raises(ShedError, match="shutdown"):
        batcher.submit(_probe_x())


def test_malformed_instance_fails_only_its_own_request():
    """One bad-shape x in a micro-batch must fail ITS request alone —
    batchmates still get answers."""
    registry, batcher = _stack(buckets=(4,), max_delay_s=0.01)
    good = [batcher.submit(_probe_x()) for _ in range(2)]
    bad = batcher.submit(np.zeros(3, np.float32))  # wrong sample shape
    batcher.start()
    for f in good:
        assert f.result(10).version == 0
    with pytest.raises(ValueError, match="does not match"):
        bad.result(10)
    # the malformed request arriving FIRST must not hijack the shape
    # anchor either (the model shape is learned from the good batch)
    bad_first = batcher.submit(np.zeros(3, np.float32))
    good_after = [batcher.submit(_probe_x()) for _ in range(2)]
    with pytest.raises(ValueError, match="does not match"):
        bad_first.result(10)
    for f in good_after:
        assert f.result(10).version == 0
    batcher.stop()


def test_cancelled_future_does_not_kill_worker():
    """A client cancelling its Future (client-side timeout) must not
    raise InvalidStateError out of the worker — everyone else's requests
    keep answering."""
    registry, batcher = _stack(buckets=(4,), max_delay_s=0.01)
    futs = [batcher.submit(_probe_x()) for _ in range(4)]
    assert futs[0].cancel()
    batcher.start()
    for f in futs[1:]:
        assert f.result(10).version == 0
    assert batcher.predict(_probe_x(), timeout=10).version == 0
    batcher.stop()


def test_abort_shutdown_sheds_queued_requests():
    registry, batcher = _stack()
    futs = [batcher.submit(_probe_x()) for _ in range(5)]
    batcher.stop(drain=False)   # never started: settles inline
    for f in futs:
        with pytest.raises(ShedError, match="shutdown"):
            f.result(0)


# -- checkpoint watcher ------------------------------------------------------

def _ck_params(seed):
    rng = np.random.RandomState(seed)
    return {"w": rng.randn(DIM, CLASSES).astype(np.float32),
            "b": rng.randn(CLASSES).astype(np.float32)}


def test_watcher_publishes_rounds_and_tolerates_gc(tmp_path):
    """Rounds appear → watcher publishes them in order; the retention GC
    (keep_last_n) deleting old steps — and a bogus/vanished step dir —
    must never kill the watcher or the live model."""
    from fedml_tpu.utils.checkpoint import RoundCheckpointer
    ck_dir = str(tmp_path / "ck")
    ck = RoundCheckpointer(ck_dir, save_every=1, keep_last_n=2)
    registry = ModelRegistry(_linear_apply(), history=16)
    watcher = CheckpointWatcher(registry, ck_dir, poll_s=0.05)

    def state(i):
        return {"params": _ck_params(i),
                "round_idx": np.asarray(i, np.int64)}

    assert watcher.poll_once() == 0            # empty dir: no-op
    ck.save(0, state(0))
    ck.save(1, state(1))
    assert watcher.poll_once() == 2
    assert registry.version == 1

    # retention GC: saves 2 and 3 evict 0 and 1 from disk
    ck.save(2, state(2))
    ck.save(3, state(3))
    import os
    steps = sorted(n for n in os.listdir(ck_dir) if n.isdigit())
    assert steps == ["2", "3"], f"keep_last_n GC kept {steps}"

    # a step dir that vanishes between list and load: simulate with a
    # bogus empty digit-dir — unreadable, must be skipped not fatal
    os.makedirs(str(tmp_path / "ck" / "7"))
    assert watcher.poll_once() == 2            # 2 and 3 load; 7 skipped
    assert registry.version == 3
    assert watcher._seen == 7                  # not retried forever
    np.testing.assert_allclose(
        np.asarray(registry.current().params["w"]), _ck_params(3)["w"])
    ck.close()


def test_serve_while_train_publish_hook(tmp_path):
    """The cross-silo server's publish hook feeds a registry each round:
    versions advance with training and the LAST round's global is what
    serves (the serve-while-train acceptance, pump-mode)."""
    from fedml_tpu.algorithms.cross_silo import (FedAvgClientActor,
                                                 FedAvgServerActor)
    from fedml_tpu.comm.local import LocalHub

    init = {"dense": {"kernel": np.zeros((4, 3), np.float32)}}

    def train_fn(params, client_idx, round_idx):
        return jax.tree.map(lambda v: v + 1.0, params), 10

    registry = ModelRegistry(lambda p, x: x, history=8)
    hub = LocalHub()
    server = FedAvgServerActor(
        hub.transport(0), init, client_num_in_total=2,
        client_num_per_round=2, num_rounds=3, publish=registry.publish)
    clients = [FedAvgClientActor(i, hub.transport(i), train_fn)
               for i in (1, 2)]
    server.register_handlers()
    for c in clients:
        c.register_handlers()
    server.start()
    hub.pump()
    assert registry.versions() == [0, 1, 2]
    assert registry.version == 2
    np.testing.assert_allclose(
        np.asarray(registry.current().params["dense"]["kernel"]),
        np.full((4, 3), 3.0))


# -- HTTP frontend -----------------------------------------------------------

def _get(port, path):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
    conn.request("GET", path)
    resp = conn.getresponse()
    body = resp.read()
    conn.close()
    return resp.status, json.loads(body) if body.startswith(b"{") else body


def _post(port, path, payload):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
    conn.request("POST", path, json.dumps(payload),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    body = json.loads(resp.read())
    conn.close()
    return resp.status, body


def test_http_frontend_lifecycle(tmp_path):
    registry = ModelRegistry(_linear_apply(), history=8)
    batcher = MicroBatcher(registry, buckets=(1, 2, 4), max_delay_s=0.001)
    frontend = ServeFrontend(registry, batcher, port=0).start()
    port = frontend.port
    try:
        # before any model: health 503 (LB keeps us out of rotation),
        # predict 503
        status, body = _get(port, "/healthz")
        assert status == 503 and body["status"] == "no_model"
        status, body = _post(port, "/predict", {"x": _probe_x().tolist()})
        assert status == 503 and body["reason"] == "no_model"

        registry.publish(_params(4), 4)
        status, body = _get(port, "/healthz")
        assert status == 200 and body["version"] == 4
        status, body = _get(port, "/healthz?probe=1")  # LB cache-buster
        assert status == 200
        status, body = _post(port, "/predict", {"x": _probe_x().tolist()})
        assert status == 200 and body["version"] == 4
        assert _consistent(np.asarray(body["y"]), 4)

        status, body = _get(port, "/version")
        assert status == 200 and body["version"] == 4
        assert body["history"] == [4]

        status, body = _post(port, "/predict", {"wrong_key": 1})
        assert status == 400
        status, body = _post(port, "/predict",
                             {"x": _probe_x().tolist(),
                              "deadline_ms": "fast"})
        assert status == 400, "non-numeric deadline must 400, not crash"
        status, _ = _get(port, "/nope")
        assert status == 404
        status, _ = _post(port, "/nope", {"x": [1]})
        assert status == 404
    finally:
        frontend.stop()
    # stopped batcher sheds: the frontend maps it to 429 — exercised via
    # the batcher directly (the listener is closed now)
    with pytest.raises(ShedError, match="shutdown"):
        batcher.submit(_probe_x())


def test_http_keepalive_two_requests_one_connection():
    """Satellite pin (ISSUE 15): the handler speaks HTTP/1.1 keep-alive
    with correct Content-Length framing — two requests ride ONE TCP
    connection, byte-accurate bodies, no per-request dial."""
    registry = ModelRegistry(_linear_apply(), history=8)
    registry.publish(_params(2), 2)
    batcher = MicroBatcher(registry, buckets=(1, 2), max_delay_s=0.001)
    frontend = ServeFrontend(registry, batcher, port=0).start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", frontend.port,
                                          timeout=10)
        conn.connect()
        sock_before = conn.sock
        for i in range(2):   # two POSTs, one connection
            conn.request("POST", "/predict",
                         json.dumps({"x": _probe_x().tolist()}),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            assert resp.version == 11, "handler fell back to HTTP/1.0"
            clen = resp.getheader("Content-Length")
            body = resp.read()
            assert clen is not None and int(clen) == len(body), (
                "Content-Length does not frame the body — keep-alive "
                "would desync on the next request")
            assert json.loads(body)["version"] == 2
        assert conn.sock is sock_before, "connection was re-dialed"
        # a GET on the SAME connection still frames correctly
        conn.request("GET", "/healthz")
        resp = conn.getresponse()
        assert resp.status == 200
        assert int(resp.getheader("Content-Length")) == len(resp.read())
        conn.close()
    finally:
        frontend.stop()


def test_registry_pin_survives_concurrent_publish_storm():
    """Satellite audit (ISSUE 15): a pinned version must never be
    evicted out from under a serving worker while publishes hammer the
    registry from another thread — current() stays the pinned snapshot
    and the pinned version stays in history throughout."""
    registry = ModelRegistry(_linear_apply(), history=3)
    for v in range(3):
        registry.publish(_params(v), v)
    registry.pin(1)
    errors = []
    stop = threading.Event()

    def reader():
        while not stop.is_set():
            m = registry.current()
            if m is None or m.version != 1:
                errors.append(("lost pin", None if m is None
                               else m.version))
            if 1 not in registry.versions():
                errors.append(("pinned version evicted from history",))

    threads = [threading.Thread(target=reader) for _ in range(2)]
    for t in threads:
        t.start()
    for v in range(3, 40):
        registry.publish(_params(v), v)
    stop.set()
    for t in threads:
        t.join(timeout=10)
    assert not errors, errors[:3]
    assert 1 in registry.versions()
    # history stayed bounded despite the protected entries
    assert len(registry.versions()) <= 4
    registry.unpin()
    assert registry.version == 39


def test_rollback_on_fully_evicted_history_fails_loudly():
    """Satellite audit: rollback() when eviction left nothing older than
    the live version raises — it must never serve None or a KeyError
    from a missing history slot."""
    registry = ModelRegistry(_linear_apply(), history=2)
    for v in range(6):   # eviction keeps only the newest + live
        registry.publish(_params(v), v)
    registry.rollback()          # one older version still exists
    assert registry.version == 4
    registry.unpin()
    for v in range(6, 12):
        registry.publish(_params(v), v)
    registry.rollback()
    with pytest.raises(RuntimeError, match="cannot rollback"):
        registry.rollback()      # nothing older survived eviction
    assert registry.current() is not None, "rollback left a None model"


def test_http_deadline_propagates_to_429():
    """A request whose deadline_ms cannot be met while the worker is
    busy answers 429 (shed), not a late 200."""
    registry = ModelRegistry(
        lambda p, x: (time.sleep(0.1), x @ p["w"] + p["b"])[1])
    registry.publish(_params(0), 0)
    batcher = MicroBatcher(registry, buckets=(1,), max_delay_s=0.0)
    frontend = ServeFrontend(registry, batcher, port=0).start()
    port = frontend.port
    try:
        blocker = threading.Thread(
            target=_post, args=(port, "/predict",
                                {"x": _probe_x().tolist()}))
        blocker.start()
        time.sleep(0.03)  # the blocker's batch is now on the worker
        status, body = _post(port, "/predict",
                             {"x": _probe_x().tolist(), "deadline_ms": 5})
        blocker.join(timeout=10)
        assert status == 429 and body["reason"] == "deadline"
    finally:
        frontend.stop()
