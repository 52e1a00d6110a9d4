"""Multi-worker serving pool contracts (ISSUE 15): N accept loops × one
registry with unchanged hot-swap semantics, torn-read-free responses
under concurrent publish (the checksum/fingerprint trick from the wire
tests), worker-labeled telemetry, tiered shedding wired to the SAME
SloEvaluator verdicts as deep-healthz, shed-reason accounting under
saturation, and the shared-socket fallback.
"""

import http.client
import json
import threading
import time

import jax
import numpy as np
import pytest

from fedml_tpu.obs import telemetry
from fedml_tpu.obs.perf import SloEvaluator
from fedml_tpu.serve.batcher import MicroBatcher, ShedError, TierGate
from fedml_tpu.serve.pool import ServeWorkerPool
from fedml_tpu.serve.registry import ModelRegistry

DIM, CLASSES = 6, 4


def _linear_apply():
    return jax.jit(lambda p, x: x.reshape(x.shape[0], -1) @ p["w"] + p["b"])


def _params(version: int):
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


def _pool(workers=2, version=0, history=64, **kw):
    registry = ModelRegistry(_linear_apply(), history=history)
    registry.publish(_params(version), version)
    kw.setdefault("max_delay_s", 0.001)
    pool = ServeWorkerPool(registry, workers=workers, **kw)
    return registry, pool


def _post(port, payload, conn=None):
    own = conn is None
    if own:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
    conn.request("POST", "/predict", json.dumps(payload),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    body = json.loads(resp.read())
    if own:
        conn.close()
    return resp.status, body


def _get(port, path, conn=None):
    own = conn is None
    if own:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
    conn.request("GET", path)
    resp = conn.getresponse()
    body = json.loads(resp.read())
    if own:
        conn.close()
    return resp.status, body


# -- pool lifecycle ----------------------------------------------------------

@pytest.mark.parametrize("reuseport", [True, False])
def test_pool_serves_on_one_port_both_socket_modes(reuseport):
    registry, pool = _pool(workers=3, reuseport=reuseport)
    pool.start()
    try:
        workers_seen = set()
        for _ in range(12):
            status, body = _get(pool.port, "/healthz")
            assert status == 200
            assert body["workers"] == 3
            assert len(body["queue_depths"]) == 3
            workers_seen.add(body["worker"])
            status, body = _post(pool.port, {"x": _probe_x().tolist()})
            assert status == 200 and body["version"] == 0
            assert _consistent(np.asarray(body["y"]), 0)
        assert workers_seen <= {0, 1, 2}
    finally:
        pool.stop()


def test_pool_rejects_invalid_workers_and_factory_kwargs():
    registry = ModelRegistry(_linear_apply())
    with pytest.raises(ValueError, match="workers"):
        ServeWorkerPool(registry, workers=0)
    with pytest.raises(ValueError, match="factory"):
        ServeWorkerPool(registry, batcher_factory=lambda i: None,
                        queue_depth=8)
    # slo + custom factory: the pool cannot inject the gate, and
    # dropping it silently would divorce shedding from deep-healthz —
    # fail loudly instead
    with pytest.raises(ValueError, match="slo"):
        ServeWorkerPool(registry, batcher_factory=lambda i: None,
                        slo=object())


def test_pool_hot_swap_never_torn_and_versions_published_only():
    """Satellite: concurrent publish under multi-worker serving — every
    response's version is one that WAS published and its params are
    internally consistent (fingerprint kernel/bias pair), across all
    workers, while 15 swaps land mid-load."""
    registry, pool = _pool(workers=3, queue_depth=512)
    pool.start()
    published = {0}
    errors = []
    stop = threading.Event()

    def reader(tid):
        conn = http.client.HTTPConnection("127.0.0.1", pool.port,
                                          timeout=10)
        last = -1
        while not stop.is_set():
            try:
                status, body = _post(pool.port,
                                     {"x": _probe_x().tolist()}, conn)
            except Exception:
                conn.close()
                conn = http.client.HTTPConnection("127.0.0.1", pool.port,
                                                  timeout=10)
                continue
            if status != 200:
                continue
            v = body["version"]
            y = np.asarray(body["y"])
            if v not in published:
                errors.append(("unpublished version", v))
            if not _consistent(y, v):
                errors.append(("torn", v, y.tolist()))
            if v < last:
                errors.append(("version regression", last, v))
            last = v
        conn.close()

    readers = [threading.Thread(target=reader, args=(i,))
               for i in range(4)]
    for t in readers:
        t.start()
    for v in range(1, 16):
        published.add(v)     # add BEFORE publish: readers may see it
        #                      the instant the registry swaps
        registry.publish(_params(v), v)
        time.sleep(0.01)
    time.sleep(0.05)
    stop.set()
    for t in readers:
        t.join(timeout=30)
    pool.stop()
    assert not errors, errors[:5]


def test_pool_worker_labeled_telemetry():
    telemetry.enable()
    try:
        registry, pool = _pool(workers=2)
        pool.start()
        for _ in range(6):
            _post(pool.port, {"x": _probe_x().tolist()})
        snap = telemetry.get_registry().snapshot()
        req_series = [k for k in snap["counters"]
                      if k.startswith("fedml_serve_requests_total")
                      and 'worker="' in k]
        assert req_series, "no worker-labeled request counters"
        gauges = [k for k in snap["gauges"]
                  if k.startswith("fedml_serve_queue_utilization_ratio")]
        assert gauges, "no queue-utilization gauges"
        assert snap["gauges"].get("fedml_serve_workers_value") == 2.0
        pool.stop()
    finally:
        telemetry.disable()


def test_pool_workers_land_on_one_metrics_scrape():
    """--metrics_port exposes EVERY pool worker on a single scrape:
    the workers are threads over one process registry, so one exposition
    carries each worker's labeled series side by side."""
    telemetry.enable()
    server = None
    try:
        registry, pool = _pool(workers=2)
        pool.start()
        for i in range(8):
            _post(pool.port, {"x": _probe_x().tolist()})
        server = telemetry.start_http_server(0, host="127.0.0.1")
        assert server is not None
        port = server.server_address[1]
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
        conn.request("GET", "/metrics")
        text = conn.getresponse().read().decode()
        conn.close()
        workers_seen = {w for w in ("0", "1")
                        if f'fedml_serve_requests_total{{worker="{w}"}}'
                        in text}
        assert workers_seen == {"0", "1"}, \
            f"one scrape must carry every worker, saw {workers_seen}"
        pool.stop()
    finally:
        if server is not None:
            server.shutdown()
            server.server_close()
        telemetry.disable()


def test_metrics_endpoint_fails_loud_when_telemetry_disabled():
    """start_http_server over the Null registry would serve an empty
    exposition forever — it must raise, not lie."""
    assert telemetry.get_registry().__class__.__name__ == "NullRegistry"
    with pytest.raises(ValueError, match="telemetry is disabled"):
        telemetry.start_http_server(0, host="127.0.0.1")


# -- tiered admission + SLO coupling ----------------------------------------

def test_best_effort_sheds_at_soft_watermark_interactive_keeps_reserve():
    registry = ModelRegistry(_linear_apply())
    registry.publish(_params(0), 0)
    batcher = MicroBatcher(registry, queue_depth=4,
                           best_effort_headroom=0.5)  # BE cap = 2
    batcher.submit(_probe_x())
    batcher.submit(_probe_x())
    with pytest.raises(ShedError, match="queue_full"):
        batcher.submit(_probe_x(), tier="best_effort")
    batcher.submit(_probe_x())          # interactive still admitted
    batcher.submit(_probe_x())
    with pytest.raises(ShedError, match="queue_full"):
        batcher.submit(_probe_x())      # hard cap for everyone
    with pytest.raises(ValueError, match="unknown tier"):
        batcher.submit(_probe_x(), tier="bulk")
    batcher.stop(drain=False)


def test_tier_gate_and_deep_healthz_read_the_same_verdict():
    """The contract satellite (c) pins: when tiered admission sheds
    best_effort for slo_degraded, /healthz?deep=1 answers 503 naming
    the SAME breached objective — one evaluator, never two stories."""
    telemetry.enable()
    try:
        reg = telemetry.get_registry()
        slo = SloEvaluator(registry=reg)
        registry, pool = _pool(workers=2, queue_depth=4, slo=slo)
        pool.start()
        gate = pool.batchers[0].tier_gate
        assert isinstance(gate, TierGate)
        assert gate.degraded() is False
        # worker 0 reports a nearly-full queue (the gauge every batcher
        # maintains on submit/dequeue): utilization 1.0 breaches the
        # serve_queue_utilization_ratio objective (threshold 0.9)
        reg.gauge("fedml_serve_queue_utilization_ratio",
                  worker="0").set(1.0)
        gate._checked_at = -1e30    # expire the TTL cache
        assert gate.degraded() is True
        with pytest.raises(ShedError, match="slo_degraded"):
            pool.batchers[1].submit(_probe_x(), tier="best_effort")
        status, body = _get(pool.port, "/healthz?deep=1")
        assert status == 503, body
        assert body["status"] == "slo_breach"
        assert not body["slo"]["serve_queue_utilization_ratio"]["ok"]
        # interactive traffic still flows on the healthy worker
        assert pool.batchers[1].submit(_probe_x()) is not None
        pool.stop()
    finally:
        telemetry.disable()


def test_slo_reads_worst_worker_not_the_average():
    telemetry.enable()
    try:
        reg = telemetry.get_registry()
        reg.gauge("fedml_serve_queue_utilization_ratio",
                  worker="0").set(0.05)
        reg.gauge("fedml_serve_queue_utilization_ratio",
                  worker="1").set(0.97)
        slo = SloEvaluator(registry=reg)
        out = slo.evaluate(count_breaches=False)
        v = out["serve_queue_utilization_ratio"]
        assert v["value"] == 0.97 and not v["ok"]
    finally:
        telemetry.disable()


def test_shed_reason_accounting_under_saturation():
    """Satellite: every 429 under saturation is accounted, by reason and
    tier, in fedml_serve_shed_total — counters and observed sheds agree
    exactly."""
    telemetry.enable()
    try:
        registry = ModelRegistry(_linear_apply())
        registry.publish(_params(0), 0)
        batcher = MicroBatcher(registry, queue_depth=3,
                               best_effort_headroom=1 / 3, worker="7")
        sheds = {"queue_full": 0}
        admitted = 0
        for i in range(10):
            tier = "best_effort" if i % 2 else "interactive"
            try:
                batcher.submit(_probe_x(), tier=tier)
                admitted += 1
            except ShedError as e:
                sheds[e.reason] += 1
        assert admitted == 3 and sheds["queue_full"] == 7
        snap = telemetry.get_registry().snapshot()
        total = sum(v for k, v in snap["counters"].items()
                    if k.startswith("fedml_serve_shed_total")
                    and 'reason="queue_full"' in k and 'worker="7"' in k)
        assert total == 7
        be = sum(v for k, v in snap["counters"].items()
                 if k.startswith("fedml_serve_shed_total")
                 and 'tier="best_effort"' in k and 'worker="7"' in k)
        assert be >= 4    # best_effort shed first (soft watermark)
        batcher.stop(drain=False)
    finally:
        telemetry.disable()


def test_slo_degraded_sheds_do_not_feed_the_shed_rate_objective():
    """Tier-gate sheds must not inflate serve_shed_rate: counting them
    would close a feedback loop (sheds raise the rate, the rate keeps
    the gate degraded, the gate sheds more) that latches a transient
    breach into a permanent one."""
    telemetry.enable()
    try:
        reg = telemetry.get_registry()
        reg.counter("fedml_serve_requests_total").inc(100)
        reg.counter("fedml_serve_shed_total", reason="queue_full",
                    tier="interactive").inc(2)
        reg.counter("fedml_serve_shed_total", reason="slo_degraded",
                    tier="best_effort").inc(500)
        slo = SloEvaluator(registry=reg)
        v = slo.evaluate(count_breaches=False)["serve_shed_rate"]
        assert v["value"] == 0.02, (
            f"slo_degraded sheds leaked into shed_rate: {v}")
        assert v["ok"]
    finally:
        telemetry.disable()


def test_unbounded_queue_has_no_best_effort_watermark():
    """queue_depth=0 (unbounded) must not collapse the best-effort cap
    to 1 — there is no fill fraction, so there is no watermark (the
    tier gate still applies)."""
    from fedml_tpu.serve.batcher import best_effort_cap
    assert best_effort_cap(0, 0.5) is None
    assert best_effort_cap(8, 0.5) == 4
    with pytest.raises(ValueError, match="headroom"):
        best_effort_cap(8, 1.5)
    registry = ModelRegistry(_linear_apply())
    registry.publish(_params(0), 0)
    batcher = MicroBatcher(registry, queue_depth=0)
    batcher.submit(_probe_x())
    batcher.submit(_probe_x(), tier="best_effort")   # not blackholed
    batcher.stop(drain=False)


def test_tier_gate_ttl_caches_the_evaluator():
    calls = []

    class _Slo:
        def evaluate(self, count_breaches=True):
            calls.append(count_breaches)
            return {"x": {"ok": True}}

    gate = TierGate(_Slo(), ttl_s=60.0)
    for _ in range(50):
        assert gate.degraded() is False
    assert len(calls) == 1, "gate must not evaluate per request"
    assert calls[0] is False, "admission probes must not count breaches"


# -- CLI config gates --------------------------------------------------------

class TestServeConfigGates:
    def test_serve_workers_requires_serve_port(self):
        from fedml_tpu.experiments.main import main
        with pytest.raises(ValueError, match="serve_port"):
            main(["--algo", "cross_silo", "--serve_workers", "2"])

    def test_serve_workers_must_be_positive(self):
        from fedml_tpu.experiments.main import main
        with pytest.raises(ValueError, match="serve_workers"):
            main(["--algo", "cross_silo", "--serve_port", "8351",
                  "--serve_workers", "0"])

    def test_best_effort_headroom_bounds(self):
        from fedml_tpu.experiments.main import main
        with pytest.raises(ValueError, match="best_effort_headroom"):
            main(["--algo", "cross_silo", "--serve_port", "8351",
                  "--serve_best_effort_headroom", "1.5"])
