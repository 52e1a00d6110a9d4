"""Device & compile observatory (fedml_tpu/obs/device.py) — the ISSUE 10
acceptance pins:

* memory-stats fallback ordering: ``device.memory_stats()`` where the
  backend provides it, the ``jax.live_arrays()`` sum where it doesn't
  (CPU), and ``null`` where neither is measurable — never a fabricated 0;
* named compile ledger: each jit cache entry records its wall time and
  arg signature; the ledger rides the perf.jsonl ``device`` section and
  `trend.validate_ledger` accepts it (with torn-tail tolerance), while
  old ledgers WITHOUT the section keep validating;
* sentry cache-key diff: a real forced re-jit fires a verdict that
  NAMES the arg shape that changed;
* honest MFU: no peak, so no MFU, on the CPU backend; one peak table,
  every kind of it a case;
* trend device gates: pass on identical ledgers, fail (exit 1, named)
  on a seeded compile-time or device-memory regression, and skip
  vacuously on pre-device-observatory ledgers;
* telemetry naming: no non-monotonic device measurement wears a fake
  ``*_total`` counter suffix.
"""

import json
import pathlib
import re

import pytest

from fedml_tpu.obs import telemetry, trend
from fedml_tpu.obs import device as device_obs
from fedml_tpu.obs.device import (DeviceRecorder, call_signature,
                                  device_memory_snapshot,
                                  peak_tflops_for_device, signature_diff)
from fedml_tpu.obs.perf import (DEFAULT_SLOS, PerfRecorder, RecompileError,
                                RecompileSentry, SloEvaluator)


def _reg():
    return telemetry.TelemetryRegistry()


# ---------------------------------------------------------------------------
# the peak table
# ---------------------------------------------------------------------------

class _FakeDev:
    def __init__(self, kind, platform="tpu"):
        self.device_kind = kind
        self.platform = platform


@pytest.mark.parametrize("kind,peak", [
    ("TPU v5e", 197.0), ("TPU v5 lite", 197.0), ("TPU v5p chip", 459.0),
    ("TPU v6e", 918.0), ("trillium", 918.0), ("TPU v4", 275.0),
    ("TPU v3", 123.0),
])
def test_peak_table_kind_match_and_env_override(kind, peak, monkeypatch):
    monkeypatch.delenv("BENCH_PEAK_TFLOPS", raising=False)
    assert peak_tflops_for_device(_FakeDev(kind)) == peak
    monkeypatch.setenv("BENCH_PEAK_TFLOPS", "42.5")
    assert peak_tflops_for_device(_FakeDev(kind)) == 42.5
    assert "env override" in device_obs.peak_and_source(None)[1]


def test_no_default_peak(monkeypatch):
    """The module's "null, never 0" contract, applied to the peak: the
    CPU backend has NO peak (so no MFU), and an accelerator whose kind
    the table lacks is an error — never the v5e number by default."""
    monkeypatch.delenv("BENCH_PEAK_TFLOPS", raising=False)
    assert not hasattr(device_obs, "DEFAULT_PEAK_TFLOPS")
    cpu = _FakeDev("cpu", platform="cpu")
    assert peak_tflops_for_device(cpu) is None
    assert "cpu backend" in device_obs.peak_and_source(cpu)[1]
    for dev in (_FakeDev("mystery accelerator"), _FakeDev("TPU v9000"),
                None):
        with pytest.raises(ValueError, match="device_kind"):
            peak_tflops_for_device(dev)


# ---------------------------------------------------------------------------
# memory snapshot fallback ordering: memory_stats -> live_arrays -> null
# ---------------------------------------------------------------------------

class _StatsDev:
    id = 0
    platform = "tpu"
    device_kind = "TPU v5 lite"

    def memory_stats(self):
        return {"bytes_in_use": 1000, "peak_bytes_in_use": 2000,
                "bytes_limit": 4000}


def test_memory_snapshot_prefers_device_memory_stats(monkeypatch):
    import jax
    monkeypatch.setattr(jax, "local_devices", lambda: [_StatsDev()])
    snap = device_memory_snapshot()
    assert len(snap) == 1
    e = snap[0]
    assert e["source"] == "memory_stats"
    assert e["bytes_in_use"] == 1000
    assert e["peak_bytes"] == 2000
    assert e["bytes_limit"] == 4000
    assert e["utilization"] == pytest.approx(0.25)


def test_memory_snapshot_cpu_falls_back_to_live_arrays():
    import jax.numpy as jnp
    x = jnp.ones((128,), jnp.float32)  # keep alive through the snapshot
    snap = device_memory_snapshot()
    assert snap, "live arrays exist, the snapshot must see them"
    e = snap[0]
    assert e["source"] == "live_arrays"
    assert e["bytes_in_use"] >= x.nbytes
    assert e["peak_bytes"] is None          # no allocator stats on CPU
    assert e["bytes_limit"] is None


def test_memory_snapshot_absent_backend_is_null_never_zero(monkeypatch):
    import jax
    # no devices at all -> null
    monkeypatch.setattr(jax, "local_devices", lambda: [])
    assert device_memory_snapshot() is None
    # devices without memory_stats AND a broken live-arrays probe -> null
    class _BareDev:
        id = 0
        platform = "cpu"
        device_kind = "cpu"

        def memory_stats(self):
            return None

    monkeypatch.setattr(jax, "local_devices", lambda: [_BareDev()])
    monkeypatch.setattr(device_obs, "_live_bytes_by_device",
                        lambda: (_ for _ in ()).throw(RuntimeError("no")))
    assert device_memory_snapshot() is None


# ---------------------------------------------------------------------------
# compile ledger + flops + MFU on a real jit
# ---------------------------------------------------------------------------

def test_instrument_compile_ledger_flops_and_cpu_mfu_null():
    import jax
    import jax.numpy as jnp
    reg = _reg()
    rec = DeviceRecorder(registry=reg)
    f = rec.instrument("probe", jax.jit(lambda a: a @ a))
    rec.round_start()
    x = jnp.ones((16, 16), jnp.float32)
    for _ in range(3):
        f(x)
    section = rec.round_snapshot(round_s=0.05)
    # one compile entry, named, with wall time and the paying signature
    assert len(section["compiles"]) == 1
    entry = section["compiles"][0]
    assert entry["fn"] == "probe"
    assert entry["wall_s"] > 0
    assert entry["signature"] == "float32[16,16]"
    assert section["jit_calls"] == {"probe": 3}
    # XLA cost analysis: a [16,16] matmul is 2*16^3 flops per call
    assert section["flops"] == pytest.approx(3 * 2 * 16 ** 3, rel=0.5)
    assert section["flops_complete"] is True
    # the CPU backend has no accelerator peak: achieved FLOP/s is
    # measured, but peak and MFU ledger null with the reason — never a
    # ratio against an assumed chip
    assert section["backend"] == "cpu"
    assert section["achieved_flops_per_s"] > 0
    assert section["mfu"] is None and section["peak_tflops"] is None
    assert "cpu backend" in section["peak_source"]
    assert section["mfu_provenance"] == device_obs.MFU_PROVENANCE
    # later rounds: cache hit, no new compile entries
    rec.round_start()
    f(x)
    section2 = rec.round_snapshot(round_s=0.01)
    assert section2["compiles"] == []
    assert section2["jit_calls"] == {"probe": 1}
    snap = reg.snapshot()
    assert snap["counters"]['fedml_dev_compiles_total{fn="probe"}'] == 1
    # no peak => the MFU gauge is never registered (absent, not 0)
    assert snap["gauges"]["fedml_dev_achieved_flops_value"] > 0
    assert "fedml_perf_mfu_ratio" not in snap["gauges"]
    # an explicit peak (a caller that knows its chip) does gauge an MFU
    rec2 = DeviceRecorder(registry=reg, peak_tflops=1000.0)
    g = rec2.instrument("probe2", jax.jit(lambda a: a @ a))
    rec2.round_start()
    g(x)
    assert 0.0 < rec2.round_snapshot(round_s=0.01)["mfu"] <= 1.0
    assert 0.0 < reg.snapshot()["gauges"]["fedml_perf_mfu_ratio"] <= 1.0


def test_instrument_forwards_cache_probe_and_unmeasured_is_null():
    import jax
    import jax.numpy as jnp
    rec = DeviceRecorder(registry=_reg(), cost_analysis=False)
    f = rec.instrument("agg", jax.jit(lambda a: a + 1))
    assert hasattr(f, "_cache_size")
    rec.round_start()
    f(jnp.ones(4))
    section = rec.round_snapshot(round_s=0.01)
    # cost analysis off: flops/mfu ledger null, never a fabricated 0
    assert section["flops"] is None
    assert section["achieved_flops_per_s"] is None
    assert section["mfu"] is None
    assert section["flops_complete"] is False
    # ...and the compile entry still landed (cache growth is observable
    # without any analysis)
    assert [e["fn"] for e in section["compiles"]] == ["agg"]


# ---------------------------------------------------------------------------
# sentry cache-key diff names the changed shape (real forced re-jit)
# ---------------------------------------------------------------------------

def test_sentry_names_changed_arg_shape_on_forced_rejit(tmp_path):
    import jax
    import jax.numpy as jnp
    reg = _reg()
    rec = PerfRecorder(str(tmp_path / "perf.jsonl"), registry=reg,
                       strict_recompiles=True,
                       device=DeviceRecorder(registry=reg))
    f = rec.instrument_jit("hot", jax.jit(lambda x: x * 2.0))
    rec.round_start(0)
    f(jnp.ones((4,), jnp.float32))
    assert rec.round_end(0)["recompiles"] == 0     # baseline round
    rec.round_start(1)
    f(jnp.ones((8,), jnp.float32))                 # forced retrace
    with pytest.raises(RecompileError) as err:
        rec.round_end(1)
    msg = str(err.value)
    assert "hot" in msg
    assert "float32[4] -> float32[8]" in msg       # the actionable diff
    rec.close()


def test_signature_diff_and_sentry_without_signatures():
    assert signature_diff(("f32[4]",), ("f32[8]",)) \
        == "arg leaf[0]: f32[4] -> f32[8]"
    assert "arity" in signature_diff(("a",), ("a", "b"))
    assert signature_diff(None, ("a",)) == ""
    # a sentry never fed signatures still fires with the bare count
    sentry = RecompileSentry(registry=_reg())
    assert sentry.signature_change("nope") == ""
    sentry.note_signature("f", ("float32[4]",))
    sentry.note_signature("f", ("float32[8]",))
    assert "float32[4] -> float32[8]" in sentry.signature_change("f")


# ---------------------------------------------------------------------------
# ledger schema: device section rides perf.jsonl; old ledgers still pass
# ---------------------------------------------------------------------------

def _device_rows(n=3, compile_s=0.2, mem=1 << 20, mfu=0.001):
    rows = []
    for i in range(n):
        rows.append({
            "round": i, "round_s": 0.3,
            "phases": {"defended_aggregate": 0.2},
            "wire": {"bytes_out": 10, "bytes_in": 10},
            "rss": {"peak_bytes": 1 << 20},
            "recompiles": 0,
            "device": {
                "backend": "cpu",
                "memory": [{"id": 0, "source": "live_arrays",
                            "bytes_in_use": mem,
                            "round_peak_bytes": mem}],
                "compiles": ([{"fn": "train_fn", "wall_s": compile_s,
                               "signature": "float32[4]"}] if i == 0
                             else []),
                "jit_calls": {"train_fn": 2},
                "flops": 1e6, "achieved_flops_per_s": 3e6, "mfu": mfu,
                "peak_tflops": 197.0}})
    return rows


def _write(path, rows):
    with open(path, "w") as f:
        f.writelines(json.dumps(r) + "\n" for r in rows)
    return str(path)


def test_device_section_rides_live_ledger_with_torn_tail(tmp_path):
    import jax
    import jax.numpy as jnp
    reg = _reg()
    rec = PerfRecorder(str(tmp_path / "perf.jsonl"), registry=reg,
                       device=DeviceRecorder(registry=reg))
    f = rec.instrument_jit("hot", jax.jit(lambda x: x * 2.0))
    for r in range(2):
        rec.round_start(r)
        f(jnp.ones(4))
        rec.round_end(r)
    rec.close()
    with open(rec.path, "a") as fh:
        fh.write('{"round": 2, "dev')            # crash mid-write
    rows = trend.load_ledger(rec.path)           # torn tail tolerated
    assert len(rows) == 2
    assert trend.validate_ledger(rows) == []
    assert all(isinstance(r["device"], dict) for r in rows)
    assert rows[0]["device"]["compiles"]         # round 0 paid the compile
    assert rows[1]["device"]["compiles"] == []


def test_old_ledger_without_device_section_still_validates():
    rows = [{"round": 0, "phases": {}, "recompiles": 0,
             "wire": {"bytes_out": 0, "bytes_in": 0}}]
    assert trend.validate_ledger(rows) == []


def test_validate_ledger_flags_malformed_device_sections():
    rows = _device_rows(1)
    rows[0]["device"]["memory"] = []             # fabricated placeholder
    problems = trend.validate_ledger(rows)
    assert any("memory" in p for p in problems)
    rows = _device_rows(1)
    del rows[0]["device"]["compiles"]
    assert any("compiles" in p for p in trend.validate_ledger(rows))
    rows = _device_rows(1, mfu=1.57)             # the retracted class
    assert any("1.57" in p and "impossible" in p
               for p in trend.validate_ledger(rows))
    rows = _device_rows(1)
    rows[0]["device"] = None                     # honest absent backend
    assert trend.validate_ledger(rows) == []


# ---------------------------------------------------------------------------
# trend device gates
# ---------------------------------------------------------------------------

def test_trend_device_gate_passes_identical_fails_seeded_compile(tmp_path,
                                                                 capsys):
    base = _write(tmp_path / "base.jsonl", _device_rows())
    same = _write(tmp_path / "same.jsonl", _device_rows())
    slow = _write(tmp_path / "slow.jsonl", _device_rows(compile_s=0.8))
    assert trend.main(["--ledger", same, "--baseline", base]) == 0
    assert "device gate: no compile-time" in capsys.readouterr().out
    assert trend.main(["--ledger", slow, "--baseline", base]) == 1
    assert "device compile regression" in capsys.readouterr().out


def test_trend_device_gate_fails_seeded_mem_regression(tmp_path, capsys):
    base = _write(tmp_path / "base.jsonl", _device_rows(mem=64 << 20))
    fat = _write(tmp_path / "fat.jsonl", _device_rows(mem=128 << 20))
    assert trend.main(["--ledger", fat, "--baseline", base]) == 1
    assert "device memory regression" in capsys.readouterr().out
    # inside the band OR under the absolute floor: not a regression
    near = _write(tmp_path / "near.jsonl", _device_rows(mem=72 << 20))
    assert trend.main(["--ledger", near, "--baseline", base]) == 0
    capsys.readouterr()


def test_trend_device_gate_skips_pre_device_ledgers(tmp_path, capsys):
    old = [{"round": i, "round_s": 0.3, "phases": {"aggregate": 0.2},
            "wire": {"bytes_out": 0, "bytes_in": 0}, "recompiles": 0}
           for i in range(3)]
    base = _write(tmp_path / "base.jsonl", old)
    cur = _write(tmp_path / "cur.jsonl", _device_rows())
    # baseline predates the observatory: vacuous pass, said out loud
    assert trend.main(["--ledger", cur, "--baseline", base]) == 0
    assert "device gate" in capsys.readouterr().out
    assert trend.device_compile_seconds(old) is None
    assert trend.device_mem_peak_bytes(old) is None


# ---------------------------------------------------------------------------
# device-memory headroom SLO
# ---------------------------------------------------------------------------

def test_slo_device_mem_headroom_vacuous_then_breaching():
    reg = _reg()
    ev = SloEvaluator(registry=reg)
    assert "device_mem_utilization_ratio" in DEFAULT_SLOS
    verdict = ev.evaluate(count_breaches=False)
    # gauge absent (device obs off / no allocator limits): vacuous
    assert verdict["device_mem_utilization_ratio"]["value"] is None
    assert verdict["device_mem_utilization_ratio"]["ok"]
    # the observatory exports a real utilization: evaluated + breachable
    reg.gauge("fedml_dev_mem_utilization_ratio").set(0.99)
    verdict = ev.evaluate()
    v = verdict["device_mem_utilization_ratio"]
    assert v["value"] == pytest.approx(0.99) and not v["ok"]
    assert not ev.healthy()
    snap = reg.snapshot()
    assert snap["gauges"]["fedml_slo_device_mem_utilization_ratio"] \
        == pytest.approx(0.99)


# ---------------------------------------------------------------------------
# report renders the device section
# ---------------------------------------------------------------------------

def test_report_renders_device_section(tmp_path):
    from fedml_tpu.obs import report
    led = _write(tmp_path / "perf.jsonl", _device_rows())
    text = report.render_report(str(tmp_path), None, perf_ledger=led)
    assert "device observatory" in text
    assert "train_fn" in text                    # the named compile
    assert "backend cpu" in text
    assert "live_arrays" in text
    # a ledger without device sections renders no device section
    old = _write(tmp_path / "old.jsonl",
                 [{"round": 0, "round_s": 0.1, "phases": {},
                   "wire": {}, "recompiles": 0}])
    assert "device observatory" not in report.render_report(
        str(tmp_path), None, perf_ledger=old)


# ---------------------------------------------------------------------------
# streaming + defended aggregation wear the instrumentation
# ---------------------------------------------------------------------------

def test_stream_aggregator_feeds_compile_ledger():
    import numpy as np
    from fedml_tpu.core.stream_agg import StreamingAggregator
    reg = _reg()
    dev = DeviceRecorder(registry=reg)
    sentry = RecompileSentry(registry=reg)
    template = {"w": np.ones(4, np.float32)}
    agg = StreamingAggregator(template, method="mean", norm_clip=5.0,
                              sentry=sentry, device=dev)
    dev.round_start()
    agg.reset(template)
    agg.fold({"w": np.full(4, 2.0, np.float32)}, 1.0)
    agg.fold({"w": np.full(4, 4.0, np.float32)}, 1.0)
    out = agg.finalize(0)
    # the 4.0 upload sits at diff norm 6 > clip 5: clipped to 1 + 3*5/6
    # = 3.5, so the defended mean is (2 + 3.5) / 2
    assert np.allclose(np.asarray(out["w"]), 2.75)
    section = dev.round_snapshot(round_s=0.1)
    names = {e["fn"] for e in section["compiles"]}
    assert "stream_fold[mean]" in names
    assert "stream_finalize[mean]" in names
    assert section["jit_calls"]["stream_fold[mean]"] == 2
    # the jit-once pin holds straight through the wrapper
    assert agg._cache_size() == 1
    assert sentry.check(0) == {}


def test_defended_aggregate_wrapper_keeps_jit_once_pin():
    import numpy as np
    from fedml_tpu.robust.defense import make_defended_aggregate
    reg = _reg()
    dev = DeviceRecorder(registry=reg)
    sentry = RecompileSentry(registry=reg)
    fn = make_defended_aggregate("mean", norm_clip=5.0, sentry=sentry,
                                 device=dev)
    assert hasattr(fn, "_cache_size")
    g = {"w": np.zeros(4, np.float32)}
    stacked = {"w": np.ones((2, 4), np.float32)}
    dev.round_start()
    for step in range(3):
        fn(g, stacked, np.ones(2, np.float32), step)
    assert fn._cache_size() == 1                 # step traces as a scalar
    section = dev.round_snapshot(round_s=0.1)
    assert [e["fn"] for e in section["compiles"]] \
        == ["defended_aggregate[mean]"]
    assert sentry.check(0) == {}                 # clean: no recompiles


# ---------------------------------------------------------------------------
# telemetry naming audit: no fake *_total counters for measurements
# ---------------------------------------------------------------------------

_TRUE_DEVICE_COUNTERS = {"fedml_dev_compiles_total"}


def test_no_device_measurement_wears_a_fake_total_suffix():
    """PR 8's rule from day one: gauges for non-monotonic device
    measurements wear _bytes/_ratio/_value; the only *_total name the
    observatory registers is the genuinely monotonic compile counter."""
    src = (pathlib.Path(__file__).resolve().parent.parent
           / "fedml_tpu" / "obs" / "device.py").read_text()
    names = set(re.findall(
        r"\.(?:counter|gauge|histogram)\(\s*\n?\s*[\"']([^\"']+)[\"']", src))
    assert names, "source scan found no registrations in obs/device.py"
    fake = {n for n in names if n.endswith("_total")} - _TRUE_DEVICE_COUNTERS
    assert not fake, f"non-monotonic measurement as a *_total counter: {fake}"
    assert "fedml_perf_mfu_ratio" in names
    for n in names:
        assert telemetry.NAME_RE.match(n), n
