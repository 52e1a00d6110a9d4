"""Unit tests for the host-side bench.py plumbing: peak resolution (no
default for an unknown chip), the no-TPU-means-fail contract, and spread
statistics.  (The honest twin-FLOPs machinery is exercised end-to-end by
the explicit-CPU bench path; these tests pin the logic that never
touches an accelerator.)"""

import time

import numpy as np
import pytest

import bench


class _FakeDev:
    def __init__(self, kind):
        self.device_kind = kind
        self.platform = "tpu"


@pytest.mark.parametrize("kind,peak", [
    ("TPU v5e", 197.0), ("TPU v5 lite", 197.0), ("TPU v5p chip", 459.0),
    ("TPU v6e", 918.0), ("trillium", 918.0), ("TPU v4", 275.0),
    ("TPU v3", 123.0),
])
def test_peak_resolution_by_device_kind(kind, peak, monkeypatch):
    monkeypatch.delenv("BENCH_PEAK_TFLOPS", raising=False)
    assert bench._peak_for_device(_FakeDev(kind)) == peak


def test_unknown_accelerator_kind_is_an_error(monkeypatch):
    """No default peak: a chip the table does not know raises, it does
    not quietly become a v5e."""
    monkeypatch.delenv("BENCH_PEAK_TFLOPS", raising=False)
    with pytest.raises(ValueError, match="mystery accelerator"):
        bench._peak_for_device(_FakeDev("mystery accelerator"))


def test_no_tpu_and_no_bench_platform_fails_with_nothing_measured(
        monkeypatch, capsys):
    """On a host where jax finds no TPU (this one), `python bench.py`
    without BENCH_PLATFORM exits non-zero, names the platform it found,
    and prints NO result line — no skipped marker, no carried number."""
    monkeypatch.delenv("BENCH_PLATFORM", raising=False)
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code not in (0, None)
    assert "no TPU" in str(exc.value.code)
    assert "'cpu'" in str(exc.value.code)
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("name", [
    "_emit_skipped", "_emit_stalled", "_quarantine", "_backend_alive",
    "promote_partial", "_beat", "_checkpoint_partial", "_WATCH",
    "_start_watchdog", "_accelerator_backend_live"])
def test_no_fallback_machinery_left(name):
    """bench.py measures on the chip or fails: nothing that survives a
    missing backend by carrying, skipping or promoting is left."""
    assert not hasattr(bench, name)


def test_no_failure_is_turned_into_skipped():
    import inspect
    src = inspect.getsource(bench)
    assert "cpu_fallback" not in src
    assert "except Exception as e:  # pallas" not in src
    # the only "skipped" left labels the explicit-CPU run's resnet56 cell
    assert src.count('"skipped"') == 1


def test_peak_env_override_wins(monkeypatch):
    monkeypatch.setenv("BENCH_PEAK_TFLOPS", "123.5")
    assert bench._peak_for_device(_FakeDev("TPU v6e")) == 123.5


def test_round_spread_statistics(monkeypatch):
    times = iter([0.1, 0.3, 0.2, 0.5, 0.2])
    clock = {"t": 0.0}
    monkeypatch.setattr(bench, "_now", lambda: clock["t"])

    def run_round(params, i):
        clock["t"] += next(times)
        return params, None

    stats = bench._round_spread(run_round, np.zeros(1), 5)
    assert stats["n"] == 5
    assert stats["median"] == pytest.approx(0.2)
    assert stats["mean"] == pytest.approx(0.26)
    assert stats["max"] == pytest.approx(0.5)
    assert stats["p10"] <= stats["median"] <= stats["p90"] <= stats["max"]


def test_mfu_uses_module_peak(monkeypatch):
    monkeypatch.setattr(bench, "PEAK_TFLOPS", 100.0)
    # 1e14 FLOPs in 2 s = 5e13 FLOP/s = 50% of a 100-TFLOPs peak
    assert bench._mfu(1e14, 2.0) == pytest.approx(0.5)
    assert bench._mfu(0.0, 2.0) == 0.0
    assert bench._mfu(1e14, 0.0) == 0.0


def test_auto_group_and_block_helpers():
    from fedml_tpu.models.moe import _auto_group
    assert _auto_group(1024) == 512     # largest divisor <= 512
    assert _auto_group(96) == 96        # <= target: itself (loop hit)
    assert _auto_group(1031) == 1031    # prime > target: n_tok fallback
    from fedml_tpu.models.transformer import _auto_block
    assert _auto_block(2048, threshold=1024) == 512
    assert _auto_block(512, threshold=1024) is None   # dense is fine
    assert _auto_block(1031, threshold=1024) is None  # prime, no divisor


def test_max_mfu_scans_configs_and_scaling():
    assert bench._max_mfu({}) == 0.0
    assert bench._max_mfu({
        "configs": {"a": {"mfu": 0.3}, "b": {"round_s_xla": 1.0}},
        "cohort_scaling": {"64": {"mfu": 0.9}, "128": {"mfu": 1.57}},
    }) == pytest.approx(1.57)


def test_timing_sanity_on_cpu_backend():
    """The gate itself, end-to-end on the CPU backend: a synchronous
    backend must pass all three checks (linearity, sync, checksum) and
    report a finite verified throughput.  Retried like main() does — but
    each retry GROWS the workload: under a full pytest run this 1-core
    container's background load makes the smallest (n=512, iters=4)
    measurement overhead-dominated, which no number of same-size retries
    fixes.  More work per timed loop shrinks the overhead fraction, so
    the linearity ratio converges to 2 exactly when the timer is honest —
    and a wall-clock flake here would erode trust in the gate it pins."""
    out = bench.bench_timing_sanity(n=512, iters=4)
    for settle_s, (n, iters) in ((1, (512, 8)), (2, (768, 8)),
                                 (4, (1024, 8))):
        if out["trusted"]:
            break
        # let straggling daemon threads from earlier suites drain: the
        # linearity ratio is only meaningful when both sides of the
        # t(2R)/t(R) comparison see the same background load
        time.sleep(settle_s)
        out = bench.bench_timing_sanity(n=n, iters=iters)
    assert out["trusted"], out["failures"]
    assert np.isfinite(out["checksum"])
    assert out["tflops_readback_verified"] > 0


def test_agg_kernels_flagship_wiring_toy_size():
    """The flagship Pallas-vs-XLA rows must be wired correctly before a
    chip run reaches them: run the full function on CPU (interpret mode)
    at toy size and check the row contract."""
    from fedml_tpu.models import LogisticRegression
    from fedml_tpu.trainer.workload import ClassificationWorkload
    wl = ClassificationWorkload(LogisticRegression(16, 4), num_classes=4)
    rows = bench.bench_agg_kernels_flagship(
        iters=2, clients=4, workload=wl, sample_shape=(4, 16))
    assert set(rows) == {"robust_agg_r56_f32", "robust_agg_r56_bf16",
                         "secagg_mask_r56_f32"}
    for name, r in rows.items():
        assert r["xla_ms"] > 0 and r["pallas_ms"] > 0
        assert r["speedup"] == pytest.approx(r["xla_ms"] / r["pallas_ms"])