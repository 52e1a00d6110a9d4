#!/usr/bin/env bash
# End-to-end observability demo (ISSUE 2 acceptance): a chaos-enabled
# 2-silo federated run with distributed tracing + telemetry on, then the
# merged run report — asserting every artifact actually materializes:
#
#   * a stitched multi-process Perfetto trace covering
#     broadcast -> train -> upload -> aggregate,
#   * a Prometheus text snapshot with link/chaos counters and
#     failure-detector gauges,
#   * an obs_report per-round timeline,
#   * a perf.jsonl flight-recorder ledger (ISSUE 6) that passes the
#     perf_trend gate honestly and FAILS it on a seeded regression,
#     with the mfu<=1.0 lint green over every committed BENCH artifact,
#   * a device & compile observatory section on every ledger line
#     (ISSUE 10): per-device memory watermarks, a NAMED compile ledger
#     with wall times, and a peak/MFU that are null on this CPU run,
#     from the same table bench.py uses — plus a forced
#     recompile whose sentry verdict names the exact arg shape change,
#     and a seeded compile-time regression failing the trend gate.
#
# Usage: scripts/run_obs_demo.sh [workdir]  (default: a fresh mktemp dir)
set -euo pipefail
cd "$(dirname "$0")/.."

DIR="${1:-$(mktemp -d /tmp/fedml_obs_demo.XXXXXX)}"
RUN="$DIR/run" TRACE="$DIR/trace"
echo "== obs demo: artifacts under $DIR"

env JAX_PLATFORMS=cpu python -m fedml_tpu \
    --algo cross_silo --model lr --dataset mnist \
    --client_num_in_total 4 --client_num_per_round 2 --comm_round 3 \
    --frequency_of_the_test 1 --batch_size 4 --log_stdout false \
    --straggler_policy drop --round_timeout_s 2 --min_silo_frac 0.5 \
    --chaos_drop 0.05 --chaos_delay 0.3 --chaos_dup 0.1 \
    --chaos_reorder 0.1 --chaos_seed 7 \
    --heartbeat_s 0.2 --dead_after_s 5 \
    --run_dir "$RUN" --trace_dir "$TRACE" --telemetry true \
    --perf true --perf_strict true --device_obs true

REPORT="$DIR/report.txt"
env JAX_PLATFORMS=cpu python scripts/obs_report.py \
    --run_dir "$RUN" --trace_dir "$TRACE" \
    --merge_trace "$DIR/trace_merged.json" | tee "$REPORT"

echo "== asserting artifacts"
# the report renders a per-round timeline with every phase stitched in
grep -q "round timelines" "$REPORT"
for phase in broadcast train upload aggregate; do
    grep -q "$phase" "$REPORT"
done
# the Prometheus snapshot carries link counters, chaos fault counters,
# and failure-detector gauges
for series in fedml_comm_send_total fedml_chaos_faults_total \
              fedml_failure_detector_alive_total \
              fedml_round_duration_seconds_count; do
    grep -q "$series" "$RUN/telemetry.prom"
done
# the merged Perfetto trace is non-trivial valid trace_event JSON
python - "$DIR/trace_merged.json" <<'EOF'
import json, sys
events = json.load(open(sys.argv[1]))["traceEvents"]
names = {e["name"] for e in events}
assert {"round", "broadcast", "train", "upload", "aggregate"} <= names, names
print(f"merged trace OK: {len(events)} spans, phases {sorted(names)}")
EOF

echo "== asserting the flight recorder (perf.jsonl + trend gate)"
[ -s "$RUN/perf.jsonl" ]
# the report renders the ledger section
grep -q "perf ledger" "$REPORT"
# honest ledger: schema + recompile gate + mfu lint over every
# committed BENCH artifact all green (exit 0)
env JAX_PLATFORMS=cpu python scripts/perf_trend.py \
    --ledger "$RUN/perf.jsonl" --baseline "$RUN/perf.jsonl" \
    --lint_mfu 'BENCH_*.json' SCALE_PROOF.json
# seeded +60% regression on the aggregate phase MUST fail the gate
# (non-zero exit, naming the phase) — proving the gate can actually
# catch what it exists to catch
python - "$RUN/perf.jsonl" "$DIR/perf_regressed.jsonl" <<'EOF'
import json, sys
rows = [json.loads(l) for l in open(sys.argv[1]) if l.strip()]
for r in rows:
    for k in r.get("phases", {}):
        if k in ("aggregate", "defended_aggregate", "broadcast_serialize"):
            r["phases"][k] = r["phases"][k] * 1.6 + 0.05
with open(sys.argv[2], "w") as f:
    f.writelines(json.dumps(r) + "\n" for r in rows)
EOF
if env JAX_PLATFORMS=cpu python scripts/perf_trend.py \
    --ledger "$DIR/perf_regressed.jsonl" --baseline "$RUN/perf.jsonl" \
    > "$DIR/trend_fail.txt"; then
    echo "ERROR: trend gate passed a seeded +60% regression"; exit 1
fi
grep -q "phase regression" "$DIR/trend_fail.txt"
echo "trend gate OK: honest ledger passes, seeded regression fails"

echo "== asserting the device & compile observatory (ISSUE 10)"
# every ledger line carries a device section: per-device memory
# watermarks (CPU-honest live_arrays source here), at least one NAMED
# compile-ledger entry with wall time, and — this being a CPU run — a
# null peak and MFU with the reason, from the SAME table bench.py
# delegates to
env JAX_PLATFORMS=cpu python - "$RUN/perf.jsonl" <<'EOF'
import json, sys
import bench
from fedml_tpu.obs.device import (MFU_PROVENANCE, compiled_flops,
                                  peak_tflops_for_device)
assert bench._peak_for_device is peak_tflops_for_device
assert bench._compiled_flops is compiled_flops
rows = [json.loads(l) for l in open(sys.argv[1]) if l.strip()]
assert rows, "no ledger lines"
compiles = []
for r in rows:
    d = r["device"]
    mem = d["memory"]
    assert mem is None or (mem and all(
        "bytes_in_use" in e and "source" in e for e in mem)), mem
    compiles += d["compiles"]
    assert d["mfu"] is None and d["peak_tflops"] is None, d
    assert "cpu backend" in d["peak_source"], d["peak_source"]
    assert d["mfu_provenance"] == MFU_PROVENANCE
assert compiles, "no named compile-ledger entry in the whole run"
assert all(e["fn"] and e["wall_s"] > 0 for e in compiles), compiles
names = sorted({e["fn"] for e in compiles})
print(f"device section OK: {len(rows)} rounds, compiles {names}, "
      f"mem source "
      f"{sorted({e['source'] for r in rows for e in r['device']['memory'] or []})}")
EOF
# the report renders the device observatory table
grep -q "device observatory" "$REPORT"
# a forced recompile (a REAL re-jit on a changed arg shape) fires a
# sentry verdict that NAMES the exact shape change
env JAX_PLATFORMS=cpu python - "$DIR/recompile_probe.jsonl" <<'EOF'
import sys
import jax, jax.numpy as jnp
from fedml_tpu.obs import telemetry
from fedml_tpu.obs.device import DeviceRecorder
from fedml_tpu.obs.perf import PerfRecorder, RecompileError
reg = telemetry.TelemetryRegistry()
rec = PerfRecorder(sys.argv[1], registry=reg, strict_recompiles=True,
                   device=DeviceRecorder(registry=reg))
f = rec.instrument_jit("hot_fn", jax.jit(lambda x: x * 2.0))
rec.round_start(0); f(jnp.ones((4,), jnp.float32)); rec.round_end(0)
rec.round_start(1); f(jnp.ones((8,), jnp.float32))
try:
    rec.round_end(1)
    raise SystemExit("ERROR: sentry did not fire on a forced re-jit")
except RecompileError as e:
    assert "float32[4] -> float32[8]" in str(e), str(e)
    print(f"sentry names the shape change: {e}")
finally:
    rec.close()
EOF
# a seeded 4x compile-time regression MUST fail the (device) trend gate
python - "$RUN/perf.jsonl" "$DIR/perf_compile_regressed.jsonl" <<'EOF'
import json, sys
rows = [json.loads(l) for l in open(sys.argv[1]) if l.strip()]
for r in rows:
    for e in r["device"]["compiles"]:
        e["wall_s"] = e["wall_s"] * 4.0 + 0.2
with open(sys.argv[2], "w") as f:
    f.writelines(json.dumps(r) + "\n" for r in rows)
EOF
if env JAX_PLATFORMS=cpu python scripts/perf_trend.py \
    --ledger "$DIR/perf_compile_regressed.jsonl" \
    --baseline "$RUN/perf.jsonl" > "$DIR/device_fail.txt"; then
    echo "ERROR: trend gate passed a seeded 4x compile regression"; exit 1
fi
grep -q "device compile regression" "$DIR/device_fail.txt"
echo "device gate OK: honest ledger passes, seeded compile regression fails"

echo "== streaming aggregation: one --agg_mode stream round, fold phase"
# the O(1)-memory fold path (ISSUE 7): uploads fold at arrival, so the
# ledger gains a 'fold' phase and never records a 'staging' one — and
# the same trend gate covers the new ledger shape
STREAM_RUN="$DIR/stream_run"
env JAX_PLATFORMS=cpu python -m fedml_tpu \
    --algo cross_silo --model lr --dataset mnist \
    --client_num_in_total 4 --client_num_per_round 2 --comm_round 3 \
    --frequency_of_the_test 1 --batch_size 4 --log_stdout false \
    --agg_mode stream --norm_clip 5.0 \
    --run_dir "$STREAM_RUN" --perf true --perf_strict true \
    --device_obs true
python - "$STREAM_RUN/perf.jsonl" <<'EOF'
import json, sys
rows = [json.loads(l) for l in open(sys.argv[1]) if l.strip()]
assert rows, "stream run wrote no ledger lines"
for r in rows:
    assert r["phases"].get("fold", 0) > 0, \
        f"round {r['round']} ledger is missing the fold phase: {r['phases']}"
    assert "staging" not in r["phases"], \
        "stream mode must not stage a cohort buffer"
# the device observatory covers the stream hot path too: the per-arrival
# fold jit compiles exactly once, named in round 0's compile ledger
fold_compiles = [e["fn"] for r in rows for e in r["device"]["compiles"]
                 if e["fn"].startswith("stream_fold")]
assert fold_compiles == ["stream_fold[mean]"], fold_compiles
print(f"fold phase present in all {len(rows)} stream-round ledger lines; "
      f"stream fold compiled once, named in the device ledger")
EOF
env JAX_PLATFORMS=cpu python scripts/perf_trend.py \
    --ledger "$STREAM_RUN/perf.jsonl" --baseline "$STREAM_RUN/perf.jsonl"
echo "stream ledger OK: fold phase recorded, trend gate green"

# sharded-spine smoke (fedml_tpu/shard_spine): per-device memory ~1/S,
# S=1 bit-parity, fused-finalize kernel named in the compile ledger
# with a non-null MFU, 0 recompiles under strict — the full gates of
# scripts/shard_bench.py at CI size (output to /tmp so the committed
# BENCH_shard.json keeps full-bench numbers)
env JAX_PLATFORMS=cpu python scripts/shard_bench.py --smoke
echo "shard spine smoke OK: per-device scaling + fused finalize gates green"

echo "== asserting the critical-path observatory (ISSUE 17)"
# every ledger line of the chaos run carries a critical_path record
# naming the round's binding constraint, with the attribution
# partitioning the round's wall clock — and the report renders it
python - "$RUN/perf.jsonl" <<'EOF'
import json, sys
from fedml_tpu.obs import critical_path as cpath
rows = [json.loads(l) for l in open(sys.argv[1]) if l.strip()]
assert rows, "no ledger lines"
for r in rows:
    cp = r["critical_path"]
    assert cpath.validate_record(cp, path=f"round {r['round']}") == []
    assert cp["coverage"] >= 0.95, cp
bindings = sorted({r["critical_path"]["binding"] for r in rows})
print(f"critical_path on all {len(rows)} ledger lines; bindings {bindings}")
EOF
grep -q "critical path" "$REPORT"
grep -q "binding constraint" "$REPORT"
# ingest gauges land beside the rest of the telemetry snapshot
grep -q "fedml_ingest_uploads_total" "$RUN/telemetry.prom"
# full cost-contract smoke: four traffic arms + the disabled-mode pin
# (output to /tmp so the committed BENCH_ingest.json keeps full-bench
# numbers), then the committed artifact through the trend gate
env JAX_PLATFORMS=cpu python scripts/ingest_bench.py --smoke
env JAX_PLATFORMS=cpu python scripts/perf_trend.py \
    --ingest_bench BENCH_ingest.json
echo "ingest smoke OK: critical-path records, gauges, and cost gates green"

echo "== asserting the server-optimizer spine (ISSUE 18)"
# structural pipe-cleaner for the convergence contract: both workloads,
# plain + optimizer arms, controller decisions on every ledger line,
# zero recompiles under --perf_strict (output to /tmp — the committed
# BENCH_opt.json keeps full-bench numbers), then the committed
# artifact through the trend gate, which re-derives the rounds-to-
# target and final-accuracy claims from the committed curves
env JAX_PLATFORMS=cpu python scripts/opt_bench.py --smoke
env JAX_PLATFORMS=cpu python scripts/perf_trend.py \
    --opt_bench BENCH_opt.json
echo "opt smoke OK: server-optimizer arms, pacing decisions, and convergence gates green"
echo "== obs demo OK ($DIR)"

echo "== asserting the zero-copy pipelined ingest (ISSUE 20)"
# pipelined vs inline twin at demo size: identical seeds and arrival
# order, so the per-round global_crc sequences must be bit-identical;
# the pipelined ledger must carry exactly one arena + one screen
# compile entry (re-staging never recompiles), and the pipeline gauges
# must land in the telemetry snapshot
ING_INLINE=$(mktemp -d /tmp/obs_ing_inline.XXXXXX)
ING_PIPED=$(mktemp -d /tmp/obs_ing_piped.XXXXXX)
for mode in "false:$ING_INLINE" "true:$ING_PIPED"; do
  env JAX_PLATFORMS=cpu python -m fedml_tpu \
      --model lr --dataset mnist --algo cross_silo --agg_mode stream \
      --comm_round 3 --client_num_per_round 4 --client_num_in_total 8 \
      --epochs 1 --batch_size 8 --admission on \
      --perf true --perf_strict true --telemetry true \
      --ingest_pipeline "${mode%%:*}" --run_dir "${mode#*:}" \
      --log_stdout false
done
python - "$ING_INLINE/perf.jsonl" "$ING_PIPED/perf.jsonl" <<'EOF2'
import json, sys
def rows(p):
    return [json.loads(l) for l in open(p) if l.strip()]
inline, piped = rows(sys.argv[1]), rows(sys.argv[2])
a = [(r["round"], r["global_crc"]) for r in inline]
b = [(r["round"], r["global_crc"]) for r in piped]
assert a == b and a, f"pipelined != inline: {a} vs {b}"
sizes = piped[-1]["jit_cache_sizes"]
assert sizes.get("ingest_arena") == 1 and sizes.get("ingest_screen") == 1, \
    sizes
assert all(r["recompiles"] == 0 for r in piped[1:]), piped
print(f"pipelined ingest bit-equal over {len(a)} rounds "
      f"(crc {a[-1][1]}); one arena + one screen compile, 0 recompiles")
EOF2
grep -q "fedml_ingest_enqueued_total" "$ING_PIPED/telemetry.prom"
grep -q "fedml_ingest_queue_depth_value" "$ING_PIPED/telemetry.prom"
echo "pipelined ingest smoke OK: bit-parity, compile pins, gauges green"
