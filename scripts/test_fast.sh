#!/usr/bin/env bash
# One-command non-slow test tier for the driver.
#
# pytest-xdist shards across workers; --dist loadfile keeps each test file
# on one worker (transport tests bind fixed ports and share module
# fixtures, so file granularity avoids cross-worker collisions).  On a
# multi-core box this lands well under 10 min; on a 1-core container it
# degrades to roughly sequential speed — xdist cannot beat nproc.
#
#   WORKERS=4 scripts/test_fast.sh          # explicit worker count
#   scripts/test_fast.sh -k compress        # extra pytest args pass through
#
# The fast tier covers every non-slow test file under tests/, including
# the serving layer (tests/test_serve.py — registry hot-swap, batching,
# shedding, HTTP frontend); sustained-load serve cases are @slow.
set -euo pipefail
cd "$(dirname "$0")/.."
[ -f tests/test_serve.py ]         # fast tier must include the serve suite
[ -f tests/test_robust_round.py ]  # ...and the payload-defense suite
[ -f tests/test_wire.py ]          # ...and the encode-once wire suite
[ -f tests/test_perf_obs.py ]      # ...and the flight-recorder suite
[ -f tests/test_stream_agg.py ]    # ...and the streaming-aggregation suite
[ -f tests/test_health_obs.py ]    # ...and the health-observatory suite
[ -f tests/test_device_obs.py ]    # ...and the device-observatory suite
[ -f tests/test_secagg_live.py ]   # ...and the live secure-aggregation suite
[ -f tests/test_crash_recovery.py ]  # ...and the crash-consistency suite
[ -f tests/test_cross_device.py ]  # ...and the cross-device wave suite
[ -f tests/test_shard_spine.py ]   # ...and the sharded-spine suite
# the interpret-mode kernel parity suites guard the Pallas kernels the
# sharded spine promotes to the live path — they must ride the fast
# tier (neither is @slow; this asserts they exist and stay collected)
[ -f tests/test_pallas_agg.py ]
[ -f tests/test_pallas_mask.py ]
grep -q "fused=True" tests/test_shard_spine.py  # fused-finalize parity too
# ISSUE 15 production serving: the multi-worker pool suite and the
# continuous-batching decode suite must ride the fast tier
[ -f tests/test_serve_pool.py ]
[ -f tests/test_decode.py ]
# ISSUE 16 release gate: the canary promote/rollback suite must ride
# the fast tier (registry states, verdict matrix, crash consistency,
# poisoned-round containment)
[ -f tests/test_release.py ]
# ISSUE 17 critical-path observatory: attribution sweep, binding
# constraints, disabled-mode zero-allocation pin
[ -f tests/test_critical_path.py ]
# ISSUE 18 server-optimizer spine: seam parity vs optax/fedac math,
# plain bit-identity, sharded state round-trip, crash kill->resume with
# optimizer slots, controller determinism, config-gate matrix
[ -f tests/test_server_opt.py ]
# ISSUE 20 zero-copy pipelined ingest: arena fused-screen numeric pin,
# per-shard order preservation, backpressure dead-letter attribution,
# pipelined==inline bit-parity (replicated/sharded/secagg), the
# kill-mid-queue journal composition, and the config-gate matrix
[ -f tests/test_ingest_pipeline.py ]
# ISSUE 19 sustained-degradation spine: adaptive deadline determinism,
# quorum/partition verdict matrix, the payload-only strike invariant,
# dead-letter attribution, and the resume-path straggler-timer audit
[ -f tests/test_degrade.py ]
exec python -m pytest tests/ -m "not slow" -q \
  -n "${WORKERS:-auto}" --dist loadfile "$@"
