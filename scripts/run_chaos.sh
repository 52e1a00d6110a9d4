#!/usr/bin/env bash
# Full seeded chaos + fault-tolerance matrix (includes the slow cases
# tier-1 skips): 20-seed drop-policy and async chaos sweeps, the
# resilient-transport suite (gRPC receiver restart, MQTT reconnect),
# crash-recovery, the end-to-end convergence-under-chaos runs, and the
# payload-defense suite (corrupt-fault injection exercising the robust
# admission pipeline, defended-vs-undefended convergence under attack,
# combined chaos+adversary runs).
#
# Usage: scripts/run_chaos.sh [extra pytest args]
set -euo pipefail
cd "$(dirname "$0")/.."

# process-kill arm (ISSUE 12): the seeded kill/disk-fault matrix with
# the invariant checker — the pytest suites below put chaos on the WIRE;
# this exercises process death, crash-at-a-point, and disk faults against
# the round journal's recovery contract
env JAX_PLATFORMS=cpu python scripts/soak.py --smoke \
    --out /tmp/soak_smoke.json

# sustained-degradation arm (ISSUE 19): the degrade spine (adaptive
# deadlines, quorum holds, fault attribution) under flapping links, a
# round-bounded partition, and a mid-soak kill+respawn.  The script
# exits 1 on its own gates; its output goes to /tmp.
env JAX_PLATFORMS=cpu python scripts/degrade_soak.py --smoke \
    --out /tmp/bench_degrade_smoke.json

exec env JAX_PLATFORMS=cpu python -m pytest \
    tests/test_chaos.py tests/test_resilient.py tests/test_recovery.py \
    tests/test_robust_round.py tests/test_wire.py \
    tests/test_crash_recovery.py \
    -q -p no:cacheprovider "$@"
