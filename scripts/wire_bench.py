#!/usr/bin/env python
"""CLI for the wire-path microbench (fedml_tpu/utils/wirebench.py).

Measures, on the CPU container (honest host wall clock, no accelerator):

  a. broadcast serialize time vs cohort size — per-silo encode (seed
     path) vs encode-once ``send_many``;
  b. encode/decode copies per leaf (codec spy counts, not estimates);
  c. end-to-end round time of a real federation over the codec-roundtrip
     hub, seed path vs encode-once + incremental staging (plus a chaos
     arm with dup/reorder/corrupt faults and the admission screen armed).

Writes BENCH_wire.json and prints one summary JSON line.

  python scripts/wire_bench.py              # full: ~10MB model, N=1..8
  python scripts/wire_bench.py --smoke      # CI/chaos-suite sized
  python scripts/wire_bench.py --out /tmp/w.json
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small model / short run (rides run_chaos.sh)")
    ap.add_argument("--out", default=None,
                    help="details artifact path ('' to skip writing); "
                         "default BENCH_wire.json for full runs, a /tmp "
                         "path for --smoke so CI-sized numbers can never "
                         "clobber a full run's artifact")
    args = ap.parse_args()
    if args.out is None:
        args.out = ("/tmp/BENCH_wire_smoke.json" if args.smoke
                    else "BENCH_wire.json")

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from fedml_tpu.utils.wirebench import run

    details = run(out_path=args.out or None, smoke=args.smoke)
    ser = details["broadcast_serialize"]
    e2e = details["round_e2e"]
    n_max = str(max(ser["cohort_sizes"]))
    line = {
        "metric": "wire_encode_once_speedup_n%s" % n_max,
        "value": round(ser["speedup_at_n%s" % n_max], 2),
        "unit": "x",
        "backend": details["backend"],
        "model_mb": details["model_mb"],
        "round_speedup_e2e": round(e2e["round_speedup"], 3),
        "results_identical": e2e["results_identical"],
        "encode_copies_per_leaf":
            details["codec_copies"]["encode_copies_per_leaf"],
        "decode_leaves_sharing_frame_memory":
            details["codec_copies"]["decode_leaves_sharing_frame_memory"],
        "chaos_rounds_completed":
            e2e["encode_once_under_chaos"]["rounds"],
    }
    print(json.dumps(line), flush=True)
    # acceptance gates.  Functional (always hard): the two e2e paths
    # agree bit-for-bit and the chaos arm completed its rounds.  Timing
    # (hard on FULL runs only): one shared encode beats N=8 per-silo
    # encodes by >= 4x — on a --smoke run inside a loaded CI container a
    # wall-clock ratio dipping under the bar is a perf flake, not a
    # functional regression, and must not fail the chaos suite.
    # (chaos-arm completion is asserted inside bench_round_e2e itself —
    # an incomplete federation raises before we get here)
    ok = e2e["results_identical"]
    timing_ok = line["value"] >= 4.0
    if not timing_ok:
        sys.stderr.write("wire_bench: encode-once speedup "
                         f"{line['value']}x below the 4x bar"
                         + (" (smoke: advisory only)\n" if args.smoke
                            else " — acceptance gate FAILED\n"))
    if not ok:
        sys.stderr.write("wire_bench: FUNCTIONAL gate failed "
                         f"(identical={e2e['results_identical']})\n")
    return 0 if ok and (timing_ok or args.smoke) else 1


if __name__ == "__main__":
    sys.exit(main())
