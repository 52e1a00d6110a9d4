#!/usr/bin/env python
"""Perf regression gate for flight-recorder ledgers.

    python scripts/perf_trend.py --ledger RUN/perf.jsonl \
        --baseline OTHER_RUN/perf.jsonl --health_ledger RUN/health.jsonl

Exit 0 = pass, 1 = named regression / malformed ledger, 2 = bad inputs —
wire it into CI beside the test tiers (scripts/test_fast.sh).
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from fedml_tpu.obs.trend import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
