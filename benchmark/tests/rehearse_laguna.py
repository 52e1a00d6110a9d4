"""Test-only entry: `rehearse.py` for the tiny window and full grouped-query
expert configuration (``tests/tiny/configs/laguna_xs2.json``: 5 layers, full
+ dense, three window layers and a full one with experts, 16 routed experts
of which 4 are held, width 64, 4 or 6 / 2 heads of 16, a window of 12 keys,
blocks of 16 queries), through the same ``run_cell`` as a cell, on the CPU.

    JAX_PLATFORMS=cpu python benchmark/tests/rehearse_laguna.py [seed] [cli args ...]

What it prints is no measurement.  ``rehearse.CELLS`` gains the entry
``laguna`` here, at import, so `rehearse.rehearse("laguna", seed)` works
from a caller that imported this file.
"""

import json
import sys

from rehearse import CELLS, rehearse   # the file beside this one

CELLS["laguna"] = ("laguna_xs2", "silos2_wave2_seq64",
                   "laguna_xs2.silos2_seq64")

if __name__ == "__main__":
    seed = int(sys.argv[1]) if len(sys.argv) > 1 else 2147483659
    print(json.dumps(rehearse("laguna", seed, extra=sys.argv[2:])))
