"""Test-only entry: `rehearse.py` for the tiny indexed grouped-query expert
configuration (``tests/tiny/configs/keye_vl2_30b_a3b.json``: 2 layers, 16
routed experts of which 4 are held, width 64, 4 / 2 heads of 16, an indexer
of 3 heads of 8 that selects 20 keys, blocks of 16 queries), through the
same ``run_cell`` as a cell, on the CPU.

    JAX_PLATFORMS=cpu python benchmark/tests/rehearse_keye.py [seed] [cli args ...]

What it prints is no measurement.  ``rehearse.CELLS`` gains the entry
``keye`` here, at import, so `rehearse.rehearse("keye", seed)` works from a
caller that imported this file.
"""

import json
import sys

from rehearse import CELLS, rehearse   # the file beside this one

CELLS["keye"] = ("keye_vl2_30b_a3b", "silos2_wave2_seq64",
                 "keye_vl2_30b_a3b.silos2_seq64")

if __name__ == "__main__":
    seed = int(sys.argv[1]) if len(sys.argv) > 1 else 2147483659
    print(json.dumps(rehearse("keye", seed, extra=sys.argv[2:])))
