"""Test-only entry: `rehearse.py` for the tiny latent-attention expert
configuration (``tests/tiny/configs/glm47_flash.json``: 2 layers, 8 routed
experts of which 2 are held, width 64, the multi-token prediction module
on), through the same ``run_cell`` as a cell, on the CPU.

    JAX_PLATFORMS=cpu python benchmark/tests/rehearse_glm47.py [seed] [cli args ...]

What it prints is no measurement.  ``rehearse.CELLS`` gains the entry
``glm47`` here, at import, so `rehearse.rehearse("glm47", seed)` works
from a caller that imported this file.
"""

import json
import sys

from rehearse import CELLS, rehearse   # the file beside this one

CELLS["glm47"] = ("glm47_flash", "silos2_wave2_seq64",
                  "glm47_flash.silos2_seq64")

if __name__ == "__main__":
    seed = int(sys.argv[1]) if len(sys.argv) > 1 else 2147483659
    print(json.dumps(rehearse("glm47", seed, extra=sys.argv[2:])))
