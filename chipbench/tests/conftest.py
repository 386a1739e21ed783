"""Self-tests of the benchmark's own arithmetic. Run by hand:

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q

They are not part of tier-1 (whose command collects ``tests/`` only)."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT, os.path.join(ROOT, "examples")):
    if p not in sys.path:
        sys.path.insert(0, p)
