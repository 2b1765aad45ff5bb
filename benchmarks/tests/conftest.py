"""Tests of the benchmark's own yardstick. Run by hand:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

Tier-1 is ``tests/`` and does not collect this directory."""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH)
for p in (CHECKOUT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
