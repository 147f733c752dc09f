"""Tests of the benchmark's own code: run by hand and in the CPU rehearsal,

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q -p no:cacheprovider

outside ``tests/`` (tier-1 does not collect them)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
