"""The benchmark's own tests run on the CPU backend (never tier-1:
``pytest benchmark/tests``). Set before JAX is imported."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (_BENCH, os.path.dirname(_BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)
