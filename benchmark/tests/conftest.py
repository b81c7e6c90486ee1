"""Tests of the yardstick itself.  Not tier-1; run by hand on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

No file here touches a TPU, or describes one, at import.
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.dirname(_BENCH), _BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
