"""Tests of the benchmark's own arithmetic. Run by hand on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

They are not under ``tests/``, so the repository's tier-1 run does not
collect them.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
