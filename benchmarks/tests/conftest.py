"""Tests of the benchmark's own yardstick. Run by hand on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q

They are not part of the repo's tier-1 tests (those collect ``tests/``).
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
