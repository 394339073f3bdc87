"""Tests of the benchmark itself: CPU, no chip, not part of the repo's
tier-1 run.  `python -m pytest benchmark/tests -q` from the checkout's root.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
