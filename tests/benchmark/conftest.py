"""The benchmark's tests import the benchmark as a package from the root of
the checkout (tier-1 runs `python -m pytest tests/` from there)."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
