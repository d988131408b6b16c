"""Tests of the yardstick: CPU only, no chip, no topology call at import.

    python -m pytest benchmarks/tests -q
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
