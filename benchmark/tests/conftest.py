"""Run by hand: ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests``.
Not collected by tier-1 (which runs ``tests/`` only)."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
