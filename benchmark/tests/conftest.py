"""Tests of the benchmark's own arithmetic and of ``correct``. Run by
hand and in the CPU rehearsal, not part of the repo's tier-1 lane:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

