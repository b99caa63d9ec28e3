"""The harness's own tests: run by hand, on the CPU, in about two minutes:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q

They are not under tests/ and tier-1 does not collect them."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
