"""The benchmark's own tests, run by hand:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

They are not part of the repo's tier-1 suite (tests/). The rehearsal and
control tests compile at the 64-node size on the CPU and take minutes."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
