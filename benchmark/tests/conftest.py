"""The benchmark's own tests run on the CPU: a rehearsal of the harness, not a
measurement.  Run them with ``python3 -m pytest benchmark/tests -q`` from the
root of the repo; they are not part of the repo's tier-1 suite."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (BENCH, os.path.dirname(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)
