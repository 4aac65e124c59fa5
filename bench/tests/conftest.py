"""CPU tests of the benchmark's own code: run by hand with
``JAX_PLATFORMS=cpu python -m pytest bench/tests -q`` from the checkout root.
They are not part of the repository's tier-1 run."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for path in (ROOT, BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)
