"""The benchmark's own tests (not part of the repository's tier-1 suite):

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
