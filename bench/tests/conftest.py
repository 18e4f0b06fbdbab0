"""CPU tests of the benchmark harness; run them by path:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
