"""Tests of the benchmark itself, on the CPU at small sizes.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))
