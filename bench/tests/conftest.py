"""The benchmark's own tests: CPU only, small sizes.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
