"""CPU tests of the benchmark: ``python -m pytest bench/tests -q``."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]
DATA = Path(__file__).resolve().parent / "data"
