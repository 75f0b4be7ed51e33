"""Harness tests run on the CPU, at each configuration's rehearsal size:

    python -m pytest bench/tests
"""

import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
