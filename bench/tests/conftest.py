"""The benchmark's own tests, on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest bench/tests -q
"""

import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))
