"""Serving example: MBA+SAM chip plan for the full arch + continuous-batching
engine on a runnable-scale model.

Run:  PYTHONPATH=src python examples/serve_lm.py
"""

import sys

from repro.jaxenv import init_compile_cache
from repro.launch.serve import main

if __name__ == "__main__":
    init_compile_cache()
    sys.argv = [sys.argv[0], "--arch", "qwen2.5-32b", "--scale", "10m",
                "--requests", "8", "--max-new", "12"] + sys.argv[1:]
    main()
