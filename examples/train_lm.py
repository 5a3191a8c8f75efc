"""End-to-end training driver example: train a ~100M-param minicpm-family
model for a few hundred steps on CPU with checkpointing.

Run:  PYTHONPATH=src python examples/train_lm.py
(thin wrapper over python -m repro.launch.train; see that module for flags)
"""

import sys

from repro.jaxenv import init_compile_cache
from repro.launch.train import main

if __name__ == "__main__":
    init_compile_cache()
    sys.argv = [sys.argv[0], "--arch", "minicpm-2b", "--scale", "100m",
                "--steps", "200", "--batch", "4", "--seq", "256",
                "--ckpt-dir", "/tmp/repro_ckpt"] + sys.argv[1:]
    main()
