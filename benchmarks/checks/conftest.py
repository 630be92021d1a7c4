"""The benchmark's own checks run on the CPU, by hand:
``python3 -m pytest benchmarks/checks -q`` from the root of the repo."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__),
                                                os.pardir, os.pardir)))
