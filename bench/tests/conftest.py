"""The benchmark's own tests run on the CPU (``JAX_PLATFORMS=cpu``, set
before JAX is imported), in Pallas interpret mode."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
