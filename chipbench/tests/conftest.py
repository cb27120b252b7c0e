"""The tests under chipbench/ are CPU rehearsals of the yardstick: they
never take a chip, wherever they run."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
