"""Tests of the yardstick itself. Run from the repository's root:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)  # as ``python3 benchmark/run.py`` has it

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
