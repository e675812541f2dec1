"""The benchmark's own tests run on the CPU at a test size:

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
