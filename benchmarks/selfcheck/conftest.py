"""Checks of the benchmark's own arithmetic, on the CPU, without the program:
``python -m pytest benchmarks/selfcheck -q``. Not part of tier-1."""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
