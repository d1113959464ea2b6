"""Tests of the benchmark's own code: pure Python and small JAX on the CPU.

They import the harness from this directory and the program from
``<checkout>/src``, as ``bench/run.py`` does.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
