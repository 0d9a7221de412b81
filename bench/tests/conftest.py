"""The benchmark's own tests, on the CPU: ``python -m pytest bench/tests``
from the repository root. They import the benchmark's modules from
``bench/`` and the program from ``src/``."""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH), "src"))
