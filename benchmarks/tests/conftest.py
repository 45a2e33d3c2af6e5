"""The benchmark's own tests run on the CPU backend: pin it before jax
is first imported, and put the benchmark and the program on the path."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (_BENCH, os.path.dirname(_BENCH)):
    if path not in sys.path:
        sys.path.insert(0, path)
