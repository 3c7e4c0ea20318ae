"""Tests of the chip benchmark's own code, run by path on the CPU:

  JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests

They sit outside the repository's ``testpaths``; the harness needs a TPU
for a real run, so what needs the program runs through ``cell.run`` with
the chip check skipped, at the tiny sizes of ``tiny/``."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = Path(__file__).resolve().parent
for p in (HERE, HERE.parent):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
