"""Tests of the benchmark itself. They run on the CPU
(``python -m pytest benchmark/tests -q``) and are not part of the
repository's tier-1 suite under ``tests/``."""

import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(ROOT), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)
