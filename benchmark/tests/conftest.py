"""`pytest benchmark/tests` from the root of the repo. Not part of tier-1,
which collects `tests/` only. Everything runs on the CPU backend."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (os.path.dirname(BENCH_DIR), BENCH_DIR):
    if path not in sys.path:
        sys.path.insert(0, path)
