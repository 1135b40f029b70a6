"""Harness self-tests: ``python -m pytest bench/tests`` from the checkout root,
on the CPU. The chip check is stubbed inside the tests that need it."""

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent

for p in (str(BENCH), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
