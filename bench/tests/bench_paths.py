"""Puts the benchmark's harness (``bench/``) and the program (``src/``) on
``sys.path`` as ``bench/run.py`` does.  A module of its own, not a
``conftest.py``: the repository's tests import their ``conftest`` by name."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
