"""The benchmark's CPU tests import the harness (``bench/``), its tools
(``bench/tools/``) and the program (``src/``) directly."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for path in (BENCH, BENCH / "tools", BENCH.parent / "src"):
    if str(path) not in sys.path:
        sys.path.insert(0, str(path))
