"""The benchmark's own CPU tests: ``python3 -m pytest benchmarks/pulse/tests``
with ``JAX_PLATFORMS=cpu``.  Puts the harness (``benchmarks/pulse`` and its
``lib``) and the program (``src``) on the import path."""

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH / "lib", BENCH.parents[1] / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
