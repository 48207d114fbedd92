import os
import sys
from pathlib import Path

# the benchmark's modules and the program, as bench/run.py finds them
BENCH = Path(__file__).resolve().parents[1]
os.environ.setdefault("JAX_PLATFORMS", "cpu")
for p in (str(BENCH.parent / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)
