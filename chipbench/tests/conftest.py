import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
for p in (str(BENCH_DIR), str(BENCH_DIR.parent / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
