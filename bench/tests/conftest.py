"""The benchmark's tests run on the CPU at toy sizes; they import the
benchmark as ``bench`` and the program from ``src``."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
