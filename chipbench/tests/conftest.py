"""The benchmark's own CPU tests: ``python -m pytest chipbench/tests``
from the repository root.  The program is imported from ``src/``."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
