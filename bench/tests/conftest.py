"""Put the program (``src/``) and the repository root on ``sys.path``, so
the benchmark's tests import ``repro`` and ``bench`` from any working
directory."""
import sys
from pathlib import Path

_ROOT = Path(__file__).resolve().parents[2]
for p in (str(_ROOT / "src"), str(_ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
