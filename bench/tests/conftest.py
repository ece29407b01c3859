"""The benchmark's own tests (run by hand: ``python -m pytest
bench/tests``); the repository's test suite does not collect them."""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
