"""The benchmark's own tests (CPU; not part of tier-1):

    python -m pytest benchmarks/tests -q
"""

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (str(BENCH), str(BENCH.parent), str(Path(__file__).resolve().parent)):
    if p not in sys.path:
        sys.path.insert(0, p)
