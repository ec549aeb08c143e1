"""Run by hand and in rehearsal (``python3 -m pytest benchmark/tests``);
tier-1 collects ``tests/`` only."""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)
