"""Puts the repository root on ``sys.path`` so the tests import the
benchmark as the ``bench`` package (``bench/run.py`` does the same)."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
