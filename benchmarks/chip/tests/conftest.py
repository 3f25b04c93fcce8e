"""The benchmark's own tests run on the CPU; they import its modules by
name, as ``run.py`` does."""

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parents[2] / "src"))
