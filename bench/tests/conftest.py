"""The benchmark's own tests (``pytest bench/``) import the repository root,
``src/`` and the helpers beside this file."""

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
for p in (str(HERE), str(HERE.parents[1]), str(HERE.parents[1] / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
