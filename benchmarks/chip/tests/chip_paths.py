"""Put the benchmark's modules and the program on the import path.

The benchmark's tests import this first.  It is a plain module and not a
``conftest.py``: the repository's own ``tests/conftest.py`` is imported by
its name, and a second module of that name would shadow it.
"""

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
for path in (HERE.parents[2] / "src", HERE.parent):
    if str(path) not in sys.path:
        sys.path.insert(0, str(path))
