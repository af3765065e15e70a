"""CPU tests of the benchmark harness.  Run from the repository root:

  JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
