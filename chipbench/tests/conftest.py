"""chipbench's own tests: ``JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q``
from the root of the checkout. Not tier-1 (that stays ``tests/``)."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
