"""perfbench's own tests: ``JAX_PLATFORMS=cpu python -m pytest
perfbench/tests -q`` from the repo's root.  Not part of tier-1."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
  sys.path.insert(0, REPO)
