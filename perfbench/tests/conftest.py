"""perfbench's own tests: run by hand and in the rehearsal,
`JAX_PLATFORMS=cpu python3 -m pytest perfbench/tests -q`. They are not part
of the repo's tier-1 suite under tests/."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
