"""The harness's own tests run on the CPU at tiny sizes (``tests/tiny.py``);
they never need the chip, and never take it."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [p for p in (REPO, os.path.join(REPO, "src"))
                if p not in sys.path]
