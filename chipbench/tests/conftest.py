import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
# The runners run tiny on the CPU here; the command itself
# (chipbench/run.py) keeps refusing to run off the chip.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
