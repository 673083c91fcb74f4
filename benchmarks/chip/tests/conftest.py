"""The benchmark's tests run on the CPU: ``python -m pytest benchmarks/chip/tests``."""
import os
import sys

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(CHIP))
for p in (os.path.join(ROOT, "src"), CHIP):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
