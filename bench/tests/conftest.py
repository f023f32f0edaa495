import os
import sys

# The benchmark's tests run on the CPU and never touch a TPU.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(CHECKOUT, "src"), CHECKOUT):
    if p not in sys.path:
        sys.path.insert(0, p)
