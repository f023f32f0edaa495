"""Run one benchmark cell and print its result as the last stdout line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the chips the cell
asks for. JAX's persistent compilation cache is kept at ``.jax_cache/``
inside the checkout, so only a cell's first run there compiles.
"""

import os
import sys
import time

T_START = time.perf_counter()
CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

if __name__ == "__main__":
    # before JAX is imported: a fixed directory inside the checkout, so
    # that the program's own cache helper takes it
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(CHECKOUT,
                                                           ".jax_cache")
    sys.path[:0] = [CHECKOUT, os.path.join(CHECKOUT, "src")]
    from bench import harness

    sys.exit(harness.main(sys.argv[1:], T_START))
