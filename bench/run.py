"""Run one benchmark cell once, on the chips of this host.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints one JSON object as the last line of stdout (see
``bench/harness.py``). Exits non-zero, printing no result, where JAX
finds no TPU or fewer chips than the cell asks for.
"""
import time

T0 = time.perf_counter()   # set-up is timed from here

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t0=T0))
