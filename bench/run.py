"""Benchmark entry point: one cell of ``BENCHMARK.json`` on this machine.

    python3 bench/run.py --workload jsc-mlp-s10.sweep --seed 7 --seconds 51 --trace 0

Runs in one process on the accelerator it is started on and prints one
JSON result line as the last line of standard output (see
:mod:`bench.harness`).  Without a TPU, or with fewer chips than the cell
asks for, it exits non-zero and prints no result.
"""
import time

T0 = time.perf_counter()   # set-up is timed from here

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], t0=T0))
