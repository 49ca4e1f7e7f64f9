#!/usr/bin/env python3
"""Run one benchmark cell once on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs from the root of a checkout. With ``--trace 0`` the last line of
standard output is the result with the cell's end-to-end metrics; with
``--trace 1`` it holds the per-layer metrics and the trace's breakdown.
The numbers the correctness check compared, each beside its limit, are
the last lines of standard error and the result's last key. Exits
non-zero with no result line when JAX finds no TPU, or fewer chips than
the cell asks for.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# the TPU runtime otherwise logs to a fixed path outside the checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))
