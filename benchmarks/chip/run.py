#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once on the chip and print its result.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``compared``: each number that decided ``correct``
beside its limit).  Without an accelerator, or with fewer chips than the
cell asks for, it exits non-zero and prints no result.
"""

import time

T_PROCESS = time.time()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from benchmarks.chip import harness  # noqa: E402

if __name__ == "__main__":
    harness.keep_runtime_logs()
    raise SystemExit(harness.main(None, T_PROCESS))
