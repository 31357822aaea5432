#!/usr/bin/env python3
"""Find the highest rate an open-loop serving cell sustains, on the chip.

    python3 benchmarks/chip/sweep.py --workload <cell> --seconds <s> \\
        --seed <n> --rates <r> <r> ...

In one process, one run of the cell per rate (the cell's traffic with its
rate replaced).  For each: flows sent, flows completed by the window's
close, the backlog then, the median and 95th percentile latency, and the
mean latency of the first and second half of the window's flows: a
backlog that grows shows as a second half slower than the first.  The
knee is the highest rate whose backlog does not grow; the cell's
``rate_per_s`` is set once from it, at about four fifths.
"""

import time

T_PROCESS = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from benchmarks.chip import harness  # noqa: E402


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--rates", type=float, nargs="+", required=True)
    args = p.parse_args()
    harness.keep_runtime_logs()
    cell = harness.find_cell(args.workload)
    harness.require_chips(cell.chips)
    harness.enable_compile_cache()
    for rate in args.rates:
        cell.traffic = dict(cell.traffic, rate_per_s=rate)
        run = harness.run_cell(cell, args.seed, args.seconds, False,
                               time.time())
        flows = run.data["flows"]
        lat = [f["completed"] - f["scheduled"] for f in flows if f["ok"]]
        by_close = sum(f["ok"] and f["completed"] <= run.t_end for f in flows)
        half = len(flows) // 2
        first = [f["completed"] - f["scheduled"] for f in flows[:half]]
        second = [f["completed"] - f["scheduled"] for f in flows[half:]]
        print(json.dumps({
            "rate": rate, "sent": len(flows), "failed": run.failed,
            "completed_by_close": by_close,
            "backlog_at_close": len(flows) - by_close,
            "latency_median_s": statistics.median(lat),
            "latency_p95_s": statistics.quantiles(lat, n=20)[-1],
            "first_half_mean_s": statistics.fmean(first),
            "second_half_mean_s": statistics.fmean(second),
            "correct": run.correct}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
