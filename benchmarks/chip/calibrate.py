#!/usr/bin/env python3
"""Readings that set a cell's limits, on the chip.

    python3 benchmarks/chip/calibrate.py --workload <cell> --seconds <s> \\
        --seeds <n> ... [--control-seeds <n> ...] \\
        [--fault <name> --fault-seeds <n> ...]

In one process, for each seed, one run of the cell at its own load and
sizes (a short window) gives the program's reading of each number the
cell compares.  For the control seeds, the control gives its reading on
the same inputs: for a served model, at the same positions of the same
prompts and served tokens, the gap of the token that the fp8 control of
the reference puts first; for training, the fp8 control's first steps
compared with the reference's.  With ``--fault``, runs of the fault
seeds have that fault planted in the program (``faults.py``).  The lower
reading of a number is the largest of the program's, the upper the
smallest of the control's and the faults'; its limit lies between them.
The benchmark's own runs never run this.
"""

import time

T_PROCESS = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from benchmarks.chip import faults, harness  # noqa: E402


def control_readings(cell, seed: int, run) -> dict:
    if cell.traffic["kind"] == "train_segments":
        from benchmarks.chip import training

        mix = cell.traffic
        data = training.SeededTokens(seed, cell.model["vocab_size"],
                                     mix["batch"], mix["seq_len"])
        ctl = cell.reference.train_readings(
            cell.model, mix["optimizer"], seed, data, mix["check"]["steps"],
            mix["check"]["rows_per_block"], control=True,
            mesh=training.mesh_of(cell))
        return training.compare(ctl, run.data["want"])
    from benchmarks.chip import serving

    return {"logit_gap": serving.control_gap(cell, seed, run)}


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", type=int, nargs="*", default=[])
    p.add_argument("--control-seeds", type=int, nargs="*", default=[])
    p.add_argument("--fault", choices=faults.FAULTS)
    p.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    args = p.parse_args()
    harness.keep_runtime_logs()
    cell = harness.find_cell(args.workload)
    harness.require_chips(cell.chips)
    harness.enable_compile_cache()
    rows = []
    for seed in args.seeds:
        t = time.time()
        run = harness.run_cell(cell, seed, args.seconds, False, t)
        row = {"seed": seed, "program": {k: v for k, (v, _) in
                                         run.checks.items()},
               "attempted": run.attempted, "failed": run.failed}
        if seed in args.control_seeds:
            row["control"] = control_readings(cell, seed, run)
        row["seconds"] = time.time() - t
        rows.append(row)
        print(json.dumps(row), flush=True)
    if args.fault:
        patch = faults.Patch()
        faults.plant(args.fault, patch)
        try:
            for seed in args.fault_seeds:
                t = time.time()
                run = harness.run_cell(cell, seed, args.seconds, False, t)
                row = {"seed": seed, args.fault: {
                    k: v for k, (v, _) in run.checks.items()},
                    "correct": run.correct, "seconds": time.time() - t}
                rows.append(row)
                print(json.dumps(row), flush=True)
        finally:
            patch.undo()
    summary = {}
    for key in ("program", "control", args.fault):
        readings = [r[key] for r in rows if key and key in r]
        if readings:
            pick = max if key == "program" else min
            summary[key] = {k: pick(r[k] for r in readings)
                            for k in readings[0]}
    print(json.dumps({"workload": args.workload, "summary": summary}),
          flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
