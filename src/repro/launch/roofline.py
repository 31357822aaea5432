"""Roofline analysis over dry-run records (EXPERIMENTS.md §Roofline).

Three terms per (arch × shape × mesh), all in seconds-per-step, against
the peaks of the record's ``device_kind`` (:mod:`repro.launch.chips`):

    compute    = HLO_FLOPs_per_device / peak bf16 FLOP/s
    memory     = HLO_bytes_per_device / HBM bandwidth
    collective = wire_bytes_per_device / ICI link bandwidth

plus MODEL_FLOPS (6·N·D train / 2·N·D serve; N_active for MoE), the
useful-compute ratio MODEL_FLOPS / (chips·HLO_FLOPs), and the roofline
fraction  ideal_time / max(term)  where ideal_time = MODEL_FLOPS /
(chips·peak).

Caveat recorded with the table: HLO bytes-accessed comes from the CPU
backend's post-fusion cost model, which over-counts relative to TPU's
aggressive fusion — cross-cell comparisons are valid, absolute memory terms
are upper bounds.
"""

from __future__ import annotations

import glob
import json
import os

from repro.launch.chips import peaks

RESULTS_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))),
    "benchmarks", "results", "dryrun",
)


def model_flops(record: dict) -> float:
    n_active = record["params_active"]
    if record["kind"] == "train":
        tokens = record["global_batch"] * record["seq_len"]
        return 6.0 * n_active * tokens
    if record["kind"] == "prefill":
        tokens = record["global_batch"] * record["seq_len"]
        return 2.0 * n_active * tokens
    # decode: one token per sequence
    return 2.0 * n_active * record["global_batch"]


def analyze(record: dict) -> dict:
    chips = record["chips"]
    peak = peaks(record["device_kind"])
    flops_dev = record.get("cost", {}).get("flops", 0.0)
    bytes_dev = record.get("cost", {}).get("bytes accessed", 0.0)
    wire_dev = (
        record.get("collectives", {})
        .get("_total", {})
        .get("wire_bytes_per_device", 0)
    )
    compute_t = flops_dev / peak.bf16_flops
    memory_t = bytes_dev / peak.hbm_bytes_per_s
    coll_t = wire_dev / peak.ici_link_bytes_per_s
    mf = model_flops(record)
    ideal_t = mf / (chips * peak.bf16_flops)
    terms = {"compute": compute_t, "memory": memory_t, "collective": coll_t}
    dominant = max(terms, key=terms.get)
    bound_t = max(terms.values()) if max(terms.values()) > 0 else float("inf")
    useful = mf / (flops_dev * chips) if flops_dev else 0.0
    suggestion = {
        "compute": "reduce recompute (remat policy) / shrink useless FLOPs "
                   "(ratio below 1 means padding or recompute waste)",
        "memory": "increase fusion / microbatch to shrink live activations /"
                  " lower-precision activations",
        "collective": "reshard to turn all-reduce(+slice) into "
                      "reduce-scatter, compress gradients to bf16, overlap "
                      "collectives with compute",
    }[dominant]
    return {
        "arch": record["arch"],
        "shape": record["shape"],
        "mesh": record["mesh"],
        "chips": chips,
        "kind": record["kind"],
        "status": record.get("status"),
        "compute_s": compute_t,
        "memory_s": memory_t,
        "collective_s": coll_t,
        "dominant": dominant,
        "model_flops": mf,
        "hlo_flops_global": flops_dev * chips,
        "useful_ratio": useful,
        "ideal_s": ideal_t,
        "roofline_fraction": (ideal_t / bound_t) if bound_t else 0.0,
        "temp_bytes_dev": record.get("memory", {}).get("temp_size_in_bytes"),
        "arg_bytes_dev": record.get("memory", {}).get("argument_size_in_bytes"),
        "collective_counts": {
            k: v.get("count")
            for k, v in record.get("collectives", {}).items()
            if not k.startswith("_")
        },
        "suggestion": suggestion,
        "tag": record.get("tag", ""),
    }


def load_records(mesh: str | None = None, tag: str | None = "") -> list[dict]:
    out = []
    for path in sorted(glob.glob(os.path.join(RESULTS_DIR, "*.json"))):
        with open(path) as fh:
            rec = json.load(fh)
        if mesh and rec.get("mesh") != mesh:
            continue
        if tag is not None and rec.get("tag", "") != tag:
            continue
        out.append(rec)
    return out


def markdown_table(rows: list[dict]) -> str:
    header = (
        "| arch | shape | mesh | compute s | memory s | collective s | "
        "dominant | useful | roofline frac |\n"
        "|---|---|---|---|---|---|---|---|---|\n"
    )
    lines = []
    for r in rows:
        if r.get("status") != "ok":
            lines.append(
                f"| {r['arch']} | {r['shape']} | {r['mesh']} | — | — | — | "
                f"ERROR | — | — |"
            )
            continue
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} "
            f"| {r['compute_s']:.3f} | {r['memory_s']:.3f} "
            f"| {r['collective_s']:.3f} | **{r['dominant']}** "
            f"| {r['useful_ratio']:.2f} | {r['roofline_fraction']:.3f} |"
        )
    return header + "\n".join(lines) + "\n"


def main():
    import argparse

    parser = argparse.ArgumentParser()
    parser.add_argument("--mesh", default="single")
    parser.add_argument("--json", action="store_true")
    args = parser.parse_args()
    rows = []
    for rec in load_records(mesh=args.mesh):
        row = analyze(rec) if rec.get("status") == "ok" else {
            **{k: rec.get(k) for k in ("arch", "shape", "mesh", "status")},
        }
        rows.append(row)
    if args.json:
        print(json.dumps(rows, indent=1))
    else:
        print(markdown_table(rows))


if __name__ == "__main__":
    main()
