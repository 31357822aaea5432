import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512 " + os.environ.get("XLA_EXTRA_FLAGS", "")

"""Multi-pod dry-run: lower + compile every (architecture × input shape) on
the production meshes, and extract the roofline inputs.

The two lines above run before ANY other import — JAX locks the device
count at first initialization, and the dry-run needs 512 placeholder host
devices to build the 16x16 and 2x16x16 production meshes.

Usage::

    PYTHONPATH=src python -m repro.launch.dryrun --arch internlm2-1.8b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

Each cell writes benchmarks/results/dryrun/<arch>__<shape>__<mesh>.json with
memory analysis, cost analysis, and collective traffic — the roofline
(launch.roofline) and EXPERIMENTS.md read from there.
"""

import argparse   # noqa: E402
import json       # noqa: E402
import time       # noqa: E402
import traceback  # noqa: E402

import jax        # noqa: E402

from repro import configs                       # noqa: E402
from repro.configs.base import TrainConfig      # noqa: E402
from repro.launch import hlo as hlo_mod         # noqa: E402
from repro.launch import specs as specs_mod     # noqa: E402
from repro.launch.mesh import make_production_mesh, mesh_chip_count  # noqa: E402
from repro.models.model import (                # noqa: E402
    Model,
    active_params_analytic,
    count_params_analytic,
)
from repro.parallel.sharding import PARAM_RULES, use_rules  # noqa: E402
from repro.train.loop import make_train_step    # noqa: E402

RESULTS_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))),
    "benchmarks", "results", "dryrun",
)


def make_step_fn(cfg, shape, mesh, rules_override=None, tcfg=None,
                 constrain_grads=False):
    """Build the function to lower for this cell."""
    model = Model(cfg)
    act_rules = specs_mod.act_rules_for(cfg, shape, mesh)
    if rules_override:
        act_rules = act_rules.merged(rules_override)
    param_rules = PARAM_RULES

    if shape.kind == "train":
        tcfg = tcfg or TrainConfig()
        grad_sh = None
        if constrain_grads:
            from repro.models.model import param_axes, param_shapes
            from repro.parallel.sharding import param_shardings

            grad_sh = param_shardings(
                param_axes(cfg), mesh, param_rules,
                param_shapes=param_shapes(cfg),
            )
        step = make_train_step(model, tcfg, grad_shardings=grad_sh)

        def train_fn(state, batch):
            with use_rules(param_rules, act_rules, mesh):
                return step(state, batch)

        return train_fn

    if shape.kind == "prefill":
        def prefill_fn(params, batch):
            with use_rules(param_rules, act_rules, mesh):
                return model.prefill(params, batch, shape.seq_len)

        return prefill_fn

    def decode_fn(params, tokens_new, cache, position):
        with use_rules(param_rules, act_rules, mesh):
            return model.decode_step(params, tokens_new, cache, position)

    return decode_fn


def run_cell(
    arch: str,
    shape_name: str,
    mesh_kind: str,
    tcfg: TrainConfig | None = None,
    rules_override: dict | None = None,
    cfg_override: dict | None = None,
    constrain_grads: bool = False,
    save: bool = True,
    tag: str = "",
) -> dict:
    # unroll layer stacks so HLO cost analysis sees full-depth FLOPs/bytes
    # (scan/while bodies are counted once by XLA's analysis)
    cfg = configs.get(arch).replace(unroll_layers=True, **(cfg_override or {}))
    shape = configs.SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_kind,
        "mesh_shape": list(mesh.devices.shape),
        "chips": mesh_chip_count(mesh),
        # the kind actually compiled for; roofline.analyze refuses kinds
        # without published peaks, such as the placeholder host devices
        "device_kind": mesh.devices.flat[0].device_kind,
        "kind": shape.kind,
        "seq_len": shape.seq_len,
        "global_batch": shape.global_batch,
        "params_total": count_params_analytic(cfg),
        "params_active": active_params_analytic(cfg),
        "tag": tag,
    }
    t0 = time.time()
    try:
        specs = specs_mod.input_specs(cfg, shape, mesh, overrides=rules_override)
        fn = make_step_fn(cfg, shape, mesh, rules_override, tcfg,
                          constrain_grads=constrain_grads)
        with mesh:
            if shape.kind == "train":
                lowered = jax.jit(fn).lower(specs["state"], specs["batch"])
            elif shape.kind == "prefill":
                lowered = jax.jit(fn).lower(specs["params"], specs["batch"])
            else:
                lowered = jax.jit(fn).lower(
                    specs["params"], specs["tokens_new"], specs["cache"],
                    specs["position"],
                )
            record["lower_seconds"] = time.time() - t0
            t1 = time.time()
            compiled = lowered.compile()
            record["compile_seconds"] = time.time() - t1

            mem = compiled.memory_analysis()
            if mem is not None:
                for key in (
                    "argument_size_in_bytes", "output_size_in_bytes",
                    "temp_size_in_bytes", "generated_code_size_in_bytes",
                    "alias_size_in_bytes",
                ):
                    record.setdefault("memory", {})[key] = getattr(
                        mem, key, None
                    )
            cost = compiled.cost_analysis()
            if cost:
                record["cost"] = {
                    k: cost[k]
                    for k in ("flops", "transcendentals", "bytes accessed")
                    if isinstance(cost.get(k), (int, float))
                }
            text = compiled.as_text()
            record["collectives"] = hlo_mod.analyze_collectives(text)
            record["hlo_instructions"] = text.count("\n")
            record["status"] = "ok"
    except Exception as e:
        record["status"] = "error"
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-4000:]
    record["total_seconds"] = time.time() - t0

    if save:
        os.makedirs(RESULTS_DIR, exist_ok=True)
        suffix = f"__{tag}" if tag else ""
        path = os.path.join(
            RESULTS_DIR, f"{arch}__{shape_name}__{mesh_kind}{suffix}.json"
        )
        with open(path, "w") as fh:
            json.dump(record, fh, indent=1)
        record["path"] = path
    return record


def iter_cells(mesh_kinds):
    for arch in configs.ARCH_IDS:
        for shape in configs.shapes_for(arch):
            for mesh_kind in mesh_kinds:
                yield arch, shape.name, mesh_kind


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--arch", default=None)
    parser.add_argument("--shape", default=None)
    parser.add_argument("--mesh", choices=["single", "multi", "both"],
                        default="single")
    parser.add_argument("--all", action="store_true")
    parser.add_argument("--skip-existing", action="store_true")
    parser.add_argument("--quiet", action="store_true")
    args = parser.parse_args()

    mesh_kinds = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.all:
        cells = list(iter_cells(mesh_kinds))
    else:
        if not args.arch or not args.shape:
            parser.error("--arch and --shape required unless --all")
        cells = [(args.arch, args.shape, mk) for mk in mesh_kinds]

    failures = 0
    for arch, shape_name, mesh_kind in cells:
        out_path = os.path.join(
            RESULTS_DIR, f"{arch}__{shape_name}__{mesh_kind}.json"
        )
        if args.skip_existing and os.path.exists(out_path):
            with open(out_path) as fh:
                if json.load(fh).get("status") == "ok":
                    continue
        rec = run_cell(arch, shape_name, mesh_kind)
        ok = rec["status"] == "ok"
        failures += not ok
        if not args.quiet:
            line = (
                f"[{'OK ' if ok else 'ERR'}] {arch} × {shape_name} × "
                f"{mesh_kind}  ({rec['total_seconds']:.1f}s"
            )
            if ok:
                mem = rec.get("memory", {})
                line += (
                    f", args/dev {mem.get('argument_size_in_bytes', 0)/2**30:.2f}"
                    f" GiB, temp/dev {mem.get('temp_size_in_bytes', 0)/2**30:.2f}"
                    f" GiB, flops {rec.get('cost', {}).get('flops', 0):.3g}"
                    f", coll {rec['collectives']['_total']['wire_bytes_per_device']/2**20:.1f} MiB)"
                )
            else:
                line += f") {rec['error'][:200]}"
            print(line, flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
