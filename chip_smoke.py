#!/usr/bin/env python3
"""Prove that the flow-driven compute plane runs on a TPU, end to end.

Every phase goes through the system's own entry points:
``FlowsService.publish_flow``/``run_flow`` -> inline backend ->
``ComputeProvider`` -> ``ServeEngine`` / ``TrainingFabric`` -> XLA on the chip.
Model weights and data are random, made from ``--seed``.

    python3 chip_smoke.py               # one chip
    python3 chip_smoke.py --four-chip   # one host of four chips

One chip:
  * serving flow -- internlm2-1.8b at its published widths (24 layers, f32
    weights, bf16 compute) serves 8 prompts of 128 tokens, 32 new tokens
    each, as one batch, through a flow whose Action state calls the engine
    on a thread-mode compute endpoint.  The served prefill's last-position
    logits and the first decode step's logits are compared with
    ``Model.forward`` in float32 at ``highest`` matmul precision.
  * training flow -- the flow of ``repro.launch.train`` (train segments,
    checkpoint, an injected NodeFailure caught -> restore -> train, then
    evaluate) over internlm2-1.8b at published widths, with depth cut so
    the training state fits one chip.
Four chips (``--four-chip``, this phase alone):
  * the whole 24-layer model trains sharded 2x2 (data x model) through the
    same flow, is killed by NodeFailure after an async save, reshards onto
    4x1, restores and continues; it must match the same steps run
    uninterrupted on 2x2 from the same seed.

Without a TPU the script exits non-zero and prints no result.  Everything
runs in this one process, which holds the chip; the last line of standard
output is the JSON result.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import configs  # noqa: E402
from repro.configs.base import TrainConfig  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.launch.serve import build_serving_flow  # noqa: E402
from repro.launch.train import run_training_flow  # noqa: E402
from repro.models.model import Model, count_params_analytic  # noqa: E402
from repro.serve.engine import ServeEngine  # noqa: E402
from repro.train.fabric import TrainingFabric  # noqa: E402

ARCH = "internlm2-1.8b"
#: scratch for checkpoints and the flows' providers, inside the checkout
WORKDIR = os.path.join(ROOT, ".chip_smoke")

# serving traffic: one full batch of equal-length prompts
REQUESTS, PROMPT_LEN, MAX_NEW = 8, 128, 32
#: served (bf16 compute) vs reference (f32, highest precision) logits: the
#: largest per-request relative L2 error ||served - ref|| / ||ref||.  bf16
#: keeps 8 significant bits (unit roundoff 2^-9 ~ 0.2%); rounding of the
#: activations and of every matmul operand through 24 residual layers
#: leaves ~2% (1.7-1.9% measured on a TPU v5e).  An 8-bit float path (2^-4
#: roundoff) or a wrong cache position lands far above 5%.
SERVE_REL_TOL = 5e-2

# one-chip training: published widths, depth cut to one chip's training
# state (16 B/param: f32 params, grads, Adam m and v); batch x sequence
# sized by the compiled step's memory_analysis for a described v5e chip
TRAIN_LAYERS, TRAIN_BATCH, TRAIN_SEQ = 4, 4, 2048
STEPS_PER_SEGMENT = 3

# four chips: the whole model, sharded
FOUR_BATCH, FOUR_SEQ = 8, 1024
#: per-step loss of the run that was killed and resharded vs the one that
#: was not.  Steps before the kill run the same program on the same mesh
#: from the same state and batch, so they agree to f32 rounding of the
#: loss (1e-5 relative).  Steps after the reshard run on 4x1, whose
#: different partitioning changes bf16 reduction order: the mean token
#: loss (~11.6 here) moves by ~1e-3 (6.4e-4 measured on a TPU v5e host);
#: 2e-2 absolute bounds that, while a step's worth of training (~0.05 to
#: 0.1 here) or a mis-placed shard moves it far more.
SAME_MESH_RTOL = 1e-5
RESHARD_ATOL = 2e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def require_tpu(n_chips: int):
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX's first device is {d.platform} "
              f"({d.device_kind}); this check runs only on the chip",
              file=sys.stderr)
        raise SystemExit(2)
    if len(devices) < n_chips:
        print(f"chip_smoke: needs {n_chips} chips, JAX sees {len(devices)}",
              file=sys.stderr)
        raise SystemExit(2)
    log(f"device: platform={d.platform} kind={d.device_kind} "
        f"count={len(devices)}")
    log(f"memory_stats[{d.device_kind}]: {d.memory_stats()}")
    return devices


def peak_bytes(device) -> int:
    """The device's running peak since this process started."""
    return int(device.memory_stats()["peak_bytes_in_use"])


def live_bytes(device) -> int:
    return int(device.memory_stats()["bytes_in_use"])


@jax.jit
def fingerprint(tree):
    """Exact per-leaf digest: wrapping uint32 sums of the raw bits, plain
    and position-weighted.  Integer sums do not depend on reduction order,
    so the digest of a state is the same under any sharding."""
    def one(x):
        u = jax.lax.bitcast_convert_type(x, jnp.uint32)
        pos = jax.lax.iota(jnp.uint32, u.size).reshape(u.shape)
        return jnp.stack([jnp.sum(u, dtype=jnp.uint32),
                          jnp.sum(u * (2 * pos + 1), dtype=jnp.uint32)])
    return [one(x) for x in jax.tree_util.tree_leaves(tree)]


class CheckedFabric(TrainingFabric):
    """A fabric that fingerprints its state at every save and restore."""

    def __init__(self, *args, **kwargs):
        self.saved_fp: dict[int, list] = {}
        self.restored_fp: dict[int, list] = {}
        super().__init__(*args, **kwargs)

    def save_checkpoint(self, synchronous: bool = True, **kw) -> dict:
        fp = jax.device_get(fingerprint(self.state))
        out = super().save_checkpoint(synchronous=synchronous, **kw)
        self.saved_fp[out["step"]] = fp
        return out

    def restore_latest(self, **kw) -> dict:
        out = super().restore_latest(**kw)
        self.restored_fp[out["restored_step"]] = jax.device_get(
            fingerprint(self.state))
        return out


def free(tree) -> None:
    """Release device buffers now, whoever still holds a reference."""
    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array):
            leaf.delete()


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def serve_phase(cfg, seed: int, kind: str) -> dict:
    model = Model(cfg)
    params = jax.jit(model.init_fn)(jax.random.PRNGKey(seed))
    engine = ServeEngine(model, params, max_len=PROMPT_LEN + MAX_NEW)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, size=(REQUESTS, PROMPT_LEN),
                            dtype=np.int32) for _ in range(2)]
    served: dict[int, dict] = {}

    def serve_batch(batch: int) -> dict:
        out = engine.generate(prompts[batch], max_new_tokens=MAX_NEW)
        served[batch] = out
        return {"requests": int(out["tokens"].shape[0]),
                "new_tokens": [len(t) for t in out["tokens"].tolist()]}

    flows, flow_id = build_serving_flow(serve_batch, {"batch.$": "$.batch"})
    walls = []
    try:
        # batch 0 pays the compiles; batch 1 is the warm measurement
        for batch in range(2):
            t0 = time.perf_counter()
            run = flows.run_flow(flow_id, {"batch": batch})
            flows.engine.wait(run.run_id, timeout=900)
            walls.append(time.perf_counter() - t0)
            if run.status != "SUCCEEDED":
                fail(f"serving run {batch} ended {run.status}: {run.error}")
            got = run.context["served"]["details"]["results"][0]
            if got != {"requests": REQUESTS, "new_tokens": [MAX_NEW] * REQUESTS}:
                fail(f"serving run {batch} returned {got}")
    finally:
        flows.engine.shutdown()
    peak = peak_bytes(jax.devices()[0])

    # reference: Model.forward in float32 at highest matmul precision
    ref_model = Model(cfg.replace(compute_dtype="float32"))

    @jax.jit
    def ref_last_logits(p, tokens):
        logits, _ = ref_model.forward(p, {"tokens": tokens})
        return logits[:, -1].astype(jnp.float32)

    worst = 0.0
    for batch, out in served.items():
        toks = out["tokens"]
        if toks.shape != (REQUESTS, MAX_NEW) or toks.min() < 0 \
                or toks.max() >= cfg.vocab_size:
            fail(f"batch {batch}: bad tokens {toks.shape}")
        seqs = [prompts[batch],
                np.concatenate([prompts[batch], toks[:, :1]], axis=1)]
        for name, got, seq in zip(("prefill", "decode1"), out["logits"], seqs):
            with jax.default_matmul_precision("highest"):
                ref = np.asarray(ref_last_logits(params, jnp.asarray(seq)))
            got = np.asarray(got[:, -1].astype(jnp.float32))
            if not np.isfinite(got).all():
                fail(f"batch {batch} {name}: non-finite served logits")
            rel = np.linalg.norm(got - ref, axis=-1) / np.linalg.norm(ref, axis=-1)
            worst = max(worst, float(rel.max()))
            log(f"serve[{kind}] batch {batch} {name}: max rel L2 err "
                f"{rel.max():.6f} (tol {SERVE_REL_TOL}), max abs err "
                f"{np.abs(got - ref).max():.6f}, ref |logit| max "
                f"{np.abs(ref).max():.4f}")
    if worst > SERVE_REL_TOL:
        fail(f"served logits differ from the reference: {worst} > "
             f"{SERVE_REL_TOL}")
    free(params)
    new_tokens = REQUESTS * MAX_NEW
    res = {"cold_wall_s": walls[0], "warm_wall_s": walls[1],
           "compile_s": walls[0] - walls[1],
           "tokens_per_s": new_tokens / walls[1],
           "peak_bytes_in_use": peak, "max_rel_err": worst}
    log(f"serve[{kind}]: {ARCH} {count_params_analytic(cfg)} params, "
        f"{REQUESTS} requests x {PROMPT_LEN} prompt + {MAX_NEW} new tokens; "
        f"compile_s={res['compile_s']:.3f} (cold run {walls[0]:.3f} s - warm "
        f"run {walls[1]:.3f} s) wall_s={walls[1]:.3f} "
        f"tokens_per_s={res['tokens_per_s']:.2f} "
        f"peak_bytes_in_use={peak}")
    return res


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------

def _finite(values, what: str) -> None:
    if not all(math.isfinite(v) for v in values):
        fail(f"non-finite {what}: {values}")


def train_phase(cfg, seed: int, kind: str, *, batch: int, seq_len: int,
                workdir: str) -> dict:
    tcfg = TrainConfig(total_steps=2 * STEPS_PER_SEGMENT, warmup_steps=2,
                       learning_rate=1e-3, seed=seed)
    t0 = time.perf_counter()
    fabric = CheckedFabric(cfg, tcfg, batch=batch, seq_len=seq_len,
                           ckpt_dir=os.path.join(workdir, "ckpt"), seed=seed)
    # the device dies one step into the second segment
    fabric.inject_failure_at = STEPS_PER_SEGMENT + 1
    run = run_training_flow(
        fabric, workdir=workdir, segments=2,
        steps_per_segment=STEPS_PER_SEGMENT, label="chip-smoke",
        timeout=900,
    )
    wall = time.perf_counter() - t0
    if run.status != "SUCCEEDED":
        fail(f"training run ended {run.status}: {run.error}")
    if "failure" not in run.context:
        fail("the injected NodeFailure was never caught")
    restored = run.context["restore"]["details"]["results"][0]["restored_step"]
    if restored != STEPS_PER_SEGMENT or STEPS_PER_SEGMENT not in fabric.saved_fp:
        fail(f"restored step {restored}; checkpointed steps "
             f"{sorted(fabric.saved_fp)}")
    if not _same_digest(fabric.saved_fp[restored], fabric.restored_fp[restored]):
        fail(f"state restored at step {restored} differs from the state saved")
    losses = [x for rec in fabric.history for x in rec["losses"]]
    _finite(losses, "training losses")
    eval_loss = run.context["eval"]["details"]["results"][0]["eval_loss"]
    _finite([eval_loss], "eval loss")
    final_step = run.context["eval"]["details"]["results"][0]["step"]
    if final_step != 2 * STEPS_PER_SEGMENT:
        fail(f"training ended at step {final_step}")
    # serving ran first, so the running peak is not training's own; the
    # live bytes are the training state the fabric holds
    live = live_bytes(jax.devices()[0])
    res = {"wall_s": wall, "losses": losses, "eval_loss": eval_loss,
           "restored_step": restored, "bytes_in_use": live}
    log(f"train[{kind}]: {count_params_analytic(cfg)} params, batch {batch} x "
        f"seq {seq_len}; flow wall_s={wall:.3f} (compiles and checkpoint "
        f"I/O included); losses={losses} eval_loss={eval_loss}; restored "
        f"step {restored} == checkpointed step, digest equal; "
        f"bytes_in_use={live}")
    free(fabric.state)
    return res


def _same_digest(a, b) -> bool:
    return len(a) == len(b) and all(np.array_equal(x, y) for x, y in zip(a, b))


def four_chip_phase(cfg, seed: int, kind: str, *, batch: int, seq_len: int,
                    workdir: str) -> dict:
    k = STEPS_PER_SEGMENT
    tcfg = TrainConfig(total_steps=2 * k, warmup_steps=2, learning_rate=1e-3,
                       seed=seed)
    mesh22 = make_mesh((2, 2), ("data", "model"))
    mesh41 = make_mesh((4, 1), ("data", "model"))
    devices = list(mesh22.devices.flat)

    # reference: the same steps, uninterrupted, on 2x2
    t0 = time.perf_counter()
    ref = TrainingFabric(cfg, tcfg, batch=batch, seq_len=seq_len,
                         ckpt_dir=os.path.join(workdir, "ref-ckpt"),
                         mesh=mesh22, seed=seed)
    ref_losses = ref.train_steps(n_steps=2 * k)["losses"]
    ref_wall = time.perf_counter() - t0
    _finite(ref_losses, "reference losses")
    log(f"four[{kind}] reference 2x2: {2 * k} steps in {ref_wall:.3f} s "
        f"(compiles included); losses={ref_losses}; per-device "
        f"peak_bytes_in_use={[peak_bytes(d) for d in devices]}")
    free(ref.state)
    del ref

    t0 = time.perf_counter()
    fabric = CheckedFabric(cfg, tcfg, batch=batch, seq_len=seq_len,
                           ckpt_dir=os.path.join(workdir, "ckpt"),
                           mesh=mesh22, seed=seed)
    fabric.checkpointer.keep = 1
    fabric.inject_failure_at = k + 1
    run = run_training_flow(
        fabric, workdir=workdir, segments=2, steps_per_segment=k,
        label="chip-smoke-4", restore=lambda: fabric.reshard(mesh41),
        async_checkpoint=True, timeout=1100,
    )
    fabric.checkpointer.wait()  # the last async save is on disk
    wall = time.perf_counter() - t0
    if run.status != "SUCCEEDED":
        fail(f"four-chip training run ended {run.status}: {run.error}")
    if "failure" not in run.context:
        fail("the injected NodeFailure was never caught")
    reshard = run.context["restore"]["details"]["results"][0]
    if (tuple(reshard["old_mesh"]), tuple(reshard["new_mesh"])) != ((2, 2), (4, 1)):
        fail(f"reshard went {reshard}")
    restored = reshard["restored_step"]
    if restored != k or not _same_digest(fabric.saved_fp[k],
                                         fabric.restored_fp[restored]):
        fail(f"restored step {restored} does not equal the state saved at {k}")
    params = jax.tree_util.tree_leaves(fabric.state.params)
    if any(len(p.sharding.device_set) != 4 for p in params):
        fail("restored parameters are not spread over the four chips")
    before, after = fabric.history[0]["losses"], fabric.history[1]["losses"]
    _finite(before + after, "losses")
    d_same = max(abs(a - b) / abs(b) for a, b in zip(before, ref_losses[:k]))
    d_resh = max(abs(a - b) for a, b in zip(after, ref_losses[k:]))
    log(f"four[{kind}] killed at step {k + 1}, resharded 2x2 -> 4x1, "
        f"restored step {restored} (digest equal); flow wall_s={wall:.3f} "
        f"(compiles and checkpoint I/O included)")
    log(f"four[{kind}] losses 2x2 {before} then 4x1 {after}; reference "
        f"{ref_losses}; max rel diff before kill {d_same:.3e} (tol "
        f"{SAME_MESH_RTOL}), max abs diff after reshard {d_resh:.3e} (tol "
        f"{RESHARD_ATOL}); per-device bytes_in_use after resuming="
        f"{[live_bytes(d) for d in devices]}")
    if d_same > SAME_MESH_RTOL or d_resh > RESHARD_ATOL:
        fail("the resumed run does not match the uninterrupted reference")
    return {"wall_s": wall, "ref_losses": ref_losses,
            "losses": before + after}


# ---------------------------------------------------------------------------

def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--four-chip", action="store_true",
                        help="run only the four-chip sharded phase")
    args = parser.parse_args()

    cache = enable_compile_cache()
    devices = require_tpu(4 if args.four_chip else 1)
    kind = devices[0].device_kind
    log(f"compile cache: {cache}")
    shutil.rmtree(WORKDIR, ignore_errors=True)
    full = configs.get(ARCH)
    try:
        if args.four_chip:
            four_chip_phase(full, args.seed, kind, batch=FOUR_BATCH,
                            seq_len=FOUR_SEQ, workdir=WORKDIR)
        else:
            serve_phase(full, args.seed, kind)
            log(f"reduced: n_layers {full.n_layers}→{TRAIN_LAYERS}")
            train_phase(full.replace(n_layers=TRAIN_LAYERS), args.seed, kind,
                        batch=TRAIN_BATCH, seq_len=TRAIN_SEQ, workdir=WORKDIR)
    finally:
        shutil.rmtree(WORKDIR, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
