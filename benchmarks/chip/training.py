"""Training cells: a flow that loops Train(n steps) -> Increment -> Choice
over a ``TrainingFabric``, through ``FlowsService.run_flow`` -> the
compute endpoint -> ``TrainingFabric.train_steps`` -> the jitted step.

Set-up builds the fabric once, gives it the benchmark's weights from the
seed, and drives it through its first steps by the same flow and call the
window uses (one step, then two), reading what the comparison needs: the
loss of each step, the first gradient as the optimizer holds it after
step 1 (Adam's first moment over ``1 - beta1``), and each parameter's
change after step 3.  The same fabric then trains through the window,
segment by segment, until the first segment that ends after ``seconds``.
After the window, with the program's state freed, the plain reference
runs the same three steps in float32.  A mix that names a mesh has the
fabric train over it, with the seed's state and the reference spread over
the cell's chips.
"""

from __future__ import annotations

import json
import math
import statistics
import time

from benchmarks.chip import traffic as tr
from benchmarks.chip.harness import RunResult, Tracer, free, log, memory_peak


class SeededTokens:
    """The feed: batch ``step`` is ``[batch, seq]`` token ids uniform over
    the vocabulary and their next-token labels, drawn from the seed, so
    every row of every step differs."""

    def __init__(self, seed: int, vocab: int, batch: int, seq: int):
        self.seed, self.vocab, self.batch, self.seq = seed, vocab, batch, seq

    def batch_at(self, step: int) -> dict:
        stream = tr.prompts(self.seed, 10**6 + step, self.batch, self.seq + 1,
                            self.vocab)
        return {"tokens": stream[:, :-1], "labels": stream[:, 1:]}


def flow_definition(chip_dir, name: str, ids: dict, steps: int) -> dict:
    """``flows/<name>.json`` with its ``${...}`` placeholders filled in."""
    text = (chip_dir / "flows" / f"{name}.json").read_text()
    text = text.replace('"${steps}"', str(int(steps)))
    for key, value in ids.items():
        text = text.replace("${" + key + "}", value)
    return json.loads(text)


def mesh_of(cell):
    """The mesh the traffic mix names (``"mesh": {"shape": [2, 2], "axes":
    ["data", "model"]}``) over the cell's chips, or ``None`` where it names
    none: the fabric then trains on one device."""
    spec = cell.traffic.get("mesh")
    if spec is None:
        return None
    from repro.launch.mesh import make_mesh

    if math.prod(spec["shape"]) != cell.chips:
        raise ValueError(f"mesh {spec['shape']} does not cover the cell's "
                         f"{cell.chips} chips")
    return make_mesh(tuple(spec["shape"]), tuple(spec["axes"]))


def run(cell, seed: int, seconds: float, trace: bool, t_process: float
        ) -> RunResult:
    import jax
    import jax.numpy as jnp

    from repro.configs.base import ModelConfig, TrainConfig
    from repro.launch.train import build_stack
    from repro.train.fabric import TrainingFabric
    from repro.train.loop import TrainState
    from repro.train.optimizer import AdamWState

    cfg, mix, ref = cell.model, cell.traffic, cell.reference
    batch, seq = mix["batch"], mix["seq_len"]
    opt = mix["optimizer"]
    workdir = cell.root / ".bench_work" / cell.name
    data = SeededTokens(seed, cfg["vocab_size"], batch, seq)
    mesh = mesh_of(cell)
    fabric = TrainingFabric(ModelConfig(**cfg), TrainConfig(**opt),
                            batch=batch, seq_len=seq,
                            ckpt_dir=str(workdir / "ckpt"), mesh=mesh,
                            data=data)
    # the benchmark's weights from the seed, in place of the fabric's own,
    # on the fabric's own shardings where it has a mesh: no device ever
    # holds the whole state
    where = None if mesh is None else jax.tree_util.tree_map(
        lambda x: x.sharding, fabric.state)
    free(fabric.state)
    p_where = None if where is None else where.params
    params = ref.init_params(cfg, seed, shardings=p_where)
    if where is None:
        zeros = jax.jit(lambda p: jax.tree_util.tree_map(jnp.zeros_like, p))
        step0 = jnp.zeros((), jnp.int32)
    else:
        zeros = jax.jit(lambda p: jax.tree_util.tree_map(jnp.zeros_like, p),
                        out_shardings=where.opt.m)
        step0 = jax.device_put(jnp.zeros((), jnp.int32), where.opt.step)
    fabric.state = TrainState(params=params, opt=AdamWState(
        step=step0, m=zeros(params), v=zeros(params)))
    params = None

    flows, compute = build_stack(str(workdir))
    segments: list[dict] = []
    # a flow loops until the first segment that ends ``seconds`` after its
    # first segment started
    stop = {"seconds": 0.0, "at": None}

    def train_segment(n_steps: int) -> dict:
        rec = {"start": time.time()}
        if stop["at"] is None:
            stop["at"] = rec["start"] + stop["seconds"]
        with jax.profiler.TraceAnnotation(f"bench:train_steps n={n_steps}"):
            out = fabric.train_steps(n_steps=n_steps)
        rec.update(end=time.time(), steps=n_steps, step=out["step"],
                   losses=out["losses"])
        segments.append(rec)
        return {"step": out["step"], "loss": out["loss"]}

    def increment(segment: int) -> dict:
        return {"segment": segment + 1, "more": time.time() < stop["at"]}

    eid = compute.register_endpoint("training-fabric", mode="inline")
    ids = {"endpoint": eid,
           "train_steps": compute.register_function(train_segment,
                                                    name="train_steps"),
           "increment": compute.register_function(increment,
                                                  name="increment")}

    def run_flow(steps: int, label: str):
        record = flows.publish_flow(
            flow_definition(cell.chip_dir, mix["flow"], ids, steps),
            title=f"Train {cfg['arch']} ({label})")
        r = flows.run_flow(record.flow_id, {"segment": 0}, label=label)
        flows.engine.wait(r.run_id, timeout=3600)
        if r.status != "SUCCEEDED":
            raise RuntimeError(f"training flow {label} ended {r.status}: "
                               f"{r.error}")
        return r

    norms = jax.jit(lambda tree: [jnp.linalg.norm(x.astype(jnp.float32))
                                  for x in jax.tree_util.tree_leaves(tree)])
    try:
        # the checked first steps, through the window's flow and call, one
        # segment each
        run_flow(1, "step-1")
        b1 = opt.get("beta1", 0.9)
        first_grad = [float(x) / (1 - b1)
                      for x in jax.device_get(norms(fabric.state.opt.m))]
        stop["at"] = None
        run_flow(mix["check"]["steps"] - 1, "steps-2-3")
        p0 = ref.init_params(cfg, seed, shardings=p_where)
        change = jax.device_get(norms(jax.tree_util.tree_map(
            lambda a, b: a - b, fabric.state.params, p0)))
        free(p0)
        checked_losses = [x for s in segments for x in s["losses"]]
        warm = len(segments)

        tracer = Tracer(cell, trace)
        t0 = time.time()
        setup_s = t0 - t_process
        stop.update(seconds=seconds, at=None)
        with tracer.window():
            run_flow(mix["steps_per_segment"], "window")
        summary = tracer.finish()
    finally:
        flows.engine.shutdown()
    peak = memory_peak()
    window = segments[warm:]
    steps = sum(s["steps"] for s in window)
    t_first, t_end = window[0]["start"], window[-1]["end"]
    losses = [x for s in window for x in s["losses"]]
    free(fabric.state)
    fabric.state = None
    log(f"train[{cell.name}]: {steps} steps in {len(window)} segments over "
        f"{t_end - t_first:.3f} s; checked losses {checked_losses}")

    t_ref = time.time()
    want = ref.train_readings(cfg, opt, seed, data, mix["check"]["steps"],
                              mix["check"]["rows_per_block"], mesh=mesh)
    log(f"reference: {mix['check']['steps']} steps in "
        f"{time.time() - t_ref:.3f} s")
    got = {"losses": checked_losses, "first_grad": first_grad,
           "change": [float(x) for x in change]}
    checks = {k: (v, cell.limits[k])
              for k, v in compare(got, want).items()}
    finite = all(math.isfinite(x) for x in losses)
    correct = finite and all(v <= lim for v, lim in checks.values())
    return RunResult(
        setup_s=setup_s, t0=t_first, t_end=t_end, seconds=seconds,
        attempted=steps, failed=0 if finite else steps, correct=correct,
        checks=checks, memory_peak_bytes=peak, trace=summary,
        device_kind=jax.devices()[0].device_kind,
        data={"segments": window, "tokens_per_step": batch * seq,
              "seq_len": seq, "chips": cell.chips, "got": got,
              "want": want},
    )


def compare(got: dict, want: dict) -> dict:
    """The three numbers compared with the reference.

    * ``loss``: the largest relative gap of a checked step's loss;
    * ``first_grad`` and ``change``: by the worst leaf, the gap between the
      program's norm and the reference's (of the first gradient as the
      optimizer holds it; of the parameters' change over the checked
      steps), over the larger of that leaf's reference norm and the median
      leaf's.  Leaves whose reference gradient is under a thousandth of the
      median leaf's move by round-off alone and are left out of the change.
    """
    loss = max(abs(a - b) / abs(b) for a, b in zip(got["losses"],
                                                    want["losses"]))
    g_med = statistics.median(want["first_grad"])
    grad = max(abs(a - b) / max(b, g_med)
               for a, b in zip(got["first_grad"], want["first_grad"]))
    moving = [i for i, g in enumerate(want["first_grad"]) if g >= 1e-3 * g_med]
    c_med = statistics.median(want["change"][i] for i in moving)
    change = max(abs(got["change"][i] - want["change"][i])
                 / max(want["change"][i], c_med) for i in moving)
    if len(got["losses"]) != len(want["losses"]):
        loss = math.inf
    return {"loss": loss, "first_grad": grad, "change": change}


def step_tokens_per_s(run) -> float | None:
    """Tokens of every step of the window's whole segments over the time
    from the first segment's start to the last one's end."""
    segs = run.data.get("segments")
    if not segs:
        return None
    steps = sum(s["steps"] for s in segs)
    return steps * run.data["tokens_per_step"] / run.window_s


def gaps_between_segments(run) -> list:
    segs = run.data.get("segments", [])
    return [b["start"] - a["end"] for a, b in zip(segs, segs[1:])]

