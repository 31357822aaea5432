"""Serving cells: flows of ``repro.launch.serve.build_serving_flow`` over a
``ServeEngine``, driven closed-loop (clients that each wait for their flow)
or open-loop (flows sent at seeded times).

One flow serves one batch of equal-length prompts: ``FlowsService.run_flow``
-> the flow's Action state -> ``ComputeProvider`` (thread endpoint) -> the
benchmark's registered function -> ``ServeEngine.generate`` -> the jitted
prefill and decode programs.  The window's results are checked against the
plain reference once the window has closed and the program's state is
freed.
"""

from __future__ import annotations

import math
import sys
import threading
import time

import numpy as np

from benchmarks.chip import traffic as tr
from benchmarks.chip.harness import RunResult, Tracer, free, log, memory_peak

#: flow indices of the warm-up flows (one per prompt length), apart from
#: the window's flows 0, 1, ...
WARM_BASE = 10**9
#: how long after the window's close a flow may take to complete
DRAIN_S = 60.0


class FlowLog:
    """What the benchmark saw of each flow, by flow index (host clock)."""

    def __init__(self):
        self.lock = threading.Lock()
        self.flows: dict[int, dict] = {}

    def new(self, i: int, **kw) -> dict:
        rec = {"flow": i, **kw}
        with self.lock:
            self.flows[i] = rec
        return rec


def run(cell, seed: int, seconds: float, trace: bool, t_process: float,
        loop: str) -> RunResult:
    import jax

    from repro.configs.base import ModelConfig
    from repro.launch.serve import build_serving_flow
    from repro.models.model import Model
    from repro.serve.engine import ServeEngine

    cfg, mix, ref = cell.model, cell.traffic, cell.reference
    batch, new_tokens = mix["batch"], mix["max_new_tokens"]
    vocab = cfg["vocab_size"]

    params = ref.init_params(cfg, seed)
    engine = ServeEngine(Model(ModelConfig(**cfg)), params,
                         max_len=mix["max_len"])
    params = None
    # the order of prompt lengths and arrivals: the mix's own, where it
    # pins one, else the seed's; token ids and weights always the seed's
    order_seed = mix.get("order_seed", seed)
    lengths = tr.prompt_lengths(order_seed, mix["prompt_buckets"],
                                mix["bucket_block"], 100_000)
    warm_lengths = sorted({int(k) for k in mix["prompt_buckets"]})
    flog = FlowLog()
    outputs: dict[int, np.ndarray] = {}

    def length_of(i: int) -> int:
        return warm_lengths[i - WARM_BASE] if i >= WARM_BASE else lengths[i]

    def serve_batch(flow: int) -> dict:
        rec = flog.flows[flow]
        s = length_of(flow)
        rec["fn_start"] = time.time()
        with jax.profiler.TraceAnnotation(f"bench:serve S={s} B={batch}"):
            out = engine.generate(tr.prompts(seed, flow, batch, s, vocab),
                                  max_new_tokens=new_tokens)
        rec["fn_end"] = time.time()
        outputs[flow] = out["tokens"]
        return {"requests": int(out["tokens"].shape[0]),
                "new_tokens": int(out["tokens"].size)}

    flows, flow_id = build_serving_flow(serve_batch, {"flow.$": "$.flow"})

    def submit(i: int, scheduled: float):
        rec = flog.new(i, prompt_len=length_of(i), scheduled=scheduled)
        with jax.profiler.TraceAnnotation("bench:submit"):
            rec["submitted"] = time.time()
            rec["run"] = flows.run_flow(flow_id, {"flow": i})
        return rec

    try:
        # warm every shape this traffic uses, through the whole path
        for k in range(len(warm_lengths)):
            rec = submit(WARM_BASE + k, time.time())
            rec["run"].done.wait(600)
            if rec["run"].status != "SUCCEEDED":
                raise RuntimeError(f"warm-up flow failed: {rec['run'].error}")
        tracer = Tracer(cell, trace)
        t0 = time.time()
        setup_s = t0 - t_process
        with tracer.window():
            if loop == "closed":
                t_end = _closed_loop(submit, mix["clients"], t0, seconds,
                                     flog)
            else:
                t_end = _open_loop(submit, order_seed, mix["rate_per_s"], t0,
                                   seconds)
        summary = tracer.finish()
        window_flows = [r for i, r in sorted(flog.flows.items())
                        if i < WARM_BASE]
        deadline = time.time() + DRAIN_S
        for r in window_flows:
            r["run"].done.wait(max(deadline - time.time(), 0.0))
    finally:
        flows.engine.shutdown()
    peak = memory_peak()
    stats = dict(engine.stats)
    free(engine.params)
    engine = None

    for r in window_flows:
        run_ = r.pop("run")
        r["status"] = run_.status
        for ev in run_.events:
            if ev["code"] == "StateEntered" and "entered" not in r:
                r["entered"] = ev["time"]
            elif ev["code"] == "FlowCompleted":
                r["completed"] = ev["time"]
        r["ok"] = run_.status == "SUCCEEDED" and _well_formed(
            outputs.get(r["flow"]), batch, new_tokens, vocab)
    failed = sum(not r["ok"] for r in window_flows)
    done = [r["flow"] for r in window_flows if r["ok"]]
    log(f"serve[{cell.name}]: {len(window_flows)} flows in the window, "
        f"{failed} failed; engine stats {stats}")
    if loop == "open":
        late = [r["submitted"] - r["scheduled"] for r in window_flows]
        log(f"open loop: sender lateness median {np.median(late) * 1e3:.3f} "
            f"ms, max {max(late) * 1e3:.3f} ms")

    # the reference, after the program's state is freed
    t_ref = time.time()
    check = mix["check"]
    longest = max(done, key=lambda i: (length_of(i), -i), default=None)
    sample = tr.sample(seed, done, check["flows"],
                       must=[longest] if longest is not None else [])
    gap = _reference_gap(cell, seed, sample, outputs, length_of)
    log(f"reference: {len(sample)} flows x {batch} rows x {new_tokens} "
        f"served tokens compared in {time.time() - t_ref:.3f} s")
    limit = cell.limits["logit_gap"]
    checks = {"logit_gap": (gap, limit)}
    correct = (failed == 0 and bool(sample) and math.isfinite(gap)
               and gap <= limit)
    return RunResult(
        setup_s=setup_s, t0=t0, t_end=t_end, seconds=seconds,
        attempted=len(window_flows), failed=failed, correct=correct,
        checks=checks, memory_peak_bytes=peak, trace=summary,
        device_kind=jax.devices()[0].device_kind,
        data={"flows": window_flows, "engine_stats": stats,
              "batch": batch, "new_tokens": new_tokens,
              "sample": {i: (length_of(i), outputs[i]) for i in sample}},
    )


def _well_formed(tokens, batch: int, new_tokens: int, vocab: int) -> bool:
    return (tokens is not None and tokens.shape == (batch, new_tokens)
            and int(tokens.min()) >= 0 and int(tokens.max()) < vocab)


def _closed_loop(submit, clients: int, t0: float, seconds: float,
                 flog: FlowLog) -> float:
    """``clients`` threads, each sending its next flow when its last one
    completes, until ``seconds`` have passed.  The window ends at the first
    completion at or after that moment, so it holds whole flows only."""
    counter = iter(range(10**9))
    lock = threading.Lock()
    stop = t0 + seconds
    errors: list[BaseException] = []

    def client():
        try:
            while True:
                with lock:
                    if time.time() >= stop:
                        return
                    i = next(counter)
                rec = submit(i, time.time())
                rec["run"].done.wait(DRAIN_S + seconds)
        except BaseException as e:  # recorded, re-raised by the caller
            errors.append(e)

    threads = [threading.Thread(target=client, name=f"bench-client-{c}")
               for c in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(seconds + 2 * DRAIN_S)
    if errors:
        raise errors[0]
    if any(t.is_alive() for t in threads):
        raise RuntimeError("a closed-loop client never finished")
    return _first_completion_after(flog, stop)


def _first_completion_after(flog: FlowLog, stop: float) -> float:
    times = []
    for r in flog.flows.values():
        if r["flow"] >= WARM_BASE or "run" not in r:
            continue
        for ev in r["run"].events:
            if ev["code"] == "FlowCompleted" and ev["time"] >= stop:
                times.append(ev["time"])
    if not times:
        raise RuntimeError("no flow completed after the window's close")
    return min(times)


def _open_loop(submit, seed: int, rate: float, t0: float, seconds: float
               ) -> float:
    """Send flows at the seeded times from one thread; the window is the
    ``seconds`` the sends span."""
    for i, offset in enumerate(tr.arrival_offsets(seed, rate, seconds)):
        due = t0 + offset
        wait = due - time.time()
        if wait > 0:
            time.sleep(wait)
        submit(i, due)
    end = t0 + seconds
    if time.time() < end:
        time.sleep(end - time.time())
    return end


def _reference_gap(cell, seed: int, sample: list, outputs: dict, length_of
                   ) -> float:
    """The widest gap by which a served token's logit lies below the
    reference's best, over every served token of the sampled flows."""
    return _widest_gap(cell, seed, {i: (length_of(i), outputs[i])
                                     for i in sample}, control=False)


def control_gap(cell, seed: int, run: RunResult) -> float:
    """The same reading for the control: at the same positions of the same
    prompts and served tokens, the gap of the token that the fp8 control
    of the reference puts first."""
    return _widest_gap(cell, seed, run.data["sample"], control=True)


def _widest_gap(cell, seed: int, sample: dict, control: bool) -> float:
    import jax.numpy as jnp

    ref, cfg = cell.reference, cell.model
    params = ref.init_params(cfg, seed)
    worst = -math.inf
    try:
        for i, (s, served) in sample.items():
            prompt = tr.prompts(seed, i, served.shape[0], s, cfg["vocab_size"])
            seq = jnp.asarray(np.concatenate([prompt, served[:, :-1]], axis=1))
            if control:
                gaps = ref.control_gaps(ref.items(cfg), params, seq, s - 1)
            else:
                gaps = ref.served_gaps(ref.items(cfg), params, seq, s - 1,
                                       jnp.asarray(served))
            worst = max(worst, float(jnp.max(gaps)))
    finally:
        free(params)
    what = "control" if control else "reference"
    print(f"{what} gap over {len(sample)} flows: {worst}", file=sys.stderr)
    return worst
