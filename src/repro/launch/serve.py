"""Serving launcher: batched generation over a model.

    PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b \\
        --requests 16 --max-new 16

``--smoke`` (the default) serves the reduced config, ``--full`` the
published one.  Requests are accumulated by the BatchAccumulator
(arrival-window batching) and served in generation batches; per-request
results and aggregate throughput are printed.  ``--via-flows`` routes each
generation batch through a published flow (Compute action on a thread-mode
endpoint), demonstrating analysis-as-a-service (paper §2.1.4) over the
serving fabric; the launcher exits non-zero unless the run SUCCEEDED.
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import configs
from repro.core.actions import ActionRegistry
from repro.core.engine import PollingPolicy
from repro.core.flows_service import FlowsService
from repro.core.providers import ComputeProvider
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import Model
from repro.serve.engine import BatchAccumulator, ServeEngine


def serving_flow_definition(eid: str, fid: str, kwargs: dict | None = None
                            ) -> dict:
    """One Action state that runs a serving function on a compute endpoint."""
    return {
        "StartAt": "Serve",
        "States": {"Serve": {
            "Type": "Action", "ActionUrl": "ap://compute",
            "Parameters": {"endpoint_id": eid, "function_id": fid,
                           "kwargs": kwargs or {}},
            "ResultPath": "$.served", "End": True,
        }},
    }


def build_serving_flow(serve_fn, kwargs: dict | None = None):
    """A FlowsService whose published flow calls ``serve_fn(**kwargs)`` on a
    thread-mode compute endpoint (the engine's dispatcher never blocks on
    the device).  ``kwargs`` may select from the run's input with ``.$``
    keys.  Returns (flows, flow_id)."""
    registry = ActionRegistry()
    compute = ComputeProvider()
    registry.register(compute)
    flows = FlowsService(
        registry,
        polling=PollingPolicy(initial_seconds=0.02, use_callbacks=True),
    )
    eid = compute.register_endpoint("serving", mode="thread", max_workers=1)
    fid = compute.register_function(serve_fn, name="serve_batch")
    record = flows.publish_flow(serving_flow_definition(eid, fid, kwargs),
                                title="Serve batch")
    return flows, record.flow_id


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--arch", default="internlm2-1.8b")
    parser.add_argument("--smoke", action="store_true", default=True)
    parser.add_argument("--full", dest="smoke", action="store_false")
    parser.add_argument("--requests", type=int, default=16)
    parser.add_argument("--prompt-len", type=int, default=16)
    parser.add_argument("--max-new", type=int, default=16)
    parser.add_argument("--max-batch", type=int, default=8)
    parser.add_argument("--via-flows", action="store_true")
    args = parser.parse_args()

    enable_compile_cache()
    cfg = configs.get(args.arch, smoke=args.smoke)
    model = Model(cfg)
    params = jax.jit(model.init_fn)(jax.random.PRNGKey(0))
    engine = ServeEngine(model, params,
                         max_len=args.prompt_len + args.max_new)
    accum = BatchAccumulator(engine, max_batch=args.max_batch)

    rng = np.random.default_rng(0)
    t0 = time.time()
    for _ in range(args.requests):
        accum.submit(rng.integers(0, cfg.vocab_size, size=args.prompt_len))

    if args.via_flows:
        flows, flow_id = build_serving_flow(
            lambda: len(accum.flush(args.max_new)))
        run = flows.run_flow(flow_id, {})
        flows.engine.wait(run.run_id, timeout=600)
        print(f"flow run {run.run_id}: {run.status}")
        if run.status != "SUCCEEDED":
            print(f"serving flow failed: {run.error}")
            return 1
        results_count = run.context["served"]["details"]["results"][0]
    else:
        results = accum.flush(args.max_new)
        results_count = len(results)

    dt = time.time() - t0
    print(f"served {results_count} requests in {dt:.2f}s "
          f"({engine.stats['tokens_generated']} tokens, "
          f"{engine.stats['tokens_generated']/max(dt,1e-9):.1f} tok/s)")
    print("engine stats:", engine.stats)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
