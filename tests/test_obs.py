"""The program's spans and compile log (``repro.obs``), read back from a
profiler trace: a serving flow and a training flow on a tiny model, each
through FlowsService -> ComputeProvider -> the engine or the fabric."""

from __future__ import annotations

import glob
import time
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs, obs
from repro.configs.base import TrainConfig
from repro.core.actions import ActionRegistry
from repro.core.engine import PollingPolicy
from repro.core.flows_service import FlowsService
from repro.core.providers import ComputeProvider
from repro.launch.serve import build_serving_flow
from repro.models.model import Model
from repro.serve.engine import ServeEngine
from repro.train.fabric import TrainingFabric

NEW_TOKENS = 4
STEPS = 3
PROGRAM = ("flows.", "journal.", "compute.", "serve.", "train.")


def record(tmp_path, fn):
    """Run ``fn`` under a profiler session; the program's spans of the
    trace as ``(line, start_ns, end_ns, name, stats)``, by start."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    spans = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for k, line in enumerate(plane.lines):
            spans += [(k, e.start_ns, e.start_ns + e.duration_ns, e.name,
                       dict(e.stats)) for e in line.events
                      if e.name.startswith(PROGRAM)]
    return sorted(spans, key=lambda s: s[1])


def within(inner, outer) -> bool:
    return (inner[0] == outer[0] and outer[1] <= inner[1]
            and inner[2] <= outer[2])


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    cfg = configs.get("internlm2-1.8b", smoke=True)
    model = Model(cfg)
    engine = ServeEngine(model, jax.jit(model.init_fn)(jax.random.PRNGKey(0)),
                         max_len=8 + NEW_TOKENS)
    prompts = np.ones((2, 8), np.int32)

    def serve():
        return int(engine.generate(prompts, NEW_TOKENS)["tokens"].size)

    serving, serve_flow = build_serving_flow(serve)
    fabric = TrainingFabric(
        cfg, TrainConfig(total_steps=8, warmup_steps=1, learning_rate=1e-3),
        batch=2, seq_len=16,
        ckpt_dir=str(tmp_path_factory.mktemp("ckpt")))
    registry, compute = ActionRegistry(), ComputeProvider()
    registry.register(compute)
    training = FlowsService(registry, polling=PollingPolicy(
        initial_seconds=0.02, use_callbacks=True))
    reg = fabric.register_all(compute)
    train_flow = training.publish_flow({"StartAt": "Train", "States": {
        "Train": {"Type": "Action", "ActionUrl": "ap://compute",
                  "Parameters": {"endpoint_id": reg["endpoint_id"],
                                 "function_id": reg["functions"]["train_steps"],
                                 "kwargs": {"n_steps": STEPS}},
                  "ResultPath": "$.trained", "End": True}}}).flow_id
    runs = []

    def both():
        for flows, flow_id in ((serving, serve_flow), (training, train_flow)):
            run = flows.run_flow(flow_id, {})
            flows.engine.wait(run.run_id, timeout=300)
            runs.append(run)

    try:
        both()  # compiles every program outside the trace
        runs.clear()
        spans = record(tmp_path_factory.mktemp("trace"), both)
    finally:
        serving.engine.shutdown()
        training.engine.shutdown()
    assert [r.status for r in runs] == ["SUCCEEDED", "SUCCEEDED"]
    return spans, runs


def test_every_layer_has_its_spans(traced):
    spans, _ = traced
    assert set(s[3] for s in spans) == {
        "flows.start", "flows.enter", "flows.dispatch", "flows.finish",
        "flows.complete", "journal.append", "compute.run", "serve.prefill",
        "serve.decode", "serve.pull", "train.sync", "train.batch",
        "train.dispatch"}


def test_one_pull_per_token_after_the_first_and_one_sync_per_step(traced):
    spans, _ = traced
    counts = Counter(s[3] for s in spans)
    assert counts["serve.prefill"] == 1
    assert counts["serve.decode"] == counts["serve.pull"] == NEW_TOKENS - 1
    assert counts["train.sync"] == counts["train.batch"] == STEPS
    assert counts["train.dispatch"] == STEPS
    assert [s[4]["step"] for s in spans if s[3] == "train.batch"] == \
        [s[4]["step"] for s in spans if s[3] == "train.dispatch"]


def test_flow_spans_carry_their_run_and_link_to_the_endpoint(traced):
    spans, runs = traced
    serve_run, train_run = (r.run_id for r in runs)
    for run_id in (serve_run, train_run):
        names = {s[3] for s in spans if s[4].get("run") == run_id}
        assert {"flows.start", "flows.enter", "flows.dispatch",
                "flows.finish", "flows.complete", "journal.append"} <= names
    computes = [s for s in spans if s[3] == "compute.run"
                and s[4]["request"].split(":", 1)[0] in (serve_run, train_run)]
    dispatches = {s[4]["run"]: s for s in spans if s[3] == "flows.dispatch"}
    assert len(computes) == 2
    for c in computes:
        run_id = c[4]["request"].split(":", 1)[0]
        assert dispatches[run_id][4]["request"] == c[4]["request"]
        assert c[4]["queued_ms"] >= 0
    serve_compute, = [c for c in computes
                      if c[4]["request"].startswith(serve_run)]
    train_compute, = [c for c in computes
                      if c[4]["request"].startswith(train_run)]
    # the serving endpoint runs on a worker thread, the training one inline
    assert serve_compute[0] != dispatches[serve_run][0]
    assert within(train_compute, dispatches[train_run])
    assert train_compute[4]["queued_ms"] == 0
    for s in spans:
        if s[3].startswith("serve."):
            assert within(s, serve_compute), s
        if s[3].startswith("train."):
            assert within(s, train_compute), s


def test_no_session_records_nothing(tmp_path):
    def one_span():
        with obs.span("train.sync"):
            pass

    one_span()
    spans = record(tmp_path, one_span)
    one_span()
    # a span outside the session is in no trace: only the session's own
    assert [s[3] for s in spans if s[3] == "train.sync"] == ["train.sync"]


def test_a_fresh_program_is_logged_once_and_a_cached_call_not_at_all():
    x = jnp.arange(7.0)

    def lowered_once(v):
        return v * 3 + 1

    with obs.span("train.dispatch"):  # binds the log where JAX is loaded
        pass
    f = jax.jit(lowered_once)
    t0 = time.time()
    f(x).block_until_ready()
    t1 = time.time()
    f(x).block_until_ready()
    f(x + 1).block_until_ready()
    t2 = time.time()
    assert obs.compiles_between(t0, t1).count("jit(lowered_once)") == 1
    assert "jit(lowered_once)" not in obs.compiles_between(t1, t2)
