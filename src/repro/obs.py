"""Spans and a compile log, on the JAX profiler's clock.

Each layer marks its work with a span named ``<layer>.<what>``: ``flows.*``
(the flow engine), ``journal.append``, ``compute.run`` (a compute endpoint
running a registered function), ``serve.*`` (the serving engine's host
loop) and ``train.*`` (the training fabric's host loop).  A span is a
``jax.profiler.TraceAnnotation``: it records only while a profiler session
runs (``jax.profiler.trace``), on the host plane of the same trace as the
device's programs, and its keyword metadata becomes the event's stats,
formatted only then.  The control plane never imports JAX; until the
process has loaded it no session can be recording, and a span is a no-op.

The compile log keeps ``(start, end, name)``, on the host clock
(``time.time()``), of every program JAX lowers, whether or not the
persistent compilation cache then supplies its binary.  It starts when
this module is imported after JAX, or else at the first span after JAX
is loaded.
"""

from __future__ import annotations

import collections
import contextlib
import sys
import threading

#: fired once per lowering of a new program, never on a cached call
_LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

_NULL = contextlib.nullcontext()
_lock = threading.Lock()
_annotation = None
_compiles: collections.deque = collections.deque(maxlen=10_000)


def span(name: str, **meta):
    """A span named ``name``; ``meta`` (strings and numbers) become its
    stats.  Spans nest with the enclosing span of their thread."""
    if _annotation is None and ("jax" not in sys.modules or not _attach()):
        return _NULL
    return _annotation(name, **meta)


def compiles_between(t0: float, t1: float) -> list[str]:
    """Names of the programs whose lowering started in ``[t0, t1]``."""
    return [name for start, _, name in list(_compiles) if t0 <= start <= t1]


def _attach() -> bool:
    """Bind the spans and the compile log to JAX, once it is loaded."""
    global _annotation
    jax = sys.modules.get("jax")
    profiler = getattr(jax, "profiler", None)
    monitoring = getattr(jax, "monitoring", None)
    if profiler is None or monitoring is None:
        return False
    with _lock:
        if _annotation is None:
            monitoring.register_event_time_span_listener(_on_time_span)
            _annotation = profiler.TraceAnnotation
    return True


def _on_time_span(event: str, start: float, end: float, **meta) -> None:
    if event == _LOWERING_EVENT:
        _compiles.append((start, end, meta.get("fun_name", "")))


_attach()
