"""Median time an action waited in the compute endpoint's queue for its
worker (``queued_ms`` of ``compute.run``), beside
``endpoint_wait_ms.score``, which also holds the engine's dispatch."""

from benchmarks.chip.program_spans import endpoint_queue_ms as read  # noqa: F401
