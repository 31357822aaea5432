"""Median per flow of the control plane's own time, read inside the
program: the summed self time of the flow's ``flows.*`` and
``journal.append`` spans (transitions, dispatch to the provider, journal
writes), beside ``control_ms.score``, which times it from outside."""

from benchmarks.chip.program_spans import transition_self_ms as read  # noqa: F401
