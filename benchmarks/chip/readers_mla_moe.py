"""Arithmetic of the readers of the latent-attention MoE serving cell's
per-layer metrics (``metrics/*.moe_decode.py``): the counts of
``flops_mla_moe``, the engine's expert counter and the device trace."""

from __future__ import annotations

from benchmarks.chip import flops_mla_moe, xplane
from benchmarks.chip.peaks import peaks
from benchmarks.chip.readers import DECODE, PREFILL, serve_shapes_of


def mean_position(mix: dict) -> float:
    """The mix's mean decode position: the mean prompt length plus half
    of the decode steps that follow the prefill."""
    prompt = sum(int(s) * w for s, w in mix["prompt_buckets"].items())
    return prompt + (mix["max_new_tokens"] - 2) / 2


def _programs(run, pattern: str) -> list | None:
    window = run.trace_window()
    if window is None or not run.trace.devices:
        return None
    return xplane.programs_matching(run.trace.devices[0], pattern, *window)


def decode_hbm_share(run, cell) -> float | None:
    """Least bytes of a decode step, the held experts at the share the
    engine's counter saw hit, over the median decode program's device
    time at the chip's HBM bandwidth, percent."""
    share = flops_mla_moe.hit_share(run.data.get("engine_stats", {}),
                                    cell.model)
    runs = _programs(run, DECODE)
    if share is None or not runs:
        return None
    mix = cell.traffic
    least = flops_mla_moe.decode_least_bytes(cell.model, mix["batch"],
                                             mean_position(mix), share)
    t = xplane.median(p[1] - p[0] for p in runs)
    return 100.0 * least / (t * peaks(run.device_kind).hbm_bytes_per_s)


def prefill_mfu(run, cell) -> float | None:
    """Model FLOPs of the window's prefill programs over their device time
    at the chip's bf16 peak, percent."""
    runs = _programs(run, PREFILL)
    if not runs:
        return None
    shaped = serve_shapes_of(run, runs)
    if not shaped:
        return None
    work = sum(flops_mla_moe.prefill_flops(cell.model, b, s)
               for _, s, b in shaped)
    busy = sum(p[1] - p[0] for p, _, _ in shaped)
    return 100.0 * work / (busy * peaks(run.device_kind).bf16_flops)


#: the named scopes of the latent-attention stack, by the first part of an
#: ``op_name`` path that names one.  The TPU's kernels for
#: ``jax.lax.ragged_dot`` (the held experts' matmuls) lose the scope: their
#: ``op_name`` is the kernel's own (``ragged-dot-none``), and only the
#: expert layer calls ``ragged_dot``
SCOPES = {"mla": lambda part: part == "mla",
          "moe": lambda part: part.startswith(("moe.", "ragged-dot"))}


def scope_hbm_share(run, cell, label: str) -> float | None:
    """Least bytes of a decode step's work under scope ``label`` (``mla``:
    every layer's attention weights and latent cache up to the mix's mean
    position; ``moe``: routers, shared experts and the held experts at the
    counter's hit share) over the median device time of the decode
    program's operations under that scope at the chip's HBM bandwidth,
    percent."""
    from benchmarks.chip import scopes

    mix, cfg = cell.traffic, cell.model
    if label == "mla":
        least = flops_mla_moe.mla_least_bytes(cfg, mix["batch"],
                                              mean_position(mix))
    else:
        share = flops_mla_moe.hit_share(run.data.get("engine_stats", {}),
                                        cfg)
        if share is None:
            return None
        least = flops_mla_moe.moe_least_bytes(cfg, share)
    times = [t[label] for t in scopes.scope_seconds(run, cell, DECODE, SCOPES)
             if t.get(label)]
    if not times:
        return None
    t = xplane.median(times)
    return 100.0 * least / (t * peaks(run.device_kind).hbm_bytes_per_s)
