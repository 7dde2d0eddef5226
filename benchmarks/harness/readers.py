"""Readers of the per-layer metrics.  Each takes the run's context —
``cell`` (spec.Cell), ``facts`` (what the driver counted and stamped),
``trace`` (trace_reduce.load's dict) and ``device_kind`` — and returns
one number, or None where it finds nothing to read (the harness then
leaves the metric out of the line).  A metric's file may give its reader
keyword arguments under ``args``.  A later PR adds a reader in a new
module and names it in a new layer_metrics/<name>.json.
"""

from __future__ import annotations

import statistics

from benchmarks.harness import flops, trace_reduce
from benchmarks.harness.peaks import peaks_for

RAGGED_KERNEL = "ragged_paged_attention"
PREFILL_MODULE = "jit_fwd"  # jax.jit(build_chunk_forward(...)'s `fwd`)


def _named(*names):
    """Match an XLA op event to a Pallas kernel by its ``pallas_call``
    name: the op is named after the kernel, with XLA's ``.N`` suffix."""
    return lambda op: any(op == n or op.startswith(n + ".") for n in names)


def busy(ctx) -> dict:
    """``trace_reduce.device_busy`` of the run's trace, worked out once (it
    sorts every device op) however many readers ask."""
    if "busy" not in ctx:
        ctx["busy"] = trace_reduce.device_busy(ctx["trace"])
    return ctx["busy"]


def _flash_seconds(trace) -> float:
    """Device time of the flash kernels.  Their ``pallas_call``s carry no
    name (the op is ``jvp__.N`` forward, ``transpose_jvp___.N`` for dq and
    dkv), so they are told by what they are: the train step's Mosaic
    calls, which the driver's check holds to 3 a layer — flash forward,
    dq and dkv and nothing else."""
    return trace_reduce.op_seconds(trace,
                                   lambda op: op in trace["mosaic_ops"])


def compile_s(ctx):
    return ctx["facts"].get("compile_s")


def train_step_ms(ctx):
    epochs = ctx["facts"].get("epoch_seconds")
    if not epochs:
        return None
    return statistics.median(epochs) / ctx["facts"]["steps_per_epoch"] * 1e3


def device_idle_share(ctx):
    return busy(ctx)["idle_share"] * 100.0


def flash_time_share(ctx):
    flash = _flash_seconds(ctx["trace"])
    if flash == 0.0:
        return None
    return flash / busy(ctx)["busy_s"] * 100.0


def flash_roofline_share(ctx):
    """FLOPs the causal flash kernels had to compute in the traced steps
    (fwd + bwd, re-computation not counted) over their device time, as a
    share of the chip's bf16 peak.  The bound is FLOPs."""
    facts = ctx["facts"]
    flash = _flash_seconds(ctx["trace"])
    steps = facts.get("traced_steps")
    if flash == 0.0 or not steps:
        return None
    need = steps * flops.flash_flops_per_step(
        facts["sizes"], facts["batch"], facts["seq_len"])
    peak = peaks_for(ctx["device_kind"])["flops_bf16_per_s"]
    return need / flash / peak * 100.0


def frame_ms_p50(ctx):
    frames = ctx["facts"].get("window_frame_seconds")
    if not frames:
        return None
    return statistics.median(frames) * 1e3


def prefill_device_share(ctx):
    inside = trace_reduce.ops_inside_modules(
        ctx["trace"], lambda m: m.startswith(PREFILL_MODULE + "("))
    if inside == 0.0:
        return None
    return inside / busy(ctx)["busy_s"] * 100.0


def ragged_roofline_share(ctx):
    """Bytes of LIVE K and V the traced frames had to read over the
    ragged kernel's device time, as a share of the chip's HBM
    bandwidth.  The bound is bytes."""
    facts = ctx["facts"]
    kernel = trace_reduce.op_seconds(ctx["trace"], _named(RAGGED_KERNEL))
    lens = facts.get("traced_live_seq_lens")
    if kernel == 0.0 or not lens:
        return None
    need = flops.ragged_live_kv_bytes(lens, facts["sizes"],
                                      facts["pool_itemsize"])
    peak = peaks_for(ctx["device_kind"])["hbm_bytes_per_s"]
    return need / kernel / peak * 100.0


def latency_ms(ctx, stat):
    """A request-latency statistic the serve driver took in the window on
    the host clock: ``stat`` is ``itl_p50_ms``, ``ttft_p50_ms``, ..."""
    return ctx["facts"].get("latency_ms", {}).get(stat)
