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

from benchmarks.harness import trace_reduce
from benchmarks.harness.peaks import peaks_for
from benchmarks.harness.spec import resolve_module

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


def _attention_seconds(ctx) -> float:
    """Device time of the configuration's attention kernels: the ops
    named after the ``pallas_call``s its ``harness.attention_kernels``
    lists (training: flash forward, dq and dkv; serving: the ragged
    paged kernel), not every Mosaic call of the program."""
    names = ctx["cell"].config["harness"]["attention_kernels"]
    return trace_reduce.op_seconds(ctx["trace"], _named(*names))


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
    flash = _attention_seconds(ctx)
    if flash == 0.0:
        return None
    return flash / busy(ctx)["busy_s"] * 100.0


def flash_roofline_share(ctx):
    """FLOPs the attention kernels had to compute in the traced steps
    (the configuration's ``work`` module: fwd + bwd, re-computation not
    counted) over their device time, as a share of the chip's bf16 peak.
    The bound is FLOPs."""
    facts, config = ctx["facts"], ctx["cell"].config
    flash = _attention_seconds(ctx)
    steps = facts.get("traced_steps")
    if flash == 0.0 or not steps:
        return None
    need = steps * resolve_module(config["work"]).attention_kernel_flops(
        config, facts["batch"], facts["seq_len"])
    peak = peaks_for(ctx["device_kind"])["flops_bf16_per_s"]
    return need / flash / peak * 100.0


UNPLACED_LIMIT = 0.10  # of busy time; past it a scope's share is not given


def scope_seconds(ctx) -> dict:
    """The traced device seconds by the ``op_name`` of the compiled
    program's instruction of that name (``facts["scopes"]``, from
    ``device.scopes_of``), worked out once a run: ``{"by_scope":
    {op_name: seconds}, "unplaced_s": seconds}``.  An instruction is NOT
    placed where the compiled text has no such name, gives it another
    family (``facts["scope_families"]``: the event came from another
    program) or no ``op_name``.  None where the driver handed no
    scopes."""
    if "scope_seconds" not in ctx:
        scopes = ctx["facts"].get("scopes")
        if not scopes:
            return None
        families = ctx["facts"]["scope_families"]
        by_scope, unplaced = {}, 0.0
        for name, sec in trace_reduce.seconds_by_name(ctx["trace"]).items():
            scope = scopes.get(name)
            if not scope or families[name] != ctx["trace"]["families"][name]:
                unplaced += sec
            else:
                by_scope[scope] = by_scope.get(scope, 0.0) + sec
        ctx["scope_seconds"] = {"by_scope": by_scope, "unplaced_s": unplaced}
    return ctx["scope_seconds"]


def scope_unplaced_share(ctx):
    """Per cent of busy time the join could not place; None without
    scopes."""
    joined = scope_seconds(ctx)
    if joined is None:
        return None
    return joined["unplaced_s"] / busy(ctx)["busy_s"] * 100.0


def scope_time_share(ctx, prefixes):
    """Device seconds of the traced instructions whose ``op_name`` holds
    one of ``prefixes`` (a ``jax.named_scope``: ``ff.exit``, ``ff.moe.``)
    over busy seconds, per cent.  None where the driver handed no
    scopes, where no instruction is under the scope, or where more than
    ``UNPLACED_LIMIT`` of busy time belongs to instructions the join
    cannot place: a share of a part of the step is not reported."""
    joined = scope_seconds(ctx)
    if joined is None:
        return None
    inside = sum(sec for scope, sec in joined["by_scope"].items()
                 if any(p in scope for p in prefixes))
    busy_s = busy(ctx)["busy_s"]
    if inside == 0.0 or joined["unplaced_s"] > UNPLACED_LIMIT * busy_s:
        return None
    return inside / busy_s * 100.0


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
    """Bytes of LIVE K and V the traced frames had to read (cached
    tokens attended to x the ``work`` module's bytes a cached token) over
    the attention kernel's device time, as a share of the chip's HBM
    bandwidth.  The bound is bytes."""
    facts, config = ctx["facts"], ctx["cell"].config
    kernel = _attention_seconds(ctx)
    lens = facts.get("traced_live_seq_lens")
    if kernel == 0.0 or not lens:
        return None
    need = float(sum(lens)) * resolve_module(
        config["work"]).cached_token_bytes(config, facts["pool_itemsize"])
    peak = peaks_for(ctx["device_kind"])["hbm_bytes_per_s"]
    return need / kernel / peak * 100.0


def latency_ms(ctx, stat):
    """A request-latency statistic the serve driver took in the window on
    the host clock: ``stat`` is ``itl_p50_ms``, ``ttft_p50_ms``, ..."""
    return ctx["facts"].get("latency_ms", {}).get(stat)
