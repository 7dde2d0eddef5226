"""Readers for a model whose attention layers are sliding-window or
global and whose feed-forward is a layer of experts on the DECODE path
(PR 35).  As in readers.py: each takes the run's context and returns one
number, or None where it finds nothing to read — a program without the
kernel, the scope or the facts, as the parent of the PR that adds them
has not.
"""

from __future__ import annotations

import bisect

from benchmarks.harness import readers, trace_reduce
from benchmarks.harness.peaks import peaks_for
from benchmarks.harness.spec import resolve_module

FRAME_MODULE = "jit_frame"  # jax.jit(compiled_decode_step's `frame`)


def window_paged_roofline_share(ctx):
    """Bytes of K and V the traced frames' decode kernels had to read —
    the configuration's ``work.attention_kernel_bytes`` of the traced
    rows' live lengths: a global layer reads a row's whole cache, a
    window layer at most its window — over the kernels' device time, as
    a share of the chip's HBM bandwidth.  The bound is bytes.
    (``readers.ragged_roofline_share`` prices EVERY cached token in
    every layer and would read past 100 % here.)"""
    facts, config = ctx["facts"], ctx["cell"].config
    work = resolve_module(config["work"])
    lens = facts.get("traced_live_seq_lens")
    if not lens or not hasattr(work, "attention_kernel_bytes"):
        return None
    kernel = readers._attention_seconds(ctx)
    if kernel == 0.0:
        return None
    need = work.attention_kernel_bytes(config, lens, facts["pool_itemsize"])
    peak = peaks_for(ctx["device_kind"])["hbm_bytes_per_s"]
    return need / kernel / peak * 100.0


def _frame_seconds_by_name(trace: dict) -> dict:
    """Device seconds of every instruction name over the ops that START
    inside a ``jit_frame(`` module event, mean over devices."""
    total = {}
    for dev in trace["devices"].values():
        spans = trace_reduce.merged_intervals(
            m for m in dev["modules"] if m[0].startswith(FRAME_MODULE + "("))
        starts = [a for a, _ in spans]
        for name, t0, dur in dev["ops"]:
            k = bisect.bisect_right(starts, t0) - 1
            if k >= 0 and t0 < spans[k][1]:
                total[name] = total.get(name, 0.0) + dur
    k = len(trace["devices"])
    return {name: sec / k for name, sec in total.items()}


def frame_scope_time_share(ctx, prefixes):
    """Device seconds of the FRAME's instructions whose ``op_name`` holds
    one of ``prefixes`` (a ``jax.named_scope``, or the name XLA gives a
    kernel that loses its scope: ``ragged-dot``) over the frame's device
    seconds, per cent.  The traced tail of a serving run also holds the
    prefill chunk's program, whose instruction names collide with the
    frame's by number, so only the ops that ran INSIDE a ``jit_frame(``
    module event are read, and joined with the frame's own text
    (``facts["scopes"]``).  The trace's ``families`` are NOT compared, as
    ``readers.scope_seconds`` does to tell programs apart: they keep the
    FIRST event of a name, the chunk's wherever the tail opens inside an
    admission, and the module events already say whose an op is.  None
    where the driver handed no scopes, the trace holds no such module or
    no instruction under the scope, or more than
    ``readers.UNPLACED_LIMIT`` of the frame's time cannot be placed (no
    such name in the text, no ``op_name``)."""
    scopes = ctx["facts"].get("scopes")
    if not scopes:
        return None
    by_name = _frame_seconds_by_name(ctx["trace"])
    total = sum(by_name.values())
    inside = unplaced = 0.0
    for name, sec in by_name.items():
        scope = scopes.get(name)
        if not scope:
            unplaced += sec
        elif any(p in scope for p in prefixes):
            inside += sec
    if inside == 0.0 or unplaced > readers.UNPLACED_LIMIT * total:
        return None
    return inside / total * 100.0
