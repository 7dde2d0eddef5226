"""Readers for a model whose attention reads only the cached positions a
learned indexer chooses (the Keye cell).  As in readers.py: each takes
the run's context and returns one number, or None where it finds
nothing to read — a program without the scope or the facts, as one
older than the sparse attention has not.

The indexer and the attention over the chosen rows are XLA, not kernels
of their own, so their device time is that of the FRAME's instructions
under a ``jax.named_scope`` (``ff.sparse.index``, ``ff.sparse.attend``):
the ops inside ``jit_frame(`` module events, joined with the frame's
compiled text, as ``window_readers.frame_scope_time_share`` reads them.
"""

from __future__ import annotations

from benchmarks.harness import readers, window_readers
from benchmarks.harness.peaks import peaks_for
from benchmarks.harness.spec import resolve_module

INDEX_SCOPE = "ff.sparse.index"
ATTEND_SCOPE = "ff.sparse.attend"


def frame_scope_seconds(ctx, prefix: str):
    """Device seconds of the frame's instructions whose ``op_name`` holds
    ``prefix``; None where the driver handed no scopes, none is under it,
    or more than ``readers.UNPLACED_LIMIT`` of the frame's time cannot be
    placed."""
    scopes = ctx["facts"].get("scopes")
    if not scopes:
        return None
    by_name = window_readers._frame_seconds_by_name(ctx["trace"])
    total = sum(by_name.values())
    inside = unplaced = 0.0
    for name, sec in by_name.items():
        scope = scopes.get(name)
        if not scope:
            unplaced += sec
        elif prefix in scope:
            inside += sec
    if inside == 0.0 or unplaced > readers.UNPLACED_LIMIT * total:
        return None
    return inside


def _roofline(ctx, prefix: str, bytes_of: str):
    """The configuration's ``work.<bytes_of>`` of the traced rows' live
    lengths over the frame's device seconds under ``prefix``, as a share
    of the chip's HBM bandwidth."""
    facts, config = ctx["facts"], ctx["cell"].config
    need_of = getattr(resolve_module(config["work"]), bytes_of, None)
    lens = facts.get("traced_live_seq_lens")
    if need_of is None or not lens:
        return None
    seconds = frame_scope_seconds(ctx, prefix)
    if seconds is None:
        return None
    need = need_of(config, lens, facts["pool_itemsize"])
    peak = peaks_for(ctx["device_kind"])["hbm_bytes_per_s"]
    return need / seconds / peak * 100.0


def indexer_roofline_share(ctx):
    """Index-key bytes the traced frames' indexer had to read (every
    cached position, every layer) over its device time, as a share of
    HBM bandwidth."""
    return _roofline(ctx, INDEX_SCOPE, "index_key_bytes")


def sparse_attention_roofline_share(ctx):
    """K/V bytes of the chosen rows the traced frames' attention had to
    read over its device time (gather and products), as a share of HBM
    bandwidth."""
    return _roofline(ctx, ATTEND_SCOPE, "selected_kv_bytes")
