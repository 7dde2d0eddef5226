"""Ulysses-style all-to-all sequence parallelism.

The second of the two first-class sequence/context-parallel schemes
(the other is the ring — parallel/ring_attention.py; the reference has
neither, SURVEY.md §5: MHA's seq dim is never partitioned,
substitution.cc:2599-2654).  Instead of rotating K/V around a ring,
two ``all_to_all`` collectives re-shard the heads: q/k/v arrive
sharded on the SEQUENCE dim, the first exchange makes every device
hold the FULL sequence for ``H/n`` heads, full-sequence attention runs
locally (the Pallas flash kernel inside), and the inverse exchange
restores sequence sharding on the output.

Trade-off vs the ring (DeepSpeed-Ulysses, arXiv:2309.14509): the ring
moves the K and V shards ``n-1`` hops each — ``2*(n-1)/n`` of the full
K/V tensors per device, overlapped with per-step compute — while
Ulysses moves ``(n-1)/n`` of each of q/k/v/out exactly once, with no
overlap but over the fatter bisection (ICI all-to-all).  Ulysses
requires ``num_heads % n == 0`` and holds the full sequence per device
for its head slice (O(S·H/n) activations instead of the ring's
O(S/n·H) — same product, different shape; causal masking needs no
zigzag re-ordering because every device sees the whole sequence).
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _full_attention(q, k, v, causal: bool, scale: float):
    """Full-sequence attention for the local head slice — the flash
    kernel where ``flash_profitable`` says so (the rule
    ops/attention.py applies), XLA's fused attention otherwise."""
    from flexflow_tpu.kernels.flash_attention import (
        _xla_attention,
        flash_attention,
        flash_profitable,
    )

    if flash_profitable(q.shape[1], k.shape[1]):
        return flash_attention(q, k, v, causal=causal, scale=scale)
    return _xla_attention(q, k, v, causal, scale)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh,
    seq_axis: "str | Tuple[str, ...]",
    causal: bool = False,
    scale: Optional[float] = None,
    batch_axes: Tuple[str, ...] = (),
) -> jax.Array:
    """Global-view Ulysses attention: q/k/v [B, S, H, D] (self-attention:
    Sk == Sq) sharded on dim 1 over ``seq_axis`` of ``mesh`` (and
    optionally dim 0 over ``batch_axes``); returns [B, S, H, D] with the
    same sharding.  Composable under jit (shard_map inside).  Requires
    ``H % n == 0`` for the head exchange."""
    from flexflow_tpu.comm.compat import shard_map

    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    assert q.shape[1] == k.shape[1], "ulysses requires Sk == Sq"
    axes = (seq_axis,) if isinstance(seq_axis, str) else tuple(seq_axis)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    if n == 1:
        return _full_attention(q, k, v, causal, scale)
    h = q.shape[2]
    assert h % n == 0, (
        f"ulysses head exchange needs num_heads ({h}) divisible by the "
        f"seq degree ({n}); use ring attention otherwise"
    )

    b_spec = None
    if batch_axes:
        b_spec = batch_axes[0] if len(batch_axes) == 1 else tuple(batch_axes)
    spec = P(b_spec, axes, None, None)

    def local_fn(q_l, k_l, v_l):
        # [B, S/n, H, D] -> exchange -> [B, S, H/n, D]
        def seq_to_head(x):
            return jax.lax.all_to_all(
                x, axes, split_axis=2, concat_axis=1, tiled=True
            )

        qh = seq_to_head(q_l)
        kh = seq_to_head(k_l)
        vh = seq_to_head(v_l)
        out = _full_attention(qh, kh, vh, causal, scale)
        # [B, S, H/n, D] -> inverse exchange -> [B, S/n, H, D]
        return jax.lax.all_to_all(
            out, axes, split_axis=1, concat_axis=2, tiled=True
        )

    return shard_map(
        local_fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
    )(q, k, v)
