"""TPU-idiomatic MoE token dispatch (sort-based).

The reference's Group_by scatters tokens into per-expert buffers with a
CUDA kernel (reference: src/ops/group_by.cu).  A row-wise scatter is
exactly what TPUs are bad at (dynamic HBM writes defeat XLA's tiling),
so the TPU-native formulation inverts it:

1. stable-sort token→expert assignments (XLA sorts are fast on TPU),
2. compute each token's rank within its expert (its capacity slot),
3. scatter only the *token indices* into the [E*cap] slot table — a
   narrow int32 scatter,
4. gather the wide [T, D] rows through the slot table — one big gather,
   which XLA lowers to efficient DMA.

Everything is jnp, so autodiff gives the combine (gather-backward)
for free; the one-hot cumsum alternative is O(T·E) memory, this is
O(T log T).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def dispatch_indices(flat_e: jax.Array, n_experts: int, capacity: int
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Per-token capacity positions + validity + slot->token table.

    flat_e: [T] int32 expert ids in token order.
    Returns (pos [T] int32, valid [T] bool, token_for_slot [E*cap] int32
    where T marks an empty slot).  Position semantics match the
    arrival-order cumsum definition (reference group_by.cc): the i-th
    token routed to expert e gets slot i.
    """
    t = flat_e.shape[0]
    in_range = (flat_e >= 0) & (flat_e < n_experts)  # reference semantics:
    # out-of-range expert ids drop the token (one_hot gave pos=-1 there)
    order = jnp.argsort(flat_e, stable=True)  # token ids grouped by expert
    sorted_e = flat_e[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(n_experts, dtype=flat_e.dtype))
    safe_e = jnp.clip(sorted_e, 0, n_experts - 1)
    ranks = jnp.arange(t, dtype=jnp.int32) - starts[safe_e].astype(jnp.int32)
    pos = jnp.zeros(t, jnp.int32).at[order].set(ranks)  # narrow scatter
    valid = (pos < capacity) & (pos >= 0) & in_range
    slot = (jnp.clip(flat_e, 0, n_experts - 1).astype(jnp.int32) * capacity
            + jnp.clip(pos, 0, capacity - 1))
    # invalid tokens write to a trash slot beyond the table
    slot = jnp.where(valid, slot, n_experts * capacity)
    token_for_slot = jnp.full((n_experts * capacity + 1,), t, jnp.int32)
    token_for_slot = token_for_slot.at[slot].set(
        jnp.arange(t, dtype=jnp.int32), mode="drop"
    )[: n_experts * capacity]
    return pos, valid, token_for_slot


def moe_dispatch(src: jax.Array, flat_e: jax.Array, n_experts: int,
                 capacity: int, k: int = 1
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(src [T / k, D], expert ids [T]) -> (grouped [E, cap, D], pos [T],
    valid [T]): assignment ``i`` is row ``i // k`` of ``src`` (each row's
    ``k`` assignments are consecutive), so no row is copied ``k`` times
    before the gather.  Empty slots are zero rows; differentiable."""
    rows, d = src.shape
    pos, valid, token_for_slot = dispatch_indices(flat_e, n_experts, capacity)
    # an empty slot holds T = rows * k: row index ``rows``, filled with 0
    grouped = src.at[token_for_slot // k].get(mode="fill", fill_value=0)
    return grouped.reshape(n_experts, capacity, d), pos, valid


def held_rows(flat_e: jax.Array, n_held: int, offset: int, rows: int
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """For the experts ``offset .. offset + n_held - 1`` that live here:
    (source [rows] int32, sizes [n_held] int32, load [n_held] int32).
    The assignments to held experts fill the first rows SORTED BY EXPERT
    (arrival order within one): ``source`` names the assignment (index
    into ``flat_e``) of each row, or ``len(flat_e)`` where the row stays
    empty; ``sizes`` says how many consecutive rows each expert has.
    ``rows`` bounds the CHIP, not an expert: one expert may take any
    share of it, and only where all held experts together draw more is
    the tail cut (``sizes`` < ``load``, the assignments each received,
    kept or not).  Assignments to experts that are not held fill
    nothing.  One stable sort and gathers — no scatter."""
    t = flat_e.shape[0]
    local = flat_e.astype(jnp.int32) - offset
    local = jnp.where((local >= 0) & (local < n_held), local, n_held)
    order = jnp.argsort(local, stable=True).astype(jnp.int32)
    bounds = jnp.searchsorted(
        local[order], jnp.arange(n_held + 1, dtype=jnp.int32)).astype(jnp.int32)
    kept = jnp.minimum(bounds, rows)
    row = jnp.arange(rows, dtype=jnp.int32)
    source = jnp.where(row < kept[-1], order[jnp.minimum(row, t - 1)], t)
    return source, kept[1:] - kept[:-1], bounds[1:] - bounds[:-1]
