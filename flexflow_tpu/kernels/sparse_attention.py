"""Learned sparse attention on a paged cache (DeepSeek-V3.2's indexer, as
Keye-VL-2.0 publishes it in ``sa_config``): a small multi-head scorer
reads one cached INDEX KEY a position, each query keeps the ``top_k``
positions of largest score, and attention reads those rows alone.

    I[t, s] = sum_j w[t, j] * ReLU(qI[t, j] . kI[s]) * scale     (float32)
    S_t     = the top_k s <= t of largest I[t, s] (all s <= t when fewer)
    o_t     = softmax_{s in S_t}(q_t . k_s / sqrt(D)) v_s

The three passes carry ``jax.named_scope``s a device trace is charged
by: ``ff.sparse.index`` (the scores), ``ff.sparse.select`` (the choice)
and ``ff.sparse.attend`` (reading the chosen rows and attending).  They
are XLA, not Pallas: the scorer is a pair of matmuls over a gathered
block of index pages, the choice an exact selection, the attend a row
gather — shapes XLA:TPU already lowers to DMA-bound gathers and MXU
products.

Selection is EXACT at token level: ``lax.top_k`` over each query's
scores.  ``topk_indices`` (a decode frame: one query a row) hands back
the chosen positions; ``topk_mask`` (a prefill chunk: a [C, context]
block of scores that only needs the chosen SET) reads the set off the
k-th value and its position — one compare over the block, no gather or
scatter of the k positions.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

INDEX_SCOPE = "ff.sparse.index"
SELECT_SCOPE = "ff.sparse.select"
ATTEND_SCOPE = "ff.sparse.attend"


def index_scores(q_index, w_index, keys, scale: float):
    """``q_index`` [..., Q, H, Di] (rounded to the keys' dtype),
    ``w_index`` [..., Q, H] float32, ``keys`` [..., R, F·Di] — F positions a row, position r·F + j in
    lanes [j·Di, (j + 1)·Di) of row r (``fold_rows``) — -> [..., Q, R·F]
    float32: ``sum_h w[h] * ReLU(q[h] . k) * scale``, the dot products
    accumulated in float32.  With F > 1 each query head is laid
    block-diagonally over the F lane groups, so one product scores a
    row's F positions and no operand is narrower than its row."""
    di = q_index.shape[-1]
    fold = keys.shape[-1] // di
    q_index = q_index.astype(keys.dtype)  # the pool's dtype: bf16 products
    with jax.named_scope(INDEX_SCOPE):
        eye = jnp.eye(fold, dtype=q_index.dtype)
        q = jnp.einsum("...hd,jk->...hjkd", q_index, eye).reshape(
            *q_index.shape[:-1], fold, fold * di)
        dots = jnp.einsum("...qhjl,...rl->...qhrj", q, keys,
                          preferred_element_type=jnp.float32)
        dots = dots.reshape(*dots.shape[:-2], -1)
        return jnp.einsum("...qhs,...qh->...qs", jax.nn.relu(dots),
                          w_index.astype(jnp.float32)) * scale


def fold_rows(width: int) -> int:
    """Positions a pool row of index keys holds: as many ``width``-wide
    keys as fill the TPU's 128 lanes — a [P, page, 64] pool would be
    stored page-minor and turned over on every write and read."""
    assert 128 % width == 0, width
    return 128 // width


def scatter_folded(pool, page, slot, rows):
    """Write ``rows`` [..., Di] at (``page``, ``slot``) [...] of a folded
    pool [P, page_size / F, F·Di]: row ``slot // F``, lanes from
    ``(slot % F)·Di`` — one ``Di``-wide window an update; a page past the
    pool is dropped."""
    di = rows.shape[-1]
    fold = pool.shape[-1] // di
    idx = jnp.stack([page, slot // fold, (slot % fold) * di], axis=-1)
    dnums = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(1,), inserted_window_dims=(0, 1),
        scatter_dims_to_operand_dims=(0, 1, 2))
    return jax.lax.scatter(
        pool, idx.reshape(-1, 3).astype(jnp.int32),
        rows.reshape(-1, di).astype(pool.dtype), dnums,
        indices_are_sorted=False, unique_indices=False,
        mode=jax.lax.GatherScatterMode.FILL_OR_DROP)


def topk_indices(scores, valid, k: int):
    """One query a row: ``scores`` [B, S] float32, ``valid`` [B, S] ->
    (positions [B, k] int32, chosen [B, k] bool): the ``k`` valid
    positions of largest score, ``lax.top_k``'s order; where a row has
    fewer than ``k`` valid positions the rest are invalid ones, marked
    not ``chosen``."""
    with jax.named_scope(SELECT_SCOPE):
        masked = jnp.where(valid, scores, -jnp.inf)
        _, idx = jax.lax.top_k(masked, k)
        return idx.astype(jnp.int32), jnp.take_along_axis(valid, idx, axis=-1)


def _total_order(x):
    """float32 -> int32 keys in ``lax.top_k``'s order of floats: -0.0
    below +0.0 (which ``==`` calls equal), -inf lowest."""
    b = jax.lax.bitcast_convert_type(x, jnp.int32)
    return b ^ ((b >> 31) & 0x7FFFFFFF)


def topk_mask(scores, valid, k: int):
    """``scores`` [..., S] float32, ``valid`` [..., S] -> [..., S] bool:
    each row's ``k`` valid positions of largest score (every valid one
    where there are fewer) — ``topk_indices``' set.  ``lax.top_k`` ranks
    in a total order, equal values lower position first, so the set is
    the positions above its k-th value ``v`` and, of those equal to
    ``v``, the ones at or below the k-th's position: read off ``v`` and
    that position by one compare over the block, in the same order."""
    with jax.named_scope(SELECT_SCOPE):
        masked = jnp.where(valid, scores, -jnp.inf)
        vals, idx = jax.lax.top_k(masked, k)
        key, kth = _total_order(masked), _total_order(vals[..., k - 1:k])
        pos = jax.lax.broadcasted_iota(jnp.int32, masked.shape,
                                       masked.ndim - 1)
        return valid & ((key > kth)
                        | ((key == kth) & (pos <= idx[..., k - 1:k])))


def gather_rows(pool, page_table, positions, page_size: int):
    """Rows of a page pool [P, page_size, W] at logical ``positions``
    [B, K] of each row's table [B, pages] -> [B, K, W]."""
    page = jnp.take_along_axis(page_table, positions // page_size, axis=1)
    return pool[page, positions % page_size]


def sparse_attend(q, k_rows, v_rows, chosen, scale: float, compute_dtype):
    """One query a row over its chosen rows: ``q`` [B, Hkv, G, D]
    float32, ``k_rows`` / ``v_rows`` [B, K, Hkv·D] (the pool's rows),
    ``chosen`` [B, K] -> [B, Hkv, G, D] float32; scores and softmax in
    float32, the products in ``compute_dtype``."""
    b, kk = chosen.shape
    hkv, d = q.shape[1], q.shape[3]
    kr = k_rows.reshape(b, kk, hkv, d).astype(compute_dtype)
    vr = v_rows.reshape(b, kk, hkv, d).astype(compute_dtype)
    s = jnp.einsum("bhgd,bkhd->bhgk", q.astype(compute_dtype), kr,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(chosen[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhgk,bkhd->bhgd", p.astype(compute_dtype), vr,
                      preferred_element_type=jnp.float32)
