"""Ragged paged decode attention — Pallas TPU kernel + XLA gather path.

The serving-side sibling of ``kernels/flash_attention``: one decode
step attends a single fresh query token per sequence against that
sequence's KV cache, which lives in a PAGED pool (PagedAttention /
"Ragged Paged Attention", arXiv:2604.15464 — PAPERS.md) instead of a
dense [B, S_max] buffer:

* ``k_pages``/``v_pages`` — [num_pages, page_size, H·D]: one global
  page pool shared by every sequence; a sequence owns the pages its
  row of ``page_table`` names, so HBM residency tracks the RAGGED
  total of live tokens, not B × S_max.  Heads and head_dim are FUSED
  on the minor axis so that it is a multiple of the TPU's 128 lanes: a
  64-wide minor axis pads to 128, and rather than double a
  [P, page, H, 64] pool XLA:TPU stores it with the PAGE axis
  minor-most — every program that scatters into or reads the pool
  row-major then transposes the whole pool in and out again, each
  call.  The fused shape's default device layout is row-major with no
  padding: the scatter, the gather and the kernel below take the pool
  as it sits in HBM.
* ``page_table`` — [B, pages_per_seq] int32 page ids (rows padded with
  any valid id past the sequence's last live page: the kernel never
  reads those entries, the gather path masks what they fetch).
* ``seq_lens`` — [B] int32 live token counts; position ``seq_lens[b]``
  is exclusive (lengths, not indices).

The Pallas kernel runs a flash-style online softmax with the PAGE as
the KV block and WALKS THE PAGES ITSELF: grid (B,), one step a
sequence, the pools passed whole in ``pl.ANY`` — as they sit in HBM, no
block, no copy.  Inside, a ``fori_loop`` runs over the sequence's
``ceil(len / page_size)`` LIVE pages (a dynamic trip count read from
the scalar-prefetched ``seq_lens``) with (m, l, acc) as its carry, and
takes page ``page_table[b, j]`` from a ring of VMEM page buffers that
the kernel fills with hand-issued DMAs, ``depth − 1`` pages ahead of
the one it weighs and on into the next sequence's first pages.  So
only the sequence's OWN LIVE pages ever move HBM→VMEM — the ragged win
twice over: a dense layout would stream B × S_max tokens, and a grid
over page SLOTS (what this kernel had before: 512 steps a call at 16 ×
32 slots, three in four of them dead pages pinned to page 0, 0.27 µs a
step whatever it did) costs a step for every page a sequence does not
have; a dead page is now neither requested nor waited for nor stepped
over.  A page's block spans all heads (q [1, H·D], page [page, H·D]):
the pool's own rows, and the per-head reduction is a matmul with a 0/1
head-membership matrix inside the kernel (``_head_sums``: Mosaic
refuses to reshape a [page, H·D] block to [page, H, D]).  The tail
page's dead rows are masked at NEG_INF exactly like flash attention's
causal mask.  On non-TPU backends the kernel runs in interpreter mode.
Kernel or XLA gather path is chosen by ``paged_kernel_applies`` (a
shape rule), never by a caught error.

``dense_decode_reference`` is the oracle: materialize every sequence's
KV densely, mask past ``seq_lens``, plain softmax — the parity target
for both the kernel and the fallback (tests/test_serving.py).

Pool dtype (the searched KV-precision lane, ops/decode_attention.py):
the plain entry points accept fp32 or bf16 pools — every dot casts its
operands to fp32, a no-op on the fp32 path, so the historical numerics
are bit-identical.  An int8 pool carries per-(page, slot) fp32 scales
and enters through ``ragged_paged_attention_quant``: the kernel
dequantizes INSIDE the page loop (a page's scale row rides the same
ring of hand-issued DMAs as its payload), so only quantized bytes ever
stream HBM→VMEM — that smaller stream is the whole point of the lane.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# dense masked reference (the oracle)
# ---------------------------------------------------------------------------
def dense_decode_reference(q, k_dense, v_dense, seq_lens, scale=None,
                           window: int = 0, starts=None):
    """Single-token decode attention against dense per-sequence KV.

    q [B, H, D], k_dense/v_dense [B, S_max, H, D], seq_lens [B] int32
    -> [B, H, D].  Positions >= seq_lens[b] are masked out.  Pure XLA,
    numerically the plain (not online) softmax — the reference both
    the paged kernel and the gather fallback must match.

    GROUPED heads: k_dense/v_dense may hold Hkv < H heads, query head h
    reading key/value head ``h // (H // Hkv)``.  ``window`` W > 0: the
    query (position ``seq_lens - 1``) sees the last W positions only.
    ``starts`` [B]: row 0 of ``k_dense[b]`` is position ``starts[b]``
    (a dense copy of the pages a window walk names), not 0."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if window or starts is not None or q.shape[1] != k_dense.shape[2]:
        return _dense_grouped_reference(q, k_dense, v_dense, seq_lens, scale,
                                        window, starts)
    # HIGHEST: the TPU's default fp32 einsum rounds its operands to
    # bf16; an oracle for an fp32 kernel has to multiply in fp32
    hi = jax.lax.Precision.HIGHEST
    s = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32),
                   k_dense.astype(jnp.float32), precision=hi) * scale
    pos = jnp.arange(k_dense.shape[1], dtype=jnp.int32)
    mask = pos[None, None, :] < seq_lens[:, None, None]
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhs,bshd->bhd", p, v_dense.astype(jnp.float32),
                     precision=hi)
    return out.astype(q.dtype)


def _dense_grouped_reference(q, k_dense, v_dense, seq_lens, scale, window,
                             starts):
    b, hq, d = q.shape
    hkv = k_dense.shape[2]
    hi = jax.lax.Precision.HIGHEST
    qg = q.astype(jnp.float32).reshape(b, hkv, hq // hkv, d)
    s = jnp.einsum("bhgd,bshd->bhgs", qg, k_dense.astype(jnp.float32),
                   precision=hi) * scale
    pos = jnp.arange(k_dense.shape[1], dtype=jnp.int32)[None, :]
    if starts is not None:
        pos = pos + starts.astype(jnp.int32)[:, None]
    mask = pos < seq_lens[:, None]
    if window:
        mask = mask & (pos >= seq_lens[:, None] - window)
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p, v_dense.astype(jnp.float32),
                     precision=hi)
    return out.reshape(b, hq, d).astype(q.dtype)


def gather_kv_pages(pages, page_table, num_heads: int):
    """[P, page_size, H·D] pool + [B, pages_per_seq] table -> dense
    [B, pages_per_seq * page_size, H, D] per-sequence KV (the fallback
    path's gather; also how tests densify a paged cache for the
    oracle).  The fused axis is split AFTER the gather, on the few
    pages gathered — never on the pool."""
    g = pages[page_table]  # [B, pages_per_seq, page_size, H·D]
    b, npp, ps, hd = g.shape
    return g.reshape(b, npp * ps, num_heads, hd // num_heads)


def gather_kv_pages_quant(pages, scales, page_table, num_heads: int):
    """Densify + DEQUANTIZE an int8 pool: pages [P, page_size, H·D]
    int8, scales [P, page_size] fp32 (per-(page, slot), shared across
    heads) -> dense fp32 [B, pages_per_seq * page_size, H, D].  The
    fallback/chunk-prefill sibling of the in-kernel page-loop
    dequant."""
    dense = gather_kv_pages(pages, page_table, num_heads).astype(
        jnp.float32)
    s = scales[page_table]  # [B, pages_per_seq, page_size]
    b, npp, ps = s.shape
    return dense * s.reshape(b, npp * ps)[:, :, None, None]


# ---------------------------------------------------------------------------
# pure-XLA fallback: gather pages, mask, dense softmax
# ---------------------------------------------------------------------------
def _xla_ragged_paged(q, k_pages, v_pages, page_table, seq_lens, scale):
    h = q.shape[1]
    k_dense = gather_kv_pages(k_pages, page_table, h)
    v_dense = gather_kv_pages(v_pages, page_table, h)
    return dense_decode_reference(q, k_dense, v_dense, seq_lens, scale)


def _xla_ragged_paged_quant(q, k_pages, v_pages, k_scale, v_scale,
                            page_table, seq_lens, scale):
    h = q.shape[1]
    k_dense = gather_kv_pages_quant(k_pages, k_scale, page_table, h)
    v_dense = gather_kv_pages_quant(v_pages, v_scale, page_table, h)
    return dense_decode_reference(q, k_dense, v_dense, seq_lens, scale)


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------
# The ring keeps this many bytes of K (and as many of V) on their way
# from HBM, in whole pages: about what 819 GB/s move in the microsecond
# a page DMA takes to land.  Measured on a v5e at the serving cell's
# block ([32, 1024] fp32, 128 KB a page), one call with 117 of its 512
# page slots live / with all live: a ring of 2 pages 64.7 / 277.7 µs,
# of 3 pages 48.8 / 200.0, of 4 pages 45.8 / 189.3; deeper rings (6, 8,
# 12 pages) read within 2 % of 4.  The live bytes alone take 37 / 164 µs.
_RING_BYTES = 512 * 1024
_RING_MAX = 8


def _ring_depth(page_bytes: int) -> int:
    return max(2, min(_RING_MAX, -(-_RING_BYTES // page_bytes)))


def _head_sums(x, head_dim: int):
    """x [rows, H·D] fp32 -> the same shape, every lane holding the sum
    over its OWN head's ``head_dim`` lanes: reduce and spread back in
    one step, ON THE MXU (idle otherwise in this kernel), as a matmul
    with the 0/1 matrix W[i, j] = (i and j lie in one head).  W is the
    same for every tile of lcm(128, head_dim) lanes of the fused axis,
    so the row is multiplied tile by tile against ONE small W (128
    wide at head_dim 64) instead of an [H·D, H·D] matrix; a fused axis
    that is not whole tiles (tiny test shapes, odd head counts) is one
    tile.  fp32-exact at bf16 speed: W is exact in bf16, so only x is
    split — into three bf16 parts whose sum is x to 2^-24 — three
    passes with fp32 accumulation where ``Precision.HIGHEST`` would
    spend six.  (Measured on a v5e at 16 × 64 heads on the 512-step
    ``(B, pages_per_seq)`` grid this kernel had until the pages were
    walked by hand: 152 µs a call against 167 µs for HIGHEST, 227 µs
    for a butterfly of lane rotations on the XLU and 138 µs with no
    reduction at all; under a ring of 2 pages 64.7 µs against 54.3 µs
    with none, under a ring of 3 or more it hides behind the page DMAs.
    Mosaic refuses the reshape to [rows, H, D] that would make it a
    plain sum.)"""
    n = x.shape[-1]
    tile = math.lcm(128, head_dim)
    if n % tile:
        tile = n
    row = jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 1)
    w = (row // head_dim == col // head_dim).astype(jnp.bfloat16)
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    out = []
    for t in range(0, n, tile):
        out.append(sum(
            jnp.dot(part[:, t:t + tile], w,
                    preferred_element_type=jnp.float32)
            for part in (hi, mid, lo)))
    return jnp.concatenate(out, axis=-1)


def _slot_scale(rows, page_size: int):
    """rows [R, 128] fp32 — one page's per-slot scales on the lane
    axis, 128 slots a row — turned onto the sublane axis the scores run
    along: [page_size, 1], by a masked lane sum a row (no transpose of
    a narrow block for Mosaic to refuse)."""
    cols = []
    for i in range(rows.shape[0]):
        shape = (min(128, page_size - 128 * i), 128)
        slot = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        cols.append(jnp.sum(jnp.where(slot == lane, rows[i:i + 1], 0.0),
                            axis=1, keepdims=True))
    return cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=0)


def _rpa_kernel(
    page_table_ref, seq_lens_ref,  # scalar-prefetch operands
    q_ref, k_hbm, v_hbm, *refs,
    page_size: int, head_dim: int, scale: float, quant: bool,
):
    """Grid (B,): one step a sequence.  The pools stay in HBM
    (``pl.ANY``); the kernel walks sequence ``b``'s
    ``ceil(seq_lens[b] / page_size)`` LIVE pages itself, in a
    ``fori_loop`` whose trip count is read from the scalar-prefetched
    ``seq_lens``, with the online-softmax state (m, l, acc) as the
    loop's carry.  Page ``page_table[b, j]`` arrives in a ring of VMEM
    page buffers filled by DMAs the kernel issues by hand; a dead page
    is never requested, never waited for, never stepped over.

    The ring is kept full ACROSS sequences: ``ring`` (SMEM, carried
    from grid step to grid step — hence the "arbitrary" grid) holds the
    cursor of the next page to request, which runs ``depth − 1`` pages
    ahead of the page being consumed and moves on to the next
    sequence's first pages while this one's last are weighed, so a
    sequence does not open on an exposed DMA (measured on a v5e, 16
    sequences of one page each: 21 µs a call with a ring a sequence,
    10.5 µs with one ring; 117 pages over 16 sequences: 56 against
    46 µs).

    A page's block spans ALL heads on the fused lane axis — q [1, H·D],
    k/v [page, H·D] — which is the pool's own layout, so a page DMA
    moves whole 128-lane rows as they sit in HBM.  A head's score is
    ``_head_sums`` of k·q, left on every lane of that head: scores,
    probabilities and the carry are all [·, H·D], so no value ever
    moves between the sublane and the lane axis and the weighted V sum
    needs no spreading back.  The tail page's dead rows are masked at
    NEG_INF, like flash attention's causal mask.

    With ``quant`` the pages arrive int8 and are DEQUANTIZED here, in
    the loop: each page's fp32 scale row rides the same ring (one more
    DMA a stream, the page's 128-lane rows of the lane-padded scale
    table), so HBM→VMEM moves 1 byte an element plus the scale rows and
    the fp32 values exist only in vregs."""
    if quant:
        ks_hbm, vs_hbm, o_ref, k_buf, v_buf, ks_buf, vs_buf, sem, ring = refs
        bufs = (k_buf, v_buf, ks_buf, vs_buf)
    else:
        o_ref, k_buf, v_buf, sem, ring = refs
        bufs = (k_buf, v_buf)
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    depth = k_buf.shape[0]
    n = seq_lens_ref[b]

    def live_pages(bi):
        return pl.cdiv(seq_lens_ref[bi], page_size)

    def next_live(bi):
        # the first sequence from ``bi`` on that has a page to walk
        return jax.lax.while_loop(
            lambda x: jnp.logical_and(
                x < nb, live_pages(jnp.minimum(x, nb - 1)) == 0),
            lambda x: x + 1, bi)

    def copies(bi, j, slot):
        page = page_table_ref[bi, j]
        srcs = [k_hbm.at[page], v_hbm.at[page]]
        if quant:
            rows = ks_buf.shape[1]  # of the scale table, a page
            srcs += [t.at[pl.ds(page * rows, rows)] for t in (ks_hbm, vs_hbm)]
        return [pltpu.make_async_copy(src, buf.at[slot], sem.at[i, slot])
                for i, (src, buf) in enumerate(zip(srcs, bufs))]

    # ring: [sequence, page] of the next page to request, pages
    # requested, pages consumed — since the call began
    def request():
        rb, rj = ring[0], ring[1]

        @pl.when(rb < nb)
        def _():
            for c in copies(rb, rj, ring[2] % depth):
                c.start()
            ring[2] = ring[2] + 1
            last = rj + 1 == live_pages(rb)
            ring[0] = jnp.where(last, next_live(rb + 1), rb)
            ring[1] = jnp.where(last, 0, rj + 1)

    @pl.when(b == 0)
    def _fill():
        ring[0] = next_live(0)
        ring[1] = 0
        ring[2] = 0
        ring[3] = 0
        for _ in range(depth - 1):
            request()

    q = q_ref[0].astype(jnp.float32)  # [1, H·D]

    def page_step(j, carry):
        m_prev, l_prev, acc_prev = carry
        # the slot the previous page left is requested for the page
        # ``depth − 1`` ahead before this one is waited for
        request()
        slot = ring[3] % depth
        for c in copies(b, j, slot):
            c.wait()
        ring[3] = ring[3] + 1
        # fp32 casts are no-ops on the fp32 pool and make the SAME
        # body serve bf16 and int8 pools
        k = k_buf[slot].astype(jnp.float32)  # [page, H·D]
        v = v_buf[slot].astype(jnp.float32)
        s = _head_sums(k * q, head_dim) * scale
        if quant:
            s = s * _slot_scale(ks_buf[slot], page_size)
            v = v * _slot_scale(vs_buf[slot], page_size)
        slots = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0)
        s = jnp.where(slots < n, s, NEG_INF)  # [page, H·D] fp32
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=0, keepdims=True)
        acc_new = acc_prev * alpha + jnp.sum(p * v, axis=0, keepdims=True)
        return m_new, l_new, acc_new

    zeros = jnp.zeros_like(q)
    _, l, acc = jax.lax.fori_loop(
        0, live_pages(b), page_step,
        (jnp.full_like(q, NEG_INF), zeros, zeros))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


# jitted so that a model's layers share ONE trace and lowering of the
# kernel (XLA inlines the calls again: one Mosaic call a layer) — traced
# per layer, 24 layers cost the decode frame a second of set-up
@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _pallas_ragged_paged(q, k_pages, v_pages, page_table, seq_lens, scale,
                         interpret: bool, k_scale=None, v_scale=None):
    b, h, d = q.shape
    num_pages, page_size, hd = k_pages.shape
    assert hd == h * d, (k_pages.shape, q.shape)
    quant = k_scale is not None
    depth = _ring_depth(page_size * hd * k_pages.dtype.itemsize)

    def q_map(bi, pt_ref, sl_ref):
        return (bi, 0, 0)

    # the pool as it sits in HBM: no block, no copy
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [pl.BlockSpec((1, 1, hd), q_map), in_hbm, in_hbm]
    operands = [q.reshape(b, 1, hd), k_pages, v_pages]
    scratch = [pltpu.VMEM((depth, page_size, hd), k_pages.dtype),
               pltpu.VMEM((depth, page_size, hd), v_pages.dtype)]
    if quant:
        # Mosaic slices a DMA out of whole 128-lane rows only, and any
        # number of them only out of a table exactly 128 wide: a page's
        # scales are padded to whole rows (what the tiled layout of
        # [P, page_size] occupies anyway)
        pad = -page_size % 128
        rows = (page_size + pad) // 128
        in_specs += [in_hbm, in_hbm]
        operands += [
            jnp.pad(t, ((0, 0), (0, pad))).reshape(num_pages * rows, 128)
            for t in (k_scale, v_scale)]
        scratch += [pltpu.VMEM((depth, rows, 128), jnp.float32)] * 2
    scratch += [pltpu.SemaphoreType.DMA((len(operands) - 1, depth)),
                pltpu.SMEM((4,), jnp.int32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, hd), q_map),
        scratch_shapes=scratch,
    )
    kernel = functools.partial(
        _rpa_kernel, page_size=page_size, head_dim=d, scale=scale,
        quant=quant)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, hd), q.dtype),
        # the ring of page DMAs runs on from one sequence to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="ragged_paged_attention",
    )(page_table.astype(jnp.int32), seq_lens.astype(jnp.int32), *operands)
    return out.reshape(b, h, d)


def paged_kernel_applies(head_dim: int, page_size: int,
                         num_heads: int = 0) -> bool:
    """THE shape rule that picks the Pallas kernel over the XLA gather
    path: head_dim and page_size multiples of 8 (whole sublane tiles
    for W's head blocks and the page buffers) and, ON THE CHIP, a fused
    width H·D of whole 128-lane rows — the kernel asks Mosaic for a DMA
    of pool page ``[page, H·D]`` into a VMEM buffer of that shape, and
    Mosaic slices a DMA only out of whole lane tiles (compiled for a
    described v5e at 8 × 64, 16 × 64 and 4 × 256 lanes, pages of 8 to
    256, fp32 / bf16 / int8; refused at 1 × 8, 2 × 32 and 3 × 96).  It
    is the width at which the pool is updated IN PLACE, too.  The
    interpreter (every other backend) has no lanes and takes any width
    — how the CPU tests run the kernel's own code at tiny shapes; asked
    without ``num_heads`` the rule answers for the block alone.
    Anything else is served by the gather path."""
    if head_dim % 8 or page_size % 8:
        return False
    return jax.default_backend() != "tpu" or (num_heads * head_dim) % 128 == 0


def ragged_paged_attention_quant(
    q, k_pages, v_pages, k_scale, v_scale, page_table, seq_lens,
    scale=None,
):
    """Paged-KV decode attention over an INT8 pool: like
    ``ragged_paged_attention`` but ``k_pages``/``v_pages`` are int8 and
    ``k_scale``/``v_scale`` [P, page_size] fp32 carry each token's
    symmetric per-(page, slot) scale (shared across heads).  Same
    kernel rule as the fp32 entry point."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if paged_kernel_applies(q.shape[-1], k_pages.shape[1], q.shape[1]):
        return _pallas_ragged_paged(
            q, k_pages, v_pages, page_table, seq_lens, float(scale),
            jax.default_backend() != "tpu", k_scale, v_scale)
    return _xla_ragged_paged_quant(q, k_pages, v_pages, k_scale, v_scale,
                                   page_table, seq_lens, float(scale))


def ragged_paged_attention(
    q, k_pages, v_pages, page_table, seq_lens, scale=None,
):
    """Paged-KV decode attention: q [B, H, D] (one fresh token per
    sequence), k_pages/v_pages [P, page_size, H·D], page_table
    [B, pages_per_seq] int32, seq_lens [B] int32 -> [B, H, D].

    Takes the Pallas kernel when ``paged_kernel_applies`` (interpreter
    mode off-TPU, like flash_attention), the gather/masked XLA path
    otherwise — chosen by shape alone, never by a caught error.
    Decode is forward-only (no gradients flow into a serving step), so
    no custom VJP is defined — autodiff through the XLA path works for
    the tests that want it."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if paged_kernel_applies(q.shape[-1], k_pages.shape[1], q.shape[1]):
        return _pallas_ragged_paged(
            q, k_pages, v_pages, page_table, seq_lens, float(scale),
            jax.default_backend() != "tpu")
    return _xla_ragged_paged(q, k_pages, v_pages, page_table, seq_lens,
                             float(scale))


# ---------------------------------------------------------------------------
# grouped heads, a window: the walk is handed in
# ---------------------------------------------------------------------------
# A layer whose query heads share key/value heads (Hq = G x Hkv) and, in
# a WINDOW layer, see the last W positions only.  The caller names the
# pages to walk: ``walk_table[b, i]`` is the pool page that holds
# positions ``(starts[b] + i) * page_size ...`` of sequence b, for the
# ``ceil(seq_lens[b] / page_size) - starts[b]`` pages from the window's
# first to the tail — so one kernel serves a pool addressed by the
# sequence's table (a global layer: ``starts`` 0, the table itself) and a
# pool used as a ring (ops/decode_attention.py names the ring's pages),
# and a page below the window is never requested.

def _xla_grouped_paged(q, k_pages, v_pages, walk_table, seq_lens, starts,
                       window, scale):
    hkv = k_pages.shape[-1] // q.shape[-1]
    k_dense = gather_kv_pages(k_pages, walk_table, hkv)
    v_dense = gather_kv_pages(v_pages, walk_table, hkv)
    return dense_decode_reference(q, k_dense, v_dense, seq_lens, scale,
                                  window=window,
                                  starts=starts * k_pages.shape[1])


def _grouped_kernel(
    table_ref, lens_ref, starts_ref,  # scalar-prefetch operands
    q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sem, ring, *,
    page_size: int, groups: int, window: int, scale: float,
):
    """Grid (B,), the pools in HBM, a ring of hand-issued page DMAs kept
    full across sequences — ``_rpa_kernel``'s walk, over the pages
    ``table_ref[b, 0 .. ceil(n / page) - starts[b])``.

    q_ref [1, Hkv·Gp, D]: the query heads of key/value head h in rows
    ``h·Gp ..`` (a group padded to Gp rows, whole sublane tiles).  A
    page is [page, Hkv·D] as the pool holds it.  The heads meet on the
    MXU: q is spread BLOCK-DIAGONALLY over the fused axis — row r keeps
    its D lanes in the lanes of its own key/value head, zeros elsewhere
    — so ``q_bd · pageᵀ`` is every head's scores [Hkv·Gp, page] in ONE
    product and ``p · page`` every head's weighted values [Hkv·Gp,
    Hkv·D], of which row r's own head's D lanes are read at the end; no
    reshape of the fused axis, no per-head slicing inside the loop.  The
    products run in the POOL's dtype (bf16: q and p rounded to it, fp32
    accumulation; an fp32 pool multiplies at HIGHEST).  Rows below
    ``n - window`` of the first page and at or past ``n`` of the tail
    are masked at NEG_INF."""
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    depth = k_buf.shape[0]
    n = lens_ref[b]
    first = starts_ref[b]
    bufs = (k_buf, v_buf)

    def walk_pages(bi):
        return pl.cdiv(lens_ref[bi], page_size) - starts_ref[bi]

    def next_live(bi):
        return jax.lax.while_loop(
            lambda x: jnp.logical_and(
                x < nb, walk_pages(jnp.minimum(x, nb - 1)) <= 0),
            lambda x: x + 1, bi)

    def copies(bi, j, slot):
        page = table_ref[bi, j]
        return [pltpu.make_async_copy(src.at[page], buf.at[slot],
                                      sem.at[i, slot])
                for i, (src, buf) in enumerate(zip((k_hbm, v_hbm), bufs))]

    def request():
        rb, rj = ring[0], ring[1]

        @pl.when(rb < nb)
        def _():
            for c in copies(rb, rj, ring[2] % depth):
                c.start()
            ring[2] = ring[2] + 1
            last = rj + 1 == walk_pages(rb)
            ring[0] = jnp.where(last, next_live(rb + 1), rb)
            ring[1] = jnp.where(last, 0, rj + 1)

    @pl.when(b == 0)
    def _fill():
        ring[0] = next_live(0)
        ring[1] = 0
        ring[2] = 0
        ring[3] = 0
        for _ in range(depth - 1):
            request()

    q = q_ref[0].astype(jnp.float32)  # [Hkv·Gp, D]
    rows, d = q.shape
    hkv = rows // groups
    width = hkv * d
    exact = k_buf.dtype == jnp.float32
    prec = jax.lax.Precision.HIGHEST if exact else None
    row_head = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 0) // groups
    lane_head = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1) // d
    q_bd = jnp.where(row_head == lane_head,
                     jnp.concatenate([q] * hkv, axis=1), 0.0
                     ).astype(k_buf.dtype)

    def page_step(j, carry):
        m_prev, l_prev, acc_prev = carry
        request()
        slot = ring[3] % depth
        for c in copies(b, j, slot):
            c.wait()
        ring[3] = ring[3] + 1
        k = k_buf[slot]  # [page, Hkv·D]
        v = v_buf[slot]
        s = jax.lax.dot_general(
            q_bd, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec) * scale
        pos = (first + j) * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        seen = pos < n
        if window:
            seen = jnp.logical_and(seen, pos >= n - window)
        s = jnp.where(seen, s, NEG_INF)  # [Hkv·Gp, page]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_new = acc_prev * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32,
            precision=prec)
        return m_new, l_new, acc_new

    _, l, acc = jax.lax.fori_loop(
        0, walk_pages(b), page_step,
        (jnp.full((rows, 1), NEG_INF, jnp.float32),
         jnp.zeros((rows, 1), jnp.float32),
         jnp.zeros((rows, width), jnp.float32)))
    acc = acc / jnp.maximum(l, 1e-30)
    # row r's own head: its D lanes of the fused axis
    o_ref[0] = jnp.concatenate(
        [acc[h * groups:(h + 1) * groups, h * d:(h + 1) * d]
         for h in range(hkv)], axis=0).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("window", "scale", "interpret"))
def _pallas_grouped_paged(q, k_pages, v_pages, walk_table, seq_lens, starts,
                          window: int, scale: float, interpret: bool):
    b, hq, d = q.shape
    _, page_size, width = k_pages.shape
    hkv = width // d
    assert hkv * d == width and hq % hkv == 0, (k_pages.shape, q.shape)
    g = hq // hkv
    gp = -(-g // 8) * 8  # a group's rows: whole sublane tiles
    qp = jnp.pad(q.reshape(b, hkv, g, d), ((0, 0), (0, 0), (0, gp - g), (0, 0))
                 ).reshape(b, hkv * gp, d)
    depth = _ring_depth(page_size * width * k_pages.dtype.itemsize)

    def q_map(bi, *_):
        return (bi, 0, 0)

    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, hkv * gp, d), q_map), in_hbm, in_hbm],
        out_specs=pl.BlockSpec((1, hkv * gp, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((depth, page_size, width), k_pages.dtype),
            pltpu.VMEM((depth, page_size, width), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, depth)),
            pltpu.SMEM((4,), jnp.int32)],
    )
    out = pl.pallas_call(
        functools.partial(_grouped_kernel, page_size=page_size, groups=gp,
                          window=window, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv * gp, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="grouped_paged_attention",
    )(walk_table.astype(jnp.int32), seq_lens.astype(jnp.int32),
      starts.astype(jnp.int32), qp, k_pages, v_pages)
    return out.reshape(b, hkv, gp, d)[:, :, :g].reshape(b, hq, d)


def grouped_kernel_applies(head_dim: int, page_size: int) -> bool:
    """The shape rule of the grouped kernel: ON THE CHIP a head is whole
    128-lane tiles (the block-diagonal q and the read of a head's own
    lanes slice the fused axis at head boundaries) and a page whole
    16-row tiles (a bf16 page buffer's sublane packing); the interpreter
    takes any multiple of 8.  Anything else is served by the gather
    path."""
    if head_dim % 8 or page_size % 8:
        return False
    return jax.default_backend() != "tpu" or (
        head_dim % 128 == 0 and page_size % 16 == 0)


def grouped_paged_attention(q, k_pages, v_pages, walk_table, seq_lens,
                            starts, window: int = 0, scale=None,
                            use_kernel: bool = True):
    """Paged-KV decode attention for grouped heads with a window:
    q [B, Hq, D], k_pages/v_pages [P, page_size, Hkv·D], ``walk_table``
    [B, n] int32 the pool pages to walk from logical page ``starts[b]``
    on, ``seq_lens`` [B] live token counts -> [B, Hq, D].  ``window``
    W > 0: positions below ``seq_lens - W`` are not seen (the caller
    starts the walk at the window's first page).  The Pallas kernel
    where ``grouped_kernel_applies`` and ``use_kernel``, the XLA gather
    path otherwise — by shape alone."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if use_kernel and grouped_kernel_applies(q.shape[-1], k_pages.shape[1]):
        return _pallas_grouped_paged(
            q, k_pages, v_pages, walk_table, seq_lens, starts, int(window),
            float(scale), jax.default_backend() != "tpu")
    return _xla_grouped_paged(q, k_pages, v_pages, walk_table, seq_lens,
                              starts, int(window), float(scale))
