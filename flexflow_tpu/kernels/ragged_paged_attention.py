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
  any valid id past the sequence's last live page — masked off).
* ``seq_lens`` — [B] int32 live token counts; position ``seq_lens[b]``
  is exclusive (lengths, not indices).

The Pallas kernel runs a flash-style online softmax with the PAGE as
the KV block: grid (B, pages_per_seq), pages innermost so the
(m, l, acc) scratch accumulators carry across a sequence's pages, and
the page indirection rides the BlockSpec index_map — the scalar-
prefetched ``page_table`` picks which pool page each grid step loads,
so only the sequence's OWN pages ever move HBM→VMEM (the ragged win;
a dense layout would stream B × S_max tokens).  Blocks span all heads
(q [1, 1, H·D], pool [1, page, H·D]): the TPU lowering takes a block
whose last two dims equal the array's, and the per-head reduction is
a matmul with a 0/1 head-membership matrix inside the kernel
(``_head_sums``: Mosaic refuses to reshape a [page, H·D] block to
[page, H, D]).  Pages past
``ceil(len/page_size)`` are skipped with ``pl.when`` (their index map
pins them to page 0, so they cost no FLOPs and consecutive dead steps
keep one resident block; the tail page's dead rows are masked at
NEG_INF exactly like flash attention's causal mask).  On non-TPU backends the kernel runs in interpreter mode.
Kernel or XLA gather path is chosen by ``paged_kernel_applies`` (a
shape rule), never by a caught error.

``dense_decode_reference`` is the oracle: materialize every sequence's
KV densely, mask past ``seq_lens``, plain softmax — the parity target
for both the kernel and the fallback (tests/test_serving.py).

Pool dtype (the searched KV-precision lane, ops/decode_attention.py):
the plain entry points accept fp32 or bf16 pools — every dot casts its
operands to fp32, a no-op on the fp32 path, so the historical numerics
are bit-identical.  An int8 pool carries per-(page, slot) fp32 scales
and enters through ``ragged_paged_attention_quant``: the Pallas
variant dequantizes INSIDE the page loop (the scales ride the same
scalar-prefetched page indirection as the payload, ``_SCALE_ROWS``
[page_size] rows per grid step), so only quantized bytes ever stream
HBM→VMEM — that smaller stream is the whole point of the lane.
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
def dense_decode_reference(q, k_dense, v_dense, seq_lens, scale=None):
    """Single-token decode attention against dense per-sequence KV.

    q [B, H, D], k_dense/v_dense [B, S_max, H, D], seq_lens [B] int32
    -> [B, H, D].  Positions >= seq_lens[b] are masked out.  Pure XLA,
    numerically the plain (not online) softmax — the reference both
    the paged kernel and the gather fallback must match."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
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
# int8 scale rows reach the kernel in blocks of this many pool pages:
# the TPU lowering wants the second-to-last block dim divisible by 8
_SCALE_ROWS = 8


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
    spend six.  (Measured on a v5e at 16 × 64 heads, a call of 512 grid
    steps: 152 µs against 167 µs for HIGHEST, 227 µs for a butterfly of
    lane rotations on the XLU and 138 µs with no reduction at all;
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


def _rpa_kernel(
    page_table_ref, seq_lens_ref,  # scalar-prefetch operands
    q_ref, k_ref, v_ref, *refs,
    page_size: int, head_dim: int, scale: float, quant: bool,
):
    """Grid (B, pages_per_seq), pages innermost (sequential on TPU) so
    the online-softmax scratch carries across one sequence's pages.
    The k/v BlockSpec index maps already routed THIS grid step's block
    to pool page ``page_table[b, j]`` — the kernel only masks the
    ragged tail and skips fully-dead pages.

    Every block spans ALL heads on the fused lane axis — q [1, H·D],
    k/v [page, H·D] — which is the pool's own layout, so Mosaic takes
    the blocks as they sit in HBM, whole 128-lane rows with no padding.
    A head's score is ``_head_sums`` of k·q, left on every lane of that
    head: scores, probabilities and the (m, l, acc) scratch are all
    [·, H·D], so no value ever moves between the sublane and the lane
    axis and the weighted V sum needs no spreading back.

    With ``quant`` the page's K/V arrive int8 and are DEQUANTIZED
    here, in the page loop — ``ks_ref``/``vs_ref`` hold the
    per-(page, slot) fp32 scale rows of the ``_SCALE_ROWS`` pool pages
    around this one, routed by the same scalar-prefetched page
    indirection as the payload.  HBM→VMEM moves 1 byte per element
    plus the scale rows; the fp32 values exist only in vregs."""
    if quant:
        ks_ref, vs_ref, o_ref, m_scratch, l_scratch, acc_scratch = refs
    else:
        o_ref, m_scratch, l_scratch, acc_scratch = refs
    b = pl.program_id(0)
    j = pl.program_id(1)
    npp = pl.num_programs(1)
    n = seq_lens_ref[b]

    @pl.when(j == 0)
    def _init():
        m_scratch[:] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[:] = jnp.zeros_like(l_scratch)
        acc_scratch[:] = jnp.zeros_like(acc_scratch)

    # a page whose first slot is already past the ragged length holds
    # no live token for this sequence
    @pl.when(j * page_size < n)
    def _step():
        q = q_ref[0].astype(jnp.float32)  # [1, H·D]
        # fp32 casts are no-ops on the fp32 pool and make the SAME
        # kernel serve bf16 and int8 pools
        k = k_ref[0].astype(jnp.float32)  # [page, H·D]
        v = v_ref[0].astype(jnp.float32)
        s = _head_sums(k * q, head_dim) * scale
        if quant:
            # this page's row of the scale block, turned so the slot
            # index sits on the major axis like the scores
            row = page_table_ref[b, j] % _SCALE_ROWS

            def slot_scale(ref):
                t = ref[...].T  # [page, _SCALE_ROWS]
                lane = jax.lax.broadcasted_iota(jnp.int32, t.shape, 1)
                return jnp.sum(jnp.where(lane == row, t, 0.0),
                               axis=1, keepdims=True)  # [page, 1]

            s = s * slot_scale(ks_ref)
            v = v * slot_scale(vs_ref)
        slots = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0)
        s = jnp.where(slots < n, s, NEG_INF)  # [page, H·D] fp32
        m_prev = m_scratch[:]  # [1, H·D]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scratch[:] = l_scratch[:] * alpha + jnp.sum(
            p, axis=0, keepdims=True)
        acc_scratch[:] = acc_scratch[:] * alpha + jnp.sum(
            p * v, axis=0, keepdims=True)
        m_scratch[:] = m_new

    @pl.when(j == npp - 1)
    def _finish():
        l = jnp.maximum(l_scratch[:], 1e-30)
        o_ref[0] = (acc_scratch[:] / l).astype(o_ref.dtype)


# jitted so that a model's layers share ONE trace and lowering of the
# kernel (XLA inlines the calls again: one Mosaic call a layer) — traced
# per layer, 24 layers cost the decode frame a second of set-up
@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _pallas_ragged_paged(q, k_pages, v_pages, page_table, seq_lens, scale,
                         interpret: bool, k_scale=None, v_scale=None):
    b, h, d = q.shape
    num_pages, page_size, hd = k_pages.shape
    assert hd == h * d, (k_pages.shape, q.shape)
    pages_per_seq = page_table.shape[1]
    quant = k_scale is not None

    def page_of(bi, j, pt_ref, sl_ref):
        # dead pages (page slot past ceil(len/page_size)) pin to pool
        # page 0 — the DMA still runs but pl.when skips the math and
        # the tail mask kills any live-page partial rows
        live = (j * page_size) < sl_ref[bi]
        return jnp.where(live, pt_ref[bi, j], 0)

    def q_map(bi, j, pt_ref, sl_ref):
        return (bi, 0, 0)

    def kv_map(bi, j, pt_ref, sl_ref):
        return (page_of(bi, j, pt_ref, sl_ref), 0, 0)

    def scale_map(bi, j, pt_ref, sl_ref):
        # the scale rows ride the SAME page indirection as the payload
        return (page_of(bi, j, pt_ref, sl_ref) // _SCALE_ROWS, 0)

    kv_spec = pl.BlockSpec((1, page_size, hd), kv_map)
    in_specs = [pl.BlockSpec((1, 1, hd), q_map), kv_spec, kv_spec]
    operands = [q.reshape(b, 1, hd), k_pages, v_pages]
    if quant:
        s_spec = pl.BlockSpec((_SCALE_ROWS, page_size), scale_map)
        in_specs += [s_spec, s_spec]
        operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, pages_per_seq),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, hd), q_map),
        scratch_shapes=[pltpu.VMEM((1, hd), jnp.float32)] * 3,
    )
    kernel = functools.partial(
        _rpa_kernel, page_size=page_size, head_dim=d, scale=scale,
        quant=quant)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, hd), q.dtype),
        # sequences are independent; only the page axis carries scratch
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="ragged_paged_attention",
    )(page_table.astype(jnp.int32), seq_lens.astype(jnp.int32), *operands)
    return out.reshape(b, h, d)


def paged_kernel_applies(head_dim: int, page_size: int) -> bool:
    """THE shape rule that picks the Pallas kernel over the XLA gather
    path: head_dim and page_size multiples of 8 (whole sublane tiles
    for W's head blocks and the scale-row transpose).  The TPU
    lowering asks nothing more of the fused block: Mosaic takes a
    (1, page, H·D) block — its last two dims ARE the array's — at any
    width and in every pool dtype (compiled for a described v5e from
    1 × 8 to 16 × 64 and 4 × 256 lanes, pages of 8 to 32, fp32 / bf16 /
    int8).  Whether the pool is updated IN PLACE is a matter of its
    layout, not of this rule: H·D a multiple of 128 lanes, on either
    path.  Anything else — tiny CPU test shapes — is served by the
    gather path."""
    return head_dim % 8 == 0 and page_size % 8 == 0


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
    if paged_kernel_applies(q.shape[-1], k_pages.shape[1]):
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
    if paged_kernel_applies(q.shape[-1], k_pages.shape[1]):
        return _pallas_ragged_paged(
            q, k_pages, v_pages, page_table, seq_lens, float(scale),
            jax.default_backend() != "tpu")
    return _xla_ragged_paged(q, k_pages, v_pages, page_table, seq_lens,
                             float(scale))
