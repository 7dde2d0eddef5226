"""DecodeAttentionOp — single-token decode attention over a paged KV
cache (the serving-side sibling of MultiHeadAttentionOp).

One decode step projects the fresh token's q/k/v, scatters the new
k/v into this layer's page-pool cache (model STATE, threaded through
``ctx.state_in``/``state_out`` like the MoE CacheOp and the EF
residuals), and attends the query against the sequence's RAGGED cache
via ``kernels/ragged_paged_attention``.  Inputs:

* hidden     [B, 1, E]            — the decode frame's token embeddings
* page_table [B, pages_per_seq]   — int32 page ids into the pool
* seq_lens   [B]                  — int32 tokens ALREADY cached per
                                    sequence (the fresh token lands at
                                    position seq_lens[b]; attention
                                    runs over seq_lens[b] + 1 tokens)

B is the decode frame's fixed sequence-slot count (``max_seqs``) —
the continuous-batching executor (runtime/decode.py) composes ragged
requests into frames of exactly this shape so the compiled program
never re-specializes.

Parallelization: batch (slot 0) shards SEQUENCES — each device then
holds only its sequences' cache pages; the replica slot shards HEADS
(classic decode TP: every device holds every sequence's pages but only
H/r heads of them, partial-summing the output projection like MHA).
Both genuinely divide per-device KV residency and KV read traffic —
``kv_cache_bytes``/``sharded_bytes_accessed`` expose exactly that to
the cost model, which is what makes the serving objective's
TP-vs-batch Pareto real instead of asserted.
"""

from __future__ import annotations

import functools
import math
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from flexflow_tpu.core.machine import MachineView
from flexflow_tpu.core.optype import OperatorType
from flexflow_tpu.core.ptensor import DataType, ParallelTensorShape
from flexflow_tpu.initializers import (
    DEFAULT_WEIGHT_INIT,
    ConstantInitializer,
    Initializer,
)
from flexflow_tpu.ops.base import (
    REPLICA_SLOT,
    LoweringContext,
    Operator,
    OpSharding,
    ShardAnnot,
    WeightSpec,
    register_op,
)


def _quantize_kv(x):
    """Per-token symmetric int8 quantization of fresh K or V rows:
    x [..., H·D] fp32 (the pool's fused rows) -> (int8 payload, fp32
    scale over the trailing axis).  One scale per token (the pool's
    per-(page, slot) "page_slot" layout) — amax/127 symmetric, the
    EQuARX-style scheme whose drift bound the accuracy-contract test
    asserts."""
    amax = jnp.max(jnp.abs(x), axis=-1)
    s = jnp.maximum(amax, 1e-30) / 127.0
    q = jnp.clip(jnp.round(x / s[..., None]), -127, 127).astype(jnp.int8)
    return q, s.astype(jnp.float32)


def _write_chunk_pages(pools, rows, page_table, positions):
    """Put a chunk's fresh rows — ``rows`` {state leaf: [B, C, ...]} at
    ``positions`` [B, C] — into ``pools`` {state leaf: [P, page, ...]};
    returns the pools, updated in place.

    A CONTIGUOUS run (``DecodeAttentionOp.forward_chunk``'s contract)
    covers at most ``C / page_size + 1`` pages a sequence: each is read,
    the run's rows are laid over it and it is written back WHOLE — one
    page-sized window a touched page instead of one row-sized window a
    token.  A page the run does not reach is not written (its index
    points past the pool: dropped).  The pool holds afterwards what the
    row scatter leaves: every row at its position, the rows the
    ``cap - 1`` clamp folds onto one position resolved as a scatter
    applied in order resolves them (the last row stays), every other row
    of a touched page as it was.  A run that is NOT contiguous takes the
    row scatter itself — the branch is chosen on the device from
    ``positions``; nothing sends such a run."""
    num_pages, ps = next(iter(pools.values())).shape[:2]
    pps = page_table.shape[1]
    cap = ps * pps
    b, c = positions.shape
    rows = {leaf: r.astype(pools[leaf].dtype) for leaf, r in rows.items()}
    steps = jnp.arange(c, dtype=jnp.int32)
    c0 = positions[:, :1]  # [B, 1]
    contiguous = jnp.all(positions == jnp.minimum(c0 + steps, cap - 1))

    def by_row(pools):
        page = jnp.take_along_axis(
            page_table, jnp.minimum(positions // ps, pps - 1), axis=1)
        slot = positions % ps
        return {leaf: pool.at[page, slot].set(rows[leaf])
                for leaf, pool in pools.items()}

    def by_page(pools):
        n = -(-c // ps) + 1  # pages a run of C positions can touch
        off = c0 % ps  # the run's first slot in its first page
        # slot r of touched page j holds chunk row j * ps + r - off
        row = jnp.arange(n * ps, dtype=jnp.int32) - off  # [B, n * ps]
        held = ((row >= 0) & (row < c) & (c0 + row < cap)).reshape(b, n, ps)
        logical = c0 // ps + jnp.arange(n, dtype=jnp.int32)  # [B, n]
        page = jnp.take_along_axis(
            page_table, jnp.minimum(logical, pps - 1), axis=1)
        target = jnp.where(jnp.any(held, axis=-1), page,
                           num_pages)  # past the pool: dropped
        # the rows clamped onto ``cap - 1``: the last one stays
        folded = (c0 + steps == cap - 1)  # [B, C]
        out = {}
        for leaf, pool in pools.items():
            r = rows[leaf]
            tail = (1,) * (r.ndim - 2)
            r = jnp.where(folded.reshape(b, c, *tail), r[:, -1:], r)
            laid = jnp.zeros((n * ps,) + r.shape[2:], r.dtype)
            laid = jnp.stack([
                jax.lax.dynamic_update_slice(
                    laid, r[i], (off[i, 0],) + (0,) * len(tail))
                for i in range(b)])
            merged = jnp.where(
                held.reshape(b, n, ps, *tail),
                laid.reshape((b, n, ps) + r.shape[2:]), pool[page])
            out[leaf] = pool.at[target].set(merged, mode="drop")
        return out

    return jax.lax.cond(contiguous, by_page, by_row, pools)


@functools.partial(jax.jit, static_argnames=(
    "num_heads", "block_pages", "compute_dtype"))
def _chunk_write_and_attend(pools, rows, q, page_table, positions, blocks,
                            *, num_heads, block_pages, compute_dtype):
    """The pool's side of ``DecodeAttentionOp.forward_chunk``: the
    chunk's rows written (``_write_chunk_pages``), then its queries ``q``
    [B, C, H·D] against ``blocks`` key blocks of ``block_pages`` pages
    from page 0, online softmax in fp32; returns the pools and the
    attention output [B, C, H·D] float32.

    Jitted on its own so that the layers of a model, whose shapes are
    one, share ONE trace and one lowered function inside the chunk
    program (a chunk's set-up is traced on every start); XLA inlines the
    calls.  The products take the operands the whole-table form's
    compiled program had — q, K and V in the compute dtype, the weights
    p in fp32 — and only the order of the softmax's sums differs."""
    from flexflow_tpu.kernels.ragged_paged_attention import (
        NEG_INF,
        gather_kv_pages,
        gather_kv_pages_quant,
    )

    cd = compute_dtype
    pools = _write_chunk_pages(pools, rows, page_table, positions)
    b, c = positions.shape
    h, bp = num_heads, block_pages
    ps = pools["k_cache"].shape[1]
    d = q.shape[-1] // h
    qc = q.reshape(b, c, h, d).astype(cd)
    scale = 1.0 / math.sqrt(d)

    def keys_of(leaf, pages):
        """Block ``pages`` [B, bp] of a pool as the products take it:
        [B, keys, H, D] in the compute dtype."""
        if f"{leaf}_scale" in pools:
            dense = gather_kv_pages_quant(
                pools[f"{leaf}_cache"], pools[f"{leaf}_scale"], pages, h)
        else:
            dense = gather_kv_pages(pools[f"{leaf}_cache"], pages, h)
        if dense.dtype == jnp.float32 and cd == jnp.bfloat16:
            # bf16's rounding, made ON THE BLOCK by an op XLA does not
            # move: a bare convert it lifts above the gather, retypes
            # the loop's pool operand to bf16 and converts the WHOLE
            # fp32 pool before the loop, every layer of every chunk
            # (tests/test_chip_lowering.py holds that no pool-sized
            # convert exists)
            dense = jax.lax.reduce_precision(dense, 8, 7)
        return dense.astype(cd)

    # the table in whole blocks (a last block past the table repeats its
    # last page, at positions no query sees)
    table = jnp.pad(page_table, ((0, 0), (0, -page_table.shape[1] % bp)),
                    mode="edge")

    def block(i, carry):
        m, l, acc = carry
        pages = jax.lax.dynamic_slice_in_dim(table, i * bp, bp, axis=1)
        s = jnp.einsum("bchd,bshd->bchs", qc, keys_of("k", pages),
                       preferred_element_type=jnp.float32) * scale
        # key j of the block sits at position i * keys + j
        seen = (jnp.arange(bp * ps, dtype=jnp.int32)[None, None, :]
                <= (positions - i * bp * ps)[:, :, None])  # [B, C, S]
        s = jnp.where(seen[:, :, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # block 0 holds position 0, which every query sees: m_new is a
        # real score from there on and an unseen key weighs 0
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bchs,bshd->bchd", p, keys_of("v", pages))
        return m_new, l, acc

    stat = jnp.zeros((b, c, h), jnp.float32)
    _, l, acc = jax.lax.fori_loop(
        0, blocks, block,
        (stat + NEG_INF, stat, jnp.zeros((b, c, h, d), jnp.float32)))
    return pools, (acc / l[..., None]).reshape(b, c, h * d)


@register_op
class DecodeAttentionOp(Operator):
    """hidden [B, 1, E], page_table [B, pages_per_seq] i32,
    seq_lens [B] i32 -> [B, 1, E].

    attrs: embed_dim, num_heads, page_size, pages_per_seq, num_pages
    (pool size; default max_seqs * pages_per_seq), use_kernel (take the
    Pallas ragged-paged path when shapes allow), kv_dtype (POOL dtype
    of the cache — "fp32"/"bf16"/"int8", the searched KV-precision
    lane; present in ``attrs`` ONLY when not "fp32", so the default
    pool adds no attr and signatures/digests/cost-cache keys stay
    byte-identical to the pre-precision tree).
    """

    op_type = OperatorType.DECODE_ATTENTION
    # the op reads + writes its KV cache through the model-state dict:
    # impure, never remat-wrapped
    writes_state = True
    # use_kernel selects the execution path, not the math — one probe
    # record serves both
    _CALIBRATION_INERT_ATTRS = frozenset({"use_kernel"})

    def __init__(
        self,
        name,
        input_shapes,
        embed_dim: int,
        num_heads: int,
        page_size: int = 16,
        pages_per_seq: int = 8,
        num_pages: int = 0,
        use_kernel: bool = True,
        kv_dtype: str = "fp32",
        kernel_initializer: Initializer | None = None,
    ):
        assert embed_dim % num_heads == 0
        assert page_size >= 1 and pages_per_seq >= 1
        assert kv_dtype in ("fp32", "bf16", "int8"), kv_dtype
        b = input_shapes[0].sizes[0]
        num_pages = num_pages or b * pages_per_seq
        assert num_pages >= b, (
            f"page pool ({num_pages}) smaller than the decode frame's "
            f"sequence slots ({b})")
        self._kernel_init = kernel_initializer or DEFAULT_WEIGHT_INIT
        # extension-only attr discipline (like ServingSpec.signature's
        # occupancy part): the default fp32 pool contributes NO attr
        extra = {} if kv_dtype == "fp32" else {"kv_dtype": kv_dtype}
        super().__init__(
            name,
            input_shapes,
            embed_dim=embed_dim,
            num_heads=num_heads,
            page_size=page_size,
            pages_per_seq=pages_per_seq,
            num_pages=num_pages,
            use_kernel=use_kernel,
            **extra,
        )

    # ---- shapes ----------------------------------------------------------
    def infer(self) -> Sequence[ParallelTensorShape]:
        h = self.input_shapes[0]
        assert h.ndim == 3 and h.sizes[1] == 1, (
            f"decode attention wants [B, 1, E] hidden, got {h.sizes}")
        pt = self.input_shapes[1]
        assert pt.ndim == 2 and pt.sizes[0] == h.sizes[0], pt.sizes
        assert pt.sizes[1] == self.attrs["pages_per_seq"], pt.sizes
        sl = self.input_shapes[2]
        assert sl.ndim == 1 and sl.sizes[0] == h.sizes[0], sl.sizes
        return (
            ParallelTensorShape.make(
                (h.sizes[0], 1, self.attrs["embed_dim"]), h.dtype),
        )

    @property
    def head_dim(self) -> int:
        return self.attrs["embed_dim"] // self.attrs["num_heads"]

    @property
    def max_seqs(self) -> int:
        return self.input_shapes[0].sizes[0]

    @property
    def max_seq_len(self) -> int:
        return self.attrs["page_size"] * self.attrs["pages_per_seq"]

    @property
    def attended_len(self) -> int:
        """Cached positions a query reads at most (the cost hooks')."""
        return self.max_seq_len

    @property
    def kv_dtype(self) -> str:
        return self.attrs.get("kv_dtype", "fp32")

    @property
    def pool_dtype(self):
        return {"fp32": jnp.float32, "bf16": jnp.bfloat16,
                "int8": jnp.int8}[self.kv_dtype]

    def weight_specs(self) -> Sequence[WeightSpec]:
        a = self.attrs
        e, h = a["embed_dim"], a["num_heads"]
        dk = self.head_dim
        qe = self.input_shapes[0].sizes[-1]
        return [
            WeightSpec("wq", (qe, h, dk), DataType.FLOAT32, self._kernel_init),
            WeightSpec("wk", (qe, h, dk), DataType.FLOAT32, self._kernel_init),
            WeightSpec("wv", (qe, h, dk), DataType.FLOAT32, self._kernel_init),
            WeightSpec("wo", (h, dk, e), DataType.FLOAT32, self._kernel_init),
        ]

    # ---- state (the paged KV cache) -------------------------------------
    def state_specs(self):
        """The layer's page-pool cache, in the POOL dtype: fp32 by
        default (decode numerics match the training-side attention's
        accumulate dtype); bf16/int8 under the searched KV-precision
        lane, an int8 pool carrying per-(page, slot) fp32 scales —
        the "page_slot" layout, one symmetric scale per cached token
        shared across heads, so scattering a fresh token never
        rescales already-written slots.

        A pool is [num_pages, page_size, heads · head_dim], heads and
        head_dim FUSED on the minor axis.  Kept apart, a 64-wide minor
        axis pads to the TPU's 128 lanes, so XLA:TPU stores
        [P, page, H, 64] with the page axis minor-most instead, and
        the scatter and the kernel — which compute row-major —
        transposed every pool in and out again on every call (four
        fifths of a decode frame's device time).  The fused axis is
        lane-dense: its default layout is the one its users compute
        in, and a call updates the pool in place."""
        a = self.attrs
        shape = (a["num_pages"], a["page_size"],
                 a["num_heads"] * self.head_dim)
        pool = self.pool_dtype
        specs = [("k_cache", shape, pool, 0), ("v_cache", shape, pool, 0)]
        if self.kv_dtype == "int8":
            sshape = (a["num_pages"], a["page_size"])
            specs += [("k_scale", sshape, jnp.float32, 0.0),
                      ("v_scale", sshape, jnp.float32, 0.0)]
        return specs

    def state_shardings(self, mv: MachineView):
        """ShardAnnot per state var under ``mv`` — the lowering places
        the page pool with it (compiler/lowering.py init_params), so
        the residency ``kv_cache_bytes`` credits is residency the
        compiled program realizes: page dim over the batch axes (each
        device holds its own sequences' pages), the fused head axis
        over the replica axes (decode TP: a split of heads · head_dim
        into r contiguous parts is a split of heads, because the
        replica degree divides ``num_heads`` — max_replica_degree)."""
        b = max(mv.dim_degrees[0], 1) if mv.dim_degrees else 1
        r = max(mv.replica_degree, 1)
        annot = ShardAnnot((b, 1, r), idx=(0, -1, REPLICA_SLOT))
        out = {"k_cache": annot, "v_cache": annot}
        if self.kv_dtype == "int8":
            # the scales shard with the page dim but REPLICATE over the
            # head split — every replica's heads share the per-token
            # scale row
            s_annot = ShardAnnot((b, 1), replica=r, idx=(0, -1))
            out["k_scale"] = s_annot
            out["v_scale"] = s_annot
        return out

    # ---- lowering --------------------------------------------------------
    def attention_path(self, multi_device: bool) -> str:
        """Which attention implementation ``forward`` lowers to:
        ``"pallas"`` (kernels/ragged_paged_attention) or ``"xla"`` (the
        gather/masked path).  The kernel runs when ``use_kernel`` asks
        for it, the shapes satisfy ``paged_kernel_applies``, and the
        mesh is ONE device: GSPMD cannot partition a Mosaic call, and
        the kernel has not been run sharded."""
        from flexflow_tpu.kernels.ragged_paged_attention import (
            paged_kernel_applies,
        )

        if (self.attrs["use_kernel"] and not multi_device
                and paged_kernel_applies(self.head_dim,
                                         self.attrs["page_size"],
                                         self.attrs["num_heads"])):
            return "pallas"
        return "xla"

    def serving_weights(self, weights, compute_dtype):
        """The projections as the matmuls read them: the compute dtype,
        heads and head_dim FUSED — wq/wk/wv [E, H·D], wo [H·D, E] — as
        the pool is (``state_specs``): a bf16 [E, H, 64] leaf would pad
        its 64-wide minor axis to 128 lanes and give the halved bytes
        back, so the cast and the layout are one change.  On a leaf
        already served both steps are no-ops."""
        hd = self.attrs["num_heads"] * self.head_dim
        out = {n: weights[n].astype(compute_dtype).reshape(-1, hd)
               for n in ("wq", "wk", "wv")}
        out["wo"] = weights["wo"].astype(compute_dtype).reshape(hd, -1)
        return out

    @staticmethod
    def _project(x, w):
        """q, k, v of x [..., E] as fp32 [..., H·D], each rounded to
        the compute dtype its matmul made it in.  The barrier holds the
        products as they are: without it XLA folds q's reshape to heads
        into the matmul (a windowed convolution that re-lays-out wq on
        every call) and may skip the rounding of the K/V rows the pool
        keeps (XLA:CPU does, ``xla_allow_excess_precision``)."""
        return tuple(p.astype(jnp.float32) for p in
                     jax.lax.optimization_barrier(
                         tuple(jnp.dot(x, w[n]) for n in ("wq", "wk", "wv"))))

    def forward(self, ctx: LoweringContext, inputs, weights):
        from flexflow_tpu.kernels.ragged_paged_attention import (
            _xla_ragged_paged,
            _xla_ragged_paged_quant,
            ragged_paged_attention,
            ragged_paged_attention_quant,
        )

        a = self.attrs
        hidden, page_table, seq_lens = inputs
        page_table = page_table.astype(jnp.int32)
        seq_lens = seq_lens.astype(jnp.int32)
        cd = ctx.compute_dtype
        x = hidden[:, 0, :].astype(cd)  # [B, E]
        w = self.serving_weights(weights, cd)
        # fresh K/V rows as the pool holds them: heads fused, [B, H·D]
        q, k_new, v_new = self._project(x, w)
        qf = q.reshape(x.shape[0], a["num_heads"], self.head_dim)

        ps = a["page_size"]
        k_cache = ctx.state_in[f"{self.name}/k_cache"]
        v_cache = ctx.state_in[f"{self.name}/v_cache"]
        # scatter the fresh token at position seq_lens[b]: pool page
        # page_table[b, seq_lens[b] // ps], slot seq_lens[b] % ps.
        # EVERY frame row scatters (rows cannot be excluded from a
        # static-shape scatter) — the executor's frame-composition
        # contract is that a row it wants IGNORED points at a page no
        # live sequence owns (runtime/decode.py: an idle slot's own
        # static range, or the reserved scratch page of an
        # oversubscribed pool), so the stray write lands in garbage no
        # one reads.
        slot = seq_lens % ps
        # a full sequence (seq_lens == max_seq_len) must be evicted by
        # the executor before it is stepped again; clamp keeps the
        # gather in-bounds rather than trusting jax's silent clamping
        page_idx = jnp.minimum(seq_lens // ps, self.attrs["pages_per_seq"] - 1)
        page = jnp.take_along_axis(
            page_table, page_idx[:, None], axis=1)[:, 0]
        kvd = self.kv_dtype
        if kvd == "int8":
            # quantize-on-scatter: the fresh token's fp32 rows collapse
            # to int8 + one per-token scale; the pool never holds fp32
            k_q, k_s = _quantize_kv(k_new)
            v_q, v_s = _quantize_kv(v_new)
            k_scale = ctx.state_in[f"{self.name}/k_scale"]
            v_scale = ctx.state_in[f"{self.name}/v_scale"]
            k_cache = k_cache.at[page, slot].set(k_q)
            v_cache = v_cache.at[page, slot].set(v_q)
            k_scale = k_scale.at[page, slot].set(k_s)
            v_scale = v_scale.at[page, slot].set(v_s)
            ctx.state_out[f"{self.name}/k_scale"] = k_scale
            ctx.state_out[f"{self.name}/v_scale"] = v_scale
        else:
            # bf16 stores the cast; fp32 stores the rows UNCHANGED —
            # the historical (bit-identical, test-enforced) path
            k_cache = k_cache.at[page, slot].set(
                k_new.astype(k_cache.dtype))
            v_cache = v_cache.at[page, slot].set(
                v_new.astype(v_cache.dtype))
        ctx.state_out[f"{self.name}/k_cache"] = k_cache
        ctx.state_out[f"{self.name}/v_cache"] = v_cache

        scale = 1.0 / math.sqrt(self.head_dim)
        lens = seq_lens + 1  # the fresh token attends to itself too
        if self.attention_path(ctx.mesh is not None) == "pallas":
            if kvd == "int8":
                out = ragged_paged_attention_quant(
                    qf, k_cache, v_cache, k_scale, v_scale,
                    page_table, lens, scale)
            else:
                out = ragged_paged_attention(
                    qf, k_cache, v_cache, page_table, lens, scale)
        elif kvd == "int8":
            out = _xla_ragged_paged_quant(
                qf, k_cache, v_cache, k_scale, v_scale,
                page_table, lens, scale)
        else:
            out = _xla_ragged_paged(
                qf, k_cache, v_cache, page_table, lens, scale)
        y = jnp.dot(out.astype(cd).reshape(x.shape[0], -1), w["wo"],
                    preferred_element_type=jnp.float32)
        return [y[:, None, :].astype(hidden.dtype)]

    # ---- chunked prefill lowering ---------------------------------------
    # keys a block of the chunk's attention: the scores of one block are
    # [B, C, H, CHUNK_KEY_BLOCK] float32
    CHUNK_KEY_BLOCK = 128

    @property
    def chunk_block_pages(self) -> int:
        """Pages a key block of the chunk's attention spans."""
        return max(1, self.CHUNK_KEY_BLOCK // self.attrs["page_size"])

    def chunk_walk(self, positions, xp=jnp):
        """The key blocks (of ``chunk_block_pages`` pages) a chunk at
        ``positions`` [B, C] attends to: each row's first logical page
        [B] and how many blocks follow it — here from page 0 to the
        chunk's last position.  ``xp`` is the array module: ``jnp`` gives
        ``forward_chunk`` its trip count, ``numpy`` the host its
        ``decode.prefill_keys_walked`` (``chunk_keys_walked``) from the
        very positions it sends."""
        block = self.chunk_block_pages * self.attrs["page_size"]
        return (xp.zeros(positions.shape[:1], xp.int32),
                xp.max(positions) // block + 1)

    def chunk_keys_walked(self, positions) -> int:
        """Keys the blocks of a chunk at ``positions`` (numpy, [B, C])
        cover in this layer, all rows together."""
        _, blocks = self.chunk_walk(positions, np)
        return (int(blocks) * self.chunk_block_pages
                * self.attrs["page_size"] * positions.shape[0])

    def forward_chunk(self, ctx: LoweringContext, inputs, weights):
        """The CHUNKED-PREFILL twin of ``forward``: C prompt tokens per
        sequence in ONE pass instead of one decode frame each.  Inputs:

        * hidden    [B, C, E] — the chunk's token embeddings
        * page_table [B, pages_per_seq]
        * positions [B, C] int32 — each token's absolute cache position.
          THE CONTRACT (``run_chunked_prefill`` is its one sender): a
          row's positions are ONE CONTIGUOUS RUN ``c0 … c0 + C − 1``,
          clamped at ``cap − 1`` (cap = page_size · pages_per_seq); the
          prompt's tokens come first and the pad tail after them, at
          FUTURE positions of the sequence's own allotment — a pad
          write is overwritten by the decode loop before any frame
          reads it, so no masking is needed.  ``cap − 1`` itself is
          always a pad.

        Writes all C tokens' K/V into the page pool page by page
        (``_write_chunk_pages``) and attends each query against cache prefix +
        intra-chunk causal in KEY BLOCKS from page 0 to the chunk's last
        position, with an online softmax: a chunk at position 0 walks
        one block, the last chunk of a full table all of them
        (``chunk_walk``), and no [C, H, table] score tensor
        exists.  The same dtype discipline as ``forward`` (projections
        in the compute dtype, cache and softmax in fp32), so the
        populated cache is numerically the one the token-by-token path
        writes (runtime/prefill.py proves token identity end-to-end)."""
        a = self.attrs
        hidden, page_table, positions = inputs
        page_table = page_table.astype(jnp.int32)
        positions = positions.astype(jnp.int32)
        cd = ctx.compute_dtype
        x = hidden.astype(cd)  # [B, C, E]
        w = self.serving_weights(weights, cd)
        # fresh K/V rows as the pool holds them: [B, C, H·D]
        q, k_new, v_new = self._project(x, w)

        rows = {"k_cache": k_new, "v_cache": v_new}
        if self.kv_dtype == "int8":
            # batched quantize-on-write, same per-token scheme as the
            # decode step — the chunked path populates the SAME pool
            rows["k_cache"], rows["k_scale"] = _quantize_kv(k_new)
            rows["v_cache"], rows["v_scale"] = _quantize_kv(v_new)
        pools = {leaf: ctx.state_in[f"{self.name}/{leaf}"] for leaf in rows}
        pools, out = _chunk_write_and_attend(
            pools, rows, q, page_table, positions,
            self.chunk_walk(positions)[1], num_heads=a["num_heads"],
            block_pages=self.chunk_block_pages, compute_dtype=jnp.dtype(cd))
        for leaf, pool in pools.items():
            ctx.state_out[f"{self.name}/{leaf}"] = pool
        y = jnp.dot(out.astype(cd), w["wo"],
                    preferred_element_type=jnp.float32)
        return [y.astype(hidden.dtype)]

    # ---- degree propagation ---------------------------------------------
    def propagate(self, mv: MachineView) -> OpSharding:
        b, s, e_deg = mv.dim_degrees
        assert s == 1, "decode token dim is length 1 — unsplittable"
        assert e_deg == 1, "embed dim of attention output stays whole"
        assert self.max_seqs % max(b, 1) == 0, (
            "sequence slots must divide evenly over the batch degree")
        r = mv.replica_degree  # head split -> partial sums over wo
        h_annot = ShardAnnot((b, 1, 1), replica=r)
        pt_annot = ShardAnnot((b, 1), replica=r)
        sl_annot = ShardAnnot((b,), replica=r)
        out = ShardAnnot(mv.dim_degrees, replica=r, partial=r > 1)
        R = REPLICA_SLOT
        head_w = ShardAnnot((1, r, 1), replica=b, idx=(-1, R, -1))
        ws = (
            head_w, head_w, head_w,
            ShardAnnot((r, 1, 1), replica=b, idx=(R, -1, -1)),  # wo
        )
        return OpSharding(inputs=(h_annot, pt_annot, sl_annot),
                          weights=ws, outputs=(out,))

    def splittable_output_dims(self) -> Tuple[int, ...]:
        return (0,)  # sequence slots; the token dim is length 1

    def max_replica_degree(self) -> int:
        return self.attrs["num_heads"]

    # ---- cost hooks ------------------------------------------------------
    def flops(self) -> float:
        a = self.attrs
        bsz = self.max_seqs
        e, h, dk = a["embed_dim"], a["num_heads"], self.head_dim
        proj = 2.0 * bsz * e * h * dk * 4  # q, k, v, o projections
        attn = 2.0 * bsz * h * self.attended_len * dk * 2
        return proj + attn

    # KV quantize-overhead pricing (the EQuARX discipline the cost
    # model's wire-precision terms follow, machine_model.QUANT_PASSES):
    # writing a quantized token costs streaming passes over the
    # per-step fp32 token buffer (read the projections, round, write
    # payload + scales).  The READ side's dequant runs in-register on
    # bytes already streamed — its price IS the smaller stream, so no
    # extra read pass is charged.
    KV_QUANT_PASSES = 3.0

    def _kv_payload_bytes_per_token(self) -> float:
        """K + V PAYLOAD bytes per cached token in the pool dtype
        (scales excluded — they shard differently)."""
        itemsize = jnp.dtype(self.pool_dtype).itemsize
        return 2.0 * self.attrs["num_heads"] * self.head_dim * itemsize

    def _kv_scale_bytes_per_token(self) -> float:
        """The int8 pool's per-(page, slot) fp32 k/v scales: 8 bytes
        per cached token, replicated over a head split."""
        return 8.0 if self.kv_dtype == "int8" else 0.0

    def kv_bytes_per_token(self) -> float:
        """K + V bytes one cached token occupies across all heads, in
        the POOL dtype (int8 includes its two fp32 scales)."""
        return (self._kv_payload_bytes_per_token()
                + self._kv_scale_bytes_per_token())

    def kv_cache_bytes(self, mv: MachineView, serving=None) -> float:
        """Per-device resident bytes of this layer's page pool under
        ``mv`` — the KV-residency term of the simulator's HBM check.
        Batch degree shards sequences (each device holds its sequences'
        pages — realized by the executor's slot-aligned allocation),
        the replica degree shards heads; both divide the payload, while
        an int8 pool's scales divide only by batch (each replica needs
        every token's scale).  When the serving arrival model declares
        an expected shared prefix (``ServingSpec.shared_prefix_pages``
        — realized by the executor's radix prefix sharing), residency
        is the SHARED total: the common-prefix pages exist once, not
        once per sequence."""
        tokens = self.attrs["num_pages"] * self.attrs["page_size"]
        b = max(mv.dim_degrees[0], 1) if mv.dim_degrees else 1
        r = max(mv.replica_degree, 1)
        per_dev = (tokens * self._kv_payload_bytes_per_token() / (b * r)
                   + tokens * self._kv_scale_bytes_per_token() / b)
        if serving is not None:
            factor = getattr(serving, "shared_residency_factor", None)
            if factor is not None:
                per_dev *= factor()
        return per_dev

    def bytes_accessed(self) -> float:
        # activations + weights + the full-occupancy cache read (the
        # decode-dominant term: attention streams every live KV byte)
        base = super().bytes_accessed()
        return base + (self.max_seqs * self.attended_len
                       * self.kv_bytes_per_token())

    def sharded_bytes_accessed(self, mv: MachineView,
                               serving=None) -> float:
        """Per-shard bytes under ``mv`` — the decode op's replacement
        for the cost model's uniform ``bytes_accessed() / parts`` rule:
        a head split divides the KV stream like a batch split does (each
        device reads only its own heads' columns), and under a serving
        arrival model the cache read scales with the RAGGED p99 shard
        load instead of full occupancy (search/serving.py
        ``load_factor`` — the currency the serve objective ranks in)."""
        b = max(mv.dim_degrees[0], 1) if mv.dim_degrees else 1
        r = max(mv.replica_degree, 1)
        # activations shard with the sequence slots; the projection
        # weights shard with the HEADS (a batch split replicates them —
        # every device streams the full wq..wo, the head split's real
        # second win beside the balanced cache read)
        act = sum(s.num_bytes for s in self.input_shapes)
        act += sum(s.num_bytes for s in self.output_shapes)
        wbytes = 0.0
        for ws in self._weight_specs:
            n = 1
            for d in ws.shape:
                n *= d
            wbytes += n * ws.dtype.itemsize
        live = self.max_seqs * self.attended_len
        # attention streams each sequence's OWN pages (a prefix shared
        # in residency is still read once per attending sequence), so
        # the stream term never takes the shared-residency discount —
        # the pool DTYPE is what shrinks it
        kv = live * self._kv_payload_bytes_per_token() / (b * r)
        # each replica streams every one of its sequences' scales
        kv += live * self._kv_scale_bytes_per_token() / b
        if serving is not None:
            kv *= serving.load_factor(b)
        quant = 0.0
        if self.kv_dtype != "fp32":
            # quantize overhead on the write path (KV_QUANT_PASSES,
            # class comment): per step each slot collapses one fp32
            # K + V token to the pool dtype
            tok_fp32 = (self.max_seqs * 2.0 * self.attrs["num_heads"]
                        * self.head_dim * 4.0)
            quant = self.KV_QUANT_PASSES * tok_fp32 / (b * r)
        return act / b + wbytes / r + kv + quant


class GroupedDecodeAttentionOp(DecodeAttentionOp):
    """Decode attention with GROUPED query heads (``num_heads`` query
    heads share ``num_kv_heads`` key/value heads, six to one, say), an
    RMS norm on q and on k per head, half-split rotary, a sliding WINDOW
    and a gated output — each present or absent by attr, so one op
    serves a model's window layers and its global layers:

        q = Nq(x Wq) [Hq, D];  k = Nk(x Wk), v = x Wv [Hkv, D]
        q, k turn by their positions              (``rope_theta``)
        o = softmax(q·k / sqrt(D)) v over the last ``window`` positions
            (``window`` 0: over all of them)
        y = (sigmoid(x Wg) * o) Wo                (``gated``)

    hidden [B, 1, E], page_table [B, pages_per_seq], seq_lens [B] ->
    [B, 1, E], as ``DecodeAttentionOp`` (same ``op_type``: the runtime
    finds decode ops by it).  The projections are declared FUSED —
    ``wq``/``wg`` [E, Hq·D], ``wk``/``wv`` [E, Hkv·D], ``wo`` [Hq·D, E] —
    and in ``param_dtype``, so where that is the compute dtype
    ``serving_weights`` changes nothing and a server holds ONE tree.

    TWO KINDS OF PAGE under one table row.  A pool is
    [pages, page_size, Hkv·D].  A global layer (``window`` 0,
    ``ring_pages`` 0): the pool has ``num_pages`` pages and logical page
    j of a sequence is ``page_table[b, j]``.  A window layer
    (``ring_pages`` R > 0): the pool has ``max_seqs · R`` pages,
    sequence slot s owns pages [s·R, (s+1)·R) and logical page j lives
    in ring page ``j mod R``; the slot is read off the row itself,
    ``page_table[b, 0] // pages_per_seq`` — which holds for SLOT-ALIGNED
    tables only (slot i owns pages [i·pps, (i+1)·pps): what
    ``ContinuousBatchingExecutor`` composes where its pool covers every
    slot and prefixes are not shared; it refuses to be built otherwise).
    R must cover the window, a prefill chunk and one page:
    a write at position p lands on the page of position p − R·page_size,
    which then lies below every window that a query at or after
    p − chunk can see.

    A row that must not write — a full sequence's clamped position, a
    prefill chunk's pad rows clamped to ``cap − 1``, which in a ring
    would alias a LIVE page of the window — is kept out of the scatter
    (its page index points past the pool, ``mode="drop"``)."""

    def __init__(
        self,
        name,
        input_shapes,
        num_heads: int,
        num_kv_heads: int,
        head_dim: int,
        page_size: int = 16,
        pages_per_seq: int = 8,
        num_pages: int = 0,
        window: int = 0,
        ring_pages: int = 0,
        rope_theta: float | None = None,
        qk_norm_eps: float | None = None,
        gated: bool = False,
        use_kernel: bool = True,
        kv_dtype: str = "fp32",
        param_dtype: str = "float32",
        kernel_initializer: Initializer | None = None,
        qk_norm_initializer: Initializer | None = None,
    ):
        assert num_heads % num_kv_heads == 0, (num_heads, num_kv_heads)
        assert kv_dtype in ("fp32", "bf16"), kv_dtype
        assert bool(window) == bool(ring_pages), (
            "a window layer's pool is a ring, a global layer's the table's")
        assert not ring_pages or (ring_pages <= pages_per_seq
                                  and ring_pages * page_size
                                  >= window + page_size), (
            f"a ring of {ring_pages} pages of {page_size} cannot hold a "
            f"window of {window}")
        b = input_shapes[0].sizes[0]
        num_pages = num_pages or b * pages_per_seq
        assert num_pages >= b
        self._kernel_init = kernel_initializer or DEFAULT_WEIGHT_INIT
        self._qk_norm_init = qk_norm_initializer or ConstantInitializer(1.0)
        self.scope = "ff.attn.window" if window else "ff.attn.global"
        Operator.__init__(
            self, name, input_shapes,
            embed_dim=input_shapes[0].sizes[-1], num_heads=num_heads,
            num_kv_heads=num_kv_heads, head_dim=head_dim,
            page_size=page_size, pages_per_seq=pages_per_seq,
            num_pages=num_pages, window=int(window),
            ring_pages=int(ring_pages), rope_theta=rope_theta,
            qk_norm_eps=qk_norm_eps, gated=bool(gated),
            use_kernel=use_kernel, kv_dtype=kv_dtype,
            param_dtype=param_dtype)

    @property
    def head_dim(self) -> int:
        return self.attrs["head_dim"]

    @property
    def kv_heads(self) -> int:
        return self.attrs["num_kv_heads"]

    @property
    def pool_pages(self) -> int:
        """Pages of THIS layer's pool: a ring a sequence slot, or the
        table's."""
        r = self.attrs["ring_pages"]
        return self.max_seqs * r if r else self.attrs["num_pages"]

    @property
    def attended_len(self) -> int:
        return min(self.max_seq_len, self.attrs["window"] or self.max_seq_len)

    def weight_specs(self) -> Sequence[WeightSpec]:
        a = self.attrs
        e, d = a["embed_dim"], self.head_dim
        hq, hkv = a["num_heads"] * d, self.kv_heads * d
        pd = DataType.from_any(a["param_dtype"])
        specs = [WeightSpec("wq", (e, hq), pd, self._kernel_init),
                 WeightSpec("wk", (e, hkv), pd, self._kernel_init),
                 WeightSpec("wv", (e, hkv), pd, self._kernel_init),
                 WeightSpec("wo", (hq, e), pd, self._kernel_init)]
        if a["gated"]:
            specs.append(WeightSpec("wg", (e, hq), pd, self._kernel_init))
        if a["qk_norm_eps"] is not None:
            specs += [WeightSpec(n, (d,), DataType.FLOAT32, self._qk_norm_init)
                      for n in ("q_norm", "k_norm")]
        return specs

    def state_specs(self):
        shape = (self.pool_pages, self.attrs["page_size"],
                 self.kv_heads * self.head_dim)
        return [("k_cache", shape, self.pool_dtype, 0),
                ("v_cache", shape, self.pool_dtype, 0)]

    def attention_path(self, multi_device: bool) -> str:
        from flexflow_tpu.kernels.ragged_paged_attention import (
            grouped_kernel_applies,
        )

        if (self.attrs["use_kernel"] and not multi_device
                and grouped_kernel_applies(self.head_dim,
                                           self.attrs["page_size"])):
            return "pallas"
        return "xla"

    def serving_weights(self, weights, compute_dtype):
        """The projections in the compute dtype — their own arrays where
        ``param_dtype`` already is; the norms' gains as they are."""
        return {n: (w if n.endswith("_norm") else w.astype(compute_dtype))
                for n, w in weights.items()}

    # ---- lowering --------------------------------------------------------
    def _project(self, x, positions, weights, cd):
        """x [B, S, E] (compute dtype) at ``positions`` [B, S] -> q
        [B, S, Hq, D] float32 (normed, turned), the K and V rows as the
        pool holds them [B, S, Hkv·D] (K normed and turned: stored so)
        and the output gate [B, S, Hq·D] or None."""
        from flexflow_tpu.ops.attention import half_split_rotary
        from flexflow_tpu.ops.norm import rms_norm

        a = self.attrs
        w = self.serving_weights(weights, cd)
        names = ("wq", "wk", "wv") + (("wg",) if a["gated"] else ())
        # the barrier as in ``DecodeAttentionOp._project``: the products
        # stay plain matmuls, each rounded to the compute dtype
        prods = jax.lax.optimization_barrier(
            tuple(jnp.dot(x, w[n]) for n in names))
        q, k, v = (p.astype(jnp.float32) for p in prods[:3])
        lead, d = x.shape[:2], self.head_dim
        q = q.reshape(*lead, a["num_heads"], d)
        k = k.reshape(*lead, self.kv_heads, d)
        if a["qk_norm_eps"] is not None:
            q = rms_norm(q, w["q_norm"], a["qk_norm_eps"])
            k = rms_norm(k, w["k_norm"], a["qk_norm_eps"])
        if a["rope_theta"] is not None:
            q = half_split_rotary(q, a["rope_theta"], positions)
            k = half_split_rotary(k, a["rope_theta"], positions)
        gate = (jax.nn.sigmoid(prods[3].astype(jnp.float32))
                if a["gated"] else None)
        return q, k.reshape(*lead, -1), v, gate

    def _pages_of(self, page_table, logical):
        """Pool pages of the logical pages ``logical`` [B, n] of each
        row's sequence (class docstring: the table's, or the ring's)."""
        a = self.attrs
        r = a["ring_pages"]
        if r:
            slot = page_table[:, :1] // a["pages_per_seq"]
            return slot * r + logical % r
        return jnp.take_along_axis(
            page_table, jnp.minimum(logical, a["pages_per_seq"] - 1), axis=1)

    def _scatter(self, ctx, page_table, positions, k_rows, v_rows, writes):
        """Put the rows [B, S, Hkv·D] at ``positions`` [B, S] into the
        pool, but for the rows ``writes`` [B, S] excludes."""
        ps = self.attrs["page_size"]
        page = jnp.where(writes, self._pages_of(page_table, positions // ps),
                         self.pool_pages)  # past the pool: dropped
        slot = positions % ps
        out = []
        for leaf, rows in (("k_cache", k_rows), ("v_cache", v_rows)):
            key = f"{self.name}/{leaf}"
            pool = ctx.state_in[key]
            pool = pool.at[page, slot].set(rows.astype(pool.dtype),
                                           mode="drop")
            ctx.state_out[key] = pool
            out.append(pool)
        return out

    def _finish(self, out, gate, weights, cd, dtype):
        """[..., Hq·D] attention output -> gate, output projection."""
        if gate is not None:
            out = out * gate
        y = jnp.dot(out.astype(cd), self.serving_weights(weights, cd)["wo"],
                    preferred_element_type=jnp.float32)
        return y.astype(dtype)

    def forward(self, ctx: LoweringContext, inputs, weights):
        from flexflow_tpu.kernels.ragged_paged_attention import (
            grouped_paged_attention,
        )

        a = self.attrs
        hidden, page_table, seq_lens = inputs
        page_table = page_table.astype(jnp.int32)
        seq_lens = seq_lens.astype(jnp.int32)
        cd = ctx.compute_dtype
        b = hidden.shape[0]
        positions = seq_lens[:, None]  # the fresh token's
        q, k_rows, v_rows, gate = self._project(
            hidden.astype(cd), positions, weights, cd)
        # a full sequence (seq_lens == max_seq_len) has nowhere to write
        k_cache, v_cache = self._scatter(
            ctx, page_table, positions, k_rows, v_rows,
            positions < self.max_seq_len)

        ps, w = a["page_size"], a["window"]
        lens = seq_lens + 1  # the fresh token attends to itself too
        if w:
            starts = jnp.maximum(lens - w, 0) // ps
            n_walk = min(a["pages_per_seq"], -(-w // ps) + 1)
        else:
            starts = jnp.zeros_like(lens)
            n_walk = a["pages_per_seq"]
        walk = self._pages_of(
            page_table, starts[:, None] + jnp.arange(n_walk, dtype=jnp.int32))
        out = grouped_paged_attention(
            q[:, 0], k_cache, v_cache, walk, lens, starts, w,
            1.0 / math.sqrt(self.head_dim),
            use_kernel=self.attention_path(ctx.mesh is not None) == "pallas")
        return [self._finish(out.reshape(b, 1, -1), gate, weights, cd,
                             hidden.dtype)]

    # the scores of one block are [B, Hq, C, CHUNK_KEY_BLOCK] float32
    CHUNK_KEY_BLOCK = 512

    def chunk_walk(self, positions, xp=jnp):
        """``DecodeAttentionOp.chunk_walk``, by layer type.  A global
        layer walks from page 0 to the last position that WRITES — a pad
        row's clamped position (``cap − 1``) is no key anyone needs; a
        window layer a fixed count from the window's first page."""
        a = self.attrs
        ps, w = a["page_size"], a["window"]
        block = self.chunk_block_pages * ps
        if w:
            lo = xp.maximum(xp.min(positions, axis=1) - w + 1, 0) // ps
            return lo, -(-(w + positions.shape[1] + ps) // block) + 1
        writes = positions < self.max_seq_len - 1
        last = xp.max(xp.where(writes, positions, 0))
        return xp.zeros(positions.shape[:1], xp.int32), last // block + 1

    def forward_chunk(self, ctx: LoweringContext, inputs, weights):
        """C prompt tokens a sequence in one pass (``DecodeAttentionOp.
        forward_chunk``'s contract).  The chunk's K/V are scattered
        first; attention then runs in KEY BLOCKS with an online softmax
        — from the window's first page (page 0 in a global layer) to the
        chunk's last position — so no [C, H, context] score tensor
        exists.  A position of ``cap − 1`` is always a pad
        (``run_chunked_prefill`` clamps there; a prompt's prefilled
        tokens end at ``cap − 2``) and is kept out of the scatter."""
        from flexflow_tpu.kernels.ragged_paged_attention import NEG_INF

        a = self.attrs
        hidden, page_table, positions = inputs
        page_table = page_table.astype(jnp.int32)
        positions = positions.astype(jnp.int32)
        cd = ctx.compute_dtype
        b, c = hidden.shape[:2]
        q, k_rows, v_rows, gate = self._project(
            hidden.astype(cd), positions, weights, cd)
        writes = positions < self.max_seq_len - 1
        k_cache, v_cache = self._scatter(
            ctx, page_table, positions, k_rows, v_rows, writes)

        ps, w, d = a["page_size"], a["window"], self.head_dim
        hkv = self.kv_heads
        g = a["num_heads"] // hkv
        bp = self.chunk_block_pages  # pages a key block
        scale = 1.0 / math.sqrt(d)
        qg = q.reshape(b, c, hkv, g, d).astype(cd)
        lo, blocks = self.chunk_walk(positions)

        def block(i, carry):
            m, l, acc = carry
            logical = (lo[:, None] + i * bp
                       + jnp.arange(bp, dtype=jnp.int32)[None, :])  # [B, bp]
            pages = self._pages_of(page_table, logical)
            kb = k_cache[pages].reshape(b, bp * ps, hkv, d).astype(cd)
            vb = v_cache[pages].reshape(b, bp * ps, hkv, d).astype(cd)
            key_pos = (logical[:, :, None] * ps
                       + jnp.arange(ps, dtype=jnp.int32)).reshape(b, bp * ps)
            s = jnp.einsum("bchgd,bkhd->bhgck", qg, kb,
                           preferred_element_type=jnp.float32) * scale
            seen = key_pos[:, None, :] <= positions[:, :, None]  # [B, C, K]
            if w:
                seen &= key_pos[:, None, :] > positions[:, :, None] - w
            seen = seen[:, None, None]
            m_new = jnp.maximum(
                m, jnp.max(jnp.where(seen, s, NEG_INF), axis=-1))
            p = jnp.where(seen, jnp.exp(s - m_new[..., None]), 0.0)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhgck,bkhd->bhgcd", p.astype(cd), vb,
                preferred_element_type=jnp.float32)
            return m_new, l, acc

        stat = jnp.zeros((b, hkv, g, c), jnp.float32)
        _, l, acc = jax.lax.fori_loop(
            0, blocks, block,
            (stat + NEG_INF, stat, jnp.zeros((b, hkv, g, c, d), jnp.float32)))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        out = out.transpose(0, 3, 1, 2, 4).reshape(b, c, -1)
        return [self._finish(out, gate, weights, cd, hidden.dtype)]

    # ---- degree propagation ---------------------------------------------
    def propagate(self, mv: MachineView) -> OpSharding:
        b, s, e_deg = mv.dim_degrees
        assert s == 1 and e_deg == 1 and mv.replica_degree == 1, (
            "grouped decode attention splits over sequence slots only")
        ws = tuple(ShardAnnot((1,) * len(spec.shape), replica=b)
                   for spec in self._weight_specs)
        return OpSharding(
            inputs=(ShardAnnot((b, 1, 1)), ShardAnnot((b, 1)),
                    ShardAnnot((b,))),
            weights=ws, outputs=(ShardAnnot(mv.dim_degrees),))

    def max_replica_degree(self) -> int:
        return 1  # no head split: the kernel spans a row's heads

    # ---- cost hooks ------------------------------------------------------
    def flops(self) -> float:
        a = self.attrs
        e, d = a["embed_dim"], self.head_dim
        hq, hkv = a["num_heads"] * d, self.kv_heads * d
        proj = 2.0 * self.max_seqs * e * (
            (3 if a["gated"] else 2) * hq + 2 * hkv)
        return proj + 4.0 * self.max_seqs * hq * self.attended_len

    def _kv_payload_bytes_per_token(self) -> float:
        return (2.0 * self.kv_heads * self.head_dim
                * jnp.dtype(self.pool_dtype).itemsize)

    def kv_cache_bytes(self, mv: MachineView, serving=None) -> float:
        b = max(mv.dim_degrees[0], 1) if mv.dim_degrees else 1
        return (self.pool_pages * self.attrs["page_size"]
                * self._kv_payload_bytes_per_token() / b)
