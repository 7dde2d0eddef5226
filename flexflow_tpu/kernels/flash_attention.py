"""Flash attention — Pallas TPU kernel.

Replaces the reference's cuDNN multi-head attention kernel
(reference: src/ops/attention.cu cudnnMultiHeadAttnForward) with an
online-softmax blocked kernel that never materializes the [Sq, Sk]
score matrix in HBM: the canonical TPU formulation with a sequential
grid over KV blocks and VMEM scratch accumulators (m, l, acc) that
persist across grid steps.

Layout: q, k, v are [B, S, H, D] ("bshd", matching the MHA op).  The
kernel runs per (batch*head, q-block) with KV blocks innermost.

Backward: fully blocked Pallas kernels (flash-attention backward) —
the forward saves per-row logsumexp; the backward recomputes scores
block-by-block and accumulates dq (one kernel, kv-blocks inner) and
dk/dv (second kernel, q-blocks inner) in VMEM scratch, so no [Sq, Sk]
matrix ever exists in HBM in either direction.  (The reference has a
monolithic cuDNN backward, src/ops/attention.cu; blocked recompute is
the TPU-native formulation.)  The partial-output variant used by ring
attention chunks its recompute backward over q blocks for the same
O(S·block) memory bound.

On non-TPU backends the kernel runs in interpreter mode so tests cover
the same code path.
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _mosaic_params(interpret: bool):
    """Grid dims (BH, outer-block) are independent; only the innermost
    accumulation dim carries scratch state — telling Mosaic lets it
    pipeline block loads across grid steps."""
    if interpret:
        return {}
    return {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        )
    }


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, *refs,
    scale: float, causal: bool, block_q: int, block_k: int, q_k_offset: int,
    partial_out: bool = False, save_lse: bool = False,
):
    """Grid: (BH, num_q_blocks, num_k_blocks) — k innermost (sequential
    on TPU), so scratch accumulators carry across k steps.
    ``q_k_offset`` = Sk - Sq aligns the causal diagonal at the sequence
    END (query i attends to keys <= i + offset), matching tril(k=sk-sq).
    With ``partial_out`` the kernel emits UNNORMALIZED (acc, m, l) so
    callers (ring attention) can merge partials across devices.  With
    ``save_lse`` it additionally emits per-row logsumexp — the residual
    the blocked backward needs."""
    if partial_out:
        m_out, l_out, m_scratch, l_scratch, acc_scratch = refs
    elif save_lse:
        lse_out, m_scratch, l_scratch, acc_scratch = refs
    else:
        m_scratch, l_scratch, acc_scratch = refs
    kb = pl.program_id(2)
    nk = pl.num_programs(2)
    qb = pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        m_scratch[:] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[:] = jnp.zeros_like(l_scratch)
        acc_scratch[:] = jnp.zeros_like(acc_scratch)

    run = True
    if causal:
        # skip blocks strictly above the (end-aligned) diagonal
        run = (kb * block_k) <= (qb * block_q + block_q - 1 + q_k_offset)

    @pl.when(run if causal else True)
    def _step():
        # dots take the refs' native dtype (bf16 on the bench path) with
        # fp32 MXU accumulation — upcasting the INPUTS to fp32 would run
        # the matmuls at the multi-pass fp32 rate, ~4x slower on the MXU
        q = q_ref[0]  # [bq, D]
        k = k_ref[0]  # [bk, D]
        v = v_ref[0]  # [bk, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [bq, bk] fp32
        if causal:
            rows = qb * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(rows + q_k_offset >= cols, s, NEG_INF)
        m_prev = m_scratch[:]  # [bq, 1]
        l_prev = l_scratch[:]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scratch[:] = acc_scratch[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scratch[:] = m_new
        l_scratch[:] = l_new

    @pl.when(kb == nk - 1)
    def _finish():
        if partial_out:
            o_ref[0] = acc_scratch[:].astype(o_ref.dtype)
            m_out[0] = m_scratch[:].astype(m_out.dtype)
            l_out[0] = l_scratch[:].astype(l_out.dtype)
        else:
            l = jnp.maximum(l_scratch[:], 1e-30)
            o_ref[0] = (acc_scratch[:] / l).astype(o_ref.dtype)
            if save_lse:
                lse_out[0] = (m_scratch[:] + jnp.log(l)).astype(lse_out.dtype)


def _flash_forward(q, k, v, causal: bool, scale: float,
                   block_q: int, block_k: int, interpret: bool,
                   save_lse: bool = False):
    b, sq, h, d = q.shape
    sk = k.shape[1]
    d_v = v.shape[-1]  # the value heads may be narrower than q/k's
    # [B, S, H, D] -> [B*H, S, D]
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * h, sk, d_v)

    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk, block_q, block_k)
    grid = (b * h, sq // block_q, sk // block_k)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, q_k_offset=sk - sq,
        save_lse=save_lse,
    )
    scratch = [
        pltpu.VMEM((block_q, 1), jnp.float32),
        pltpu.VMEM((block_q, 1), jnp.float32),
        pltpu.VMEM((block_q, d_v), jnp.float32),
    ]
    qspec = pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0))
    out_specs = pl.BlockSpec((1, block_q, d_v), lambda bh, i, j: (bh, i, 0))
    out_shape = jax.ShapeDtypeStruct((b * h, sq, d_v), q.dtype)
    if save_lse:
        out_specs = [out_specs,
                     pl.BlockSpec((1, block_q, 1), lambda bh, i, j: (bh, i, 0))]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((b * h, sq, 1), jnp.float32)]
    res = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            qspec,
            pl.BlockSpec((1, block_k, d), lambda bh, i, j: (bh, j, 0)),
            pl.BlockSpec((1, block_k, d_v), lambda bh, i, j: (bh, j, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
        name="flash_fwd",
        **_mosaic_params(interpret),
    )(qt, kt, vt)
    if save_lse:
        out, lse = res
        return out.reshape(b, h, sq, d_v).transpose(0, 2, 1, 3), lse
    return res.reshape(b, h, sq, d_v).transpose(0, 2, 1, 3)


def _flash_bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scratch,
    *, scale: float, causal: bool, block_q: int, block_k: int, q_k_offset: int,
):
    """dq = sum_j ds_ij @ k_j, ds = p * (do v^T - delta) * scale.
    Grid (BH, nq, nk), kv innermost; dq accumulates in VMEM scratch."""
    kb = pl.program_id(2)
    nk = pl.num_programs(2)
    qb = pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        dq_scratch[:] = jnp.zeros_like(dq_scratch)

    run = True
    if causal:
        run = (kb * block_k) <= (qb * block_q + block_q - 1 + q_k_offset)

    @pl.when(run if causal else True)
    def _step():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]  # [bq, 1]
        delta = delta_ref[0]  # [bq, 1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if causal:
            rows = qb * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(rows + q_k_offset >= cols, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bq, bk]
        ds = p * (dp - delta.astype(jnp.float32)) * scale
        dq_scratch[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(kb == nk - 1)
    def _finish():
        dq_ref[0] = dq_scratch[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_scratch, dv_scratch,
    *, scale: float, causal: bool, block_q: int, block_k: int, q_k_offset: int,
):
    """dk_j = sum_i ds_ij^T @ q_i, dv_j = sum_i p_ij^T @ do_i.
    Grid (BH, nk, nq), q innermost; dk/dv accumulate in VMEM scratch."""
    ib = pl.program_id(2)
    nq = pl.num_programs(2)
    jb = pl.program_id(1)

    @pl.when(ib == 0)
    def _init():
        dk_scratch[:] = jnp.zeros_like(dk_scratch)
        dv_scratch[:] = jnp.zeros_like(dv_scratch)

    run = True
    if causal:
        # the i-block contributes unless every row is masked for every
        # col of the j-block: max row + offset >= min col
        run = (ib * block_q + block_q - 1 + q_k_offset) >= (jb * block_k)

    @pl.when(run if causal else True)
    def _step():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]
        delta = delta_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [bq, bk]
        if causal:
            rows = ib * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = jb * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(rows + q_k_offset >= cols, s, NEG_INF)
        p = jnp.exp(s - lse)
        pc = p.astype(do.dtype)
        dv_scratch[:] += jax.lax.dot_general(
            pc, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bk, D]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta.astype(jnp.float32)) * scale
        dk_scratch[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bk, D]

    @pl.when(ib == nq - 1)
    def _finish():
        dk_ref[0] = dk_scratch[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scratch[:].astype(dv_ref.dtype)


def _flash_backward(q, k, v, o, lse, do, causal, scale,
                    block_q, block_k, interpret):
    """Blocked flash backward: q,k [B,S,H,D], v,o,do [B,S,H,Dv] (Dv = D
    unless the value heads are narrower), lse [B*H,Sq,1]."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    d_v = v.shape[-1]
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * h, sk, d_v)
    # do stays in the inputs' dtype so the kernel's dots run at bf16
    # MXU rate; delta (a reduction) is computed in fp32 outside
    dot = do.transpose(0, 2, 1, 3).reshape(b * h, sq, d_v).astype(q.dtype)
    ot = o.transpose(0, 2, 1, 3).reshape(b * h, sq, d_v).astype(jnp.float32)
    delta = jnp.sum(dot.astype(jnp.float32) * ot, axis=-1, keepdims=True)

    qspec = pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0))
    kspec = pl.BlockSpec((1, block_k, d), lambda bh, i, j: (bh, j, 0))
    vspec = pl.BlockSpec((1, block_k, d_v), lambda bh, i, j: (bh, j, 0))
    dospec = pl.BlockSpec((1, block_q, d_v), lambda bh, i, j: (bh, i, 0))
    rspec = pl.BlockSpec((1, block_q, 1), lambda bh, i, j: (bh, i, 0))
    kernel_kw = dict(scale=scale, causal=causal, block_q=block_q,
                     block_k=block_k, q_k_offset=sk - sq)
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, **kernel_kw),
        grid=(b * h, sq // block_q, sk // block_k),
        in_specs=[qspec, kspec, vspec, dospec, rspec, rspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
        **_mosaic_params(interpret),
    )(qt, kt, vt, dot, lse, delta)

    # roles of the two non-BH grid axes swap: axis1 = kv block, axis2 = q
    qspec2 = pl.BlockSpec((1, block_q, d), lambda bh, j, i: (bh, i, 0))
    kspec2 = pl.BlockSpec((1, block_k, d), lambda bh, j, i: (bh, j, 0))
    vspec2 = pl.BlockSpec((1, block_k, d_v), lambda bh, j, i: (bh, j, 0))
    dospec2 = pl.BlockSpec((1, block_q, d_v), lambda bh, j, i: (bh, i, 0))
    rspec2 = pl.BlockSpec((1, block_q, 1), lambda bh, j, i: (bh, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, **kernel_kw),
        grid=(b * h, sk // block_k, sq // block_q),
        in_specs=[qspec2, kspec2, vspec2, dospec2, rspec2, rspec2],
        out_specs=[kspec2, vspec2],
        out_shape=[jax.ShapeDtypeStruct((b * h, sk, d), k.dtype),
                   jax.ShapeDtypeStruct((b * h, sk, d_v), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d_v), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dkv",
        **_mosaic_params(interpret),
    )(qt, kt, vt, dot, lse, delta)

    dq = dq.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
    dk = dk.reshape(b, h, sk, d).transpose(0, 2, 1, 3)
    dv = dv.reshape(b, h, sk, d_v).transpose(0, 2, 1, 3)
    return dq, dk, dv


def _attn_logits_probs(q, k, causal, scale):
    # inputs stay in their native dtype (bf16 on TPU) — the MXU
    # accumulates in fp32 via preferred_element_type; upcasting inputs
    # would force the slow multi-pass fp32 matmul
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    logits = logits * scale
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(mask, logits, NEG_INF)
    return jax.nn.softmax(logits, axis=-1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _attn_core(q, k, v, causal, scale):
    """Dropout-free attention core with a COMPACT-residual backward.

    Plain autodiff of the einsum path saves the fp32 logits AND fp32
    probs ([B,H,Sq,Sk] each, per layer) between forward and backward —
    the dominant HBM residual of a short-seq transformer train step
    (the bench workload's compiled HLO held 100+ fp32 score-shaped
    buffers).  This custom VJP saves only (q, k, v, probs-at-q.dtype):
    under a bf16 activation stream that halves the probs residual and
    removes the fp32 logits residual entirely; in fp32 mode the cast is
    the identity and the backward matches plain autodiff to round-off
    (same formula, fused differently).  Reverse-mode only, like the
    Pallas kernel (custom_vjp forbids forward mode) — jvp/jacfwd
    callers set COMPACT_ATTENTION_VJP = False to get the plain-autodiff
    einsum path back."""
    probs = _attn_logits_probs(q, k, causal, scale)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(q.dtype), v)


def _attn_core_fwd(q, k, v, causal, scale):
    # nondiff args keep their primal positions in fwd (only bwd gets
    # them moved to the front)
    probs = _attn_logits_probs(q, k, causal, scale).astype(q.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    return out, (q, k, v, probs)


def _softmax_qk_grads(pf, gp, q, k, causal, scale):
    """Shared backward tail: softmax VJP from saved fp32 probs ``pf``
    and probs-cotangent ``gp``, then the q/k einsum grads.
    PARTIALLY-masked entries have p == 0 exactly (exp underflow), so
    their gradient vanishes without consulting the mask again;
    FULLY-masked rows (i < sq-sk in causal cross-attention) softmax to
    uniform 1/sk, not 0 — zero their logit grads the way the
    where-mask VJP does in plain autodiff."""
    gs = (pf * (gp - jnp.sum(pf * gp, axis=-1, keepdims=True))) * scale
    if causal:
        sq, sk = gs.shape[-2], gs.shape[-1]
        if sq > sk:
            rows = jnp.arange(sq)[:, None]
            gs = jnp.where(rows < sq - sk, 0.0, gs)
    gq = jnp.einsum("bhqk,bkhd->bqhd", gs.astype(q.dtype), k,
                    preferred_element_type=jnp.float32).astype(q.dtype)
    gk = jnp.einsum("bhqk,bqhd->bkhd", gs.astype(q.dtype), q,
                    preferred_element_type=jnp.float32).astype(k.dtype)
    return gq, gk


def _attn_core_bwd(causal, scale, res, g):
    q, k, v, p = res
    pf = p.astype(jnp.float32)
    gv = jnp.einsum("bhqk,bqhd->bkhd", p, g.astype(p.dtype),
                    preferred_element_type=jnp.float32).astype(v.dtype)
    gp = jnp.einsum("bqhd,bkhd->bhqk", g, v,
                    preferred_element_type=jnp.float32)
    gq, gk = _softmax_qk_grads(pf, gp, q, k, causal, scale)
    return gq, gk, gv


_attn_core.defvjp(_attn_core_fwd, _attn_core_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _attn_core_dropout(q, k, v, mask, causal, scale, keep):
    """Attention with post-softmax dropout, compact residuals: saves
    (q, k, v, probs-at-q.dtype, bool mask) instead of autodiff's fp32
    logits + fp32 probs + mask — the same residual diet as _attn_core
    for the dropout-training regime (the reference's BERT workloads
    train with attention dropout).  Reverse-mode only."""
    # body mirrors _attn_core_dropout_fwd exactly (probs round to
    # q.dtype BEFORE the keep-scaling) so primal and fwd agree bitwise
    probs = _attn_logits_probs(q, k, causal, scale).astype(q.dtype)
    dropped = jnp.where(mask, probs.astype(jnp.float32) / keep, 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", dropped.astype(q.dtype), v)


def _attn_core_dropout_fwd(q, k, v, mask, causal, scale, keep):
    probs = _attn_logits_probs(q, k, causal, scale).astype(q.dtype)
    dropped = jnp.where(mask, probs.astype(jnp.float32) / keep, 0.0)
    out = jnp.einsum("bhqk,bkhd->bqhd", dropped.astype(q.dtype), v)
    return out, (q, k, v, probs, mask)


def _attn_core_dropout_bwd(causal, scale, keep, res, g):
    q, k, v, p, mask = res
    pf = p.astype(jnp.float32)
    dropped = jnp.where(mask, pf / keep, 0.0)
    gv = jnp.einsum("bhqk,bqhd->bkhd", dropped.astype(q.dtype),
                    g.astype(q.dtype),
                    preferred_element_type=jnp.float32).astype(v.dtype)
    g_dropped = jnp.einsum("bqhd,bkhd->bhqk", g, v,
                           preferred_element_type=jnp.float32)
    gp = jnp.where(mask, g_dropped / keep, 0.0)  # where-VJP of dropout
    gq, gk = _softmax_qk_grads(pf, gp, q, k, causal, scale)
    return gq, gk, gv, None


_attn_core_dropout.defvjp(_attn_core_dropout_fwd, _attn_core_dropout_bwd)


# escape hatch for forward-mode (jvp/jacfwd) callers: custom_vjp
# forbids forward-mode autodiff, so setting this False routes
# _xla_attention through plain-autodiff einsums (fat fp32 residuals,
# full differentiability) — nothing in the training stack needs it
COMPACT_ATTENTION_VJP = True


def _xla_attention(q, k, v, causal, scale, dropout_rate=0.0, dropout_rng=None):
    dropout_active = dropout_rate > 0.0 and dropout_rng is not None
    if not COMPACT_ATTENTION_VJP:
        probs = _attn_logits_probs(q, k, causal, scale)
        if dropout_active:
            keep = 1.0 - dropout_rate
            mask = jax.random.bernoulli(dropout_rng, keep, probs.shape)
            probs = jnp.where(mask, probs / keep, 0.0)
        return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(q.dtype), v)
    if not dropout_active:
        return _attn_core(q, k, v, causal, float(scale))
    keep = 1.0 - dropout_rate
    b, sq, h, _ = q.shape
    mask = jax.random.bernoulli(dropout_rng, keep,
                                (b, h, sq, k.shape[1]))
    return _attn_core_dropout(q, k, v, mask, causal, float(scale),
                              float(keep))


def _xla_attention_partial(q, k, v, causal, scale):
    """Unnormalized blockwise partials (acc, m, l) in fp32, layout
    acc [B,H,Sq,D], m/l [B,H,Sq,1] — the XLA fallback twin of the
    partial-out Pallas path, and its recompute-backward reference."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    acc = jnp.einsum("bhqk,bkhd->bhqd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return acc, m, l


def _flash_forward_partial(q, k, v, causal, scale, block_q, block_k, interpret):
    """Pallas partial-out forward: returns (acc, m, l) shaped
    [B,H,Sq,D] / [B,H,Sq,1] fp32."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    grid = (b * h, sq // block_q, sk // block_k)
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, q_k_offset=sk - sq,
        partial_out=True,
    )
    qspec = pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0))
    sspec = pl.BlockSpec((1, block_q, 1), lambda bh, i, j: (bh, i, 0))
    acc, m, l = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            qspec,
            pl.BlockSpec((1, block_k, d), lambda bh, i, j: (bh, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, i, j: (bh, j, 0)),
        ],
        out_specs=[qspec, sspec, sspec],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, d), jnp.float32),
            jax.ShapeDtypeStruct((b * h, sq, 1), jnp.float32),
            jax.ShapeDtypeStruct((b * h, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd_partial",
        **_mosaic_params(interpret),
    )(qt, kt, vt)
    return (
        acc.reshape(b, h, sq, d),
        m.reshape(b, h, sq, 1),
        l.reshape(b, h, sq, 1),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_partial_vjp(q, k, v, causal, scale, block_q, block_k):
    return _fap_fwd(q, k, v, causal, scale, block_q, block_k)[0]


def flash_attention_partial(
    q, k, v, causal: bool = False, scale: float | None = None,
    block_q: int = 512, block_k: int = 1024,
):
    """Blocked attention partials for cross-device merging (ring
    attention): q,k,v [B,S,H,D] -> (acc [B,H,Sq,D], m, l [B,H,Sq,1]),
    all fp32 and unnormalized (out = acc/l after merging)."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return _flash_partial_vjp(q, k, v, causal, scale, block_q, block_k)


def _fap_fwd(q, k, v, causal, scale, block_q, block_k):
    interpret = jax.default_backend() != "tpu"
    sq, sk = q.shape[1], k.shape[1]
    bq = _pick_block(sq, block_q)
    bk = _pick_block(sk, block_k)
    if bq is None or bk is None or q.shape[-1] % 8 != 0:
        out = _xla_attention_partial(q, k, v, causal, scale)
    else:
        out = _flash_forward_partial(q, k, v, causal, scale, bq, bk, interpret)
    return out, (q, k, v)


def _xla_attention_partial_at(q, k, v, causal, scale, row_offset, sq_total):
    """_xla_attention_partial for a q-chunk whose first row sits at
    global position ``row_offset`` of a length-``sq_total`` query
    sequence (the causal mask is global, so chunking must not shift the
    diagonal)."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        sk = s.shape[-1]
        rows = row_offset + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 3)
        s = jnp.where(rows + (sk - sq_total) >= cols, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    acc = jnp.einsum("bhqk,bkhd->bhqd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return acc, m, l


def _fap_bwd(causal, scale, block_q, block_k, res, g):
    """Recompute backward CHUNKED over q blocks: peak memory
    O(block_q · Sk) per step instead of the full [Sq, Sk] matrix, with
    dk/dv accumulated in a scan carry."""
    q, k, v = res
    b, sq, h, d = q.shape
    # chunk the recompute backward at <=128 rows regardless of the
    # (large, speed-tuned) forward block so the O(bq*Sk) memory bound
    # holds even when the forward block covers the whole shard
    bq = _pick_block(sq, min(block_q, 128)) or sq
    if sq % bq != 0 or sq == bq:
        def f(q, k, v):
            return _xla_attention_partial(q, k, v, causal, scale)

        _, vjp = jax.vjp(f, q, k, v)
        return vjp(g)
    dacc, dm, dl = g
    nq = sq // bq
    q_chunks = q.reshape(b, nq, bq, h, d).transpose(1, 0, 2, 3, 4)
    dacc_c = dacc.reshape(b, h, nq, bq, d).transpose(2, 0, 1, 3, 4)
    dm_c = dm.reshape(b, h, nq, bq, 1).transpose(2, 0, 1, 3, 4)
    dl_c = dl.reshape(b, h, nq, bq, 1).transpose(2, 0, 1, 3, 4)
    offsets = jnp.arange(nq, dtype=jnp.int32) * bq

    def body(carry, args):
        dk_acc, dv_acc = carry
        qc, daccc, dmc, dlc, off = args

        def f(qc, k, v):
            return _xla_attention_partial_at(qc, k, v, causal, scale, off, sq)

        _, vjp = jax.vjp(f, qc, k, v)
        dqc, dkc, dvc = vjp((daccc, dmc, dlc))
        return (dk_acc + dkc, dv_acc + dvc), dqc

    (dk, dv), dq_chunks = jax.lax.scan(
        body,
        (jnp.zeros(k.shape, jnp.float32), jnp.zeros(v.shape, jnp.float32)),
        (q_chunks, dacc_c, dm_c, dl_c, offsets),
    )
    dq = dq_chunks.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, d)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash_partial_vjp.defvjp(_fap_fwd, _fap_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention_vjp(q, k, v, causal, scale, block_q, block_k):
    return _fa_fwd(q, k, v, causal, scale, block_q, block_k)[0]


def flash_attention(
    q, k, v, causal: bool = False, scale: float | None = None,
    block_q: int | None = None, block_k: int | None = None,
):
    """q, k: [B, S, H, D], v: [B, S, H, Dv] -> [B, Sq, H, Dv] (Dv may
    differ from D: latent attention's 192-wide q/k beside 128-wide v).

    Default blocks are large (512/1024): per-grid-step overhead on the
    TPU dominates at small blocks — measured on v5e, bq 512 is ~5x
    faster than the canonical GPU-ish 128."""
    if block_q is None:
        block_q = 512
    if block_k is None:
        block_k = 1024
    return _flash_attention_vjp(q, k, v, causal, scale, block_q, block_k)


def flash_profitable(sq: int, sk: int) -> bool:
    """THE shape rule that picks the flash kernel over XLA's fused
    attention (measured on v5e, BENCH_LONGCTX.md): below ~512 keys the
    [Sq, Sk] tile fits comfortably and XLA's fused attention beats the
    kernel's launch + lse/delta traffic; above it flash wins (3x at
    4k, and XLA falls off a memory cliff by 8k).  Long-Sq
    cross-attention also wants flash (the materialized logits scale
    with Sq*Sk)."""
    return sk >= 512 or sq * sk >= 512 * 2048


def flash_attention_sharded(
    q, k, v, mesh, batch_axes: Tuple[str, ...] = (),
    head_axes: Tuple[str, ...] = (), causal: bool = False,
    scale: float | None = None,
):
    """``flash_attention`` on a multi-device mesh: q, k, v [B, S, H, D]
    sharded on dim 0 over ``batch_axes`` and dim 2 over ``head_axes``
    (either may be empty), sequence whole.  GSPMD refuses to partition
    a Mosaic call ("Mosaic kernels cannot be automatically
    partitioned"), so the kernel runs per shard under ``shard_map`` —
    attention is independent across batch rows and heads, so no
    collective is needed."""
    from jax.sharding import PartitionSpec as P

    from flexflow_tpu.comm.compat import shard_map

    spec = P(tuple(batch_axes) or None, None, tuple(head_axes) or None,
             None)
    return shard_map(
        functools.partial(flash_attention, causal=causal, scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
    )(q, k, v)


def _pick_block(size: int, want: int):
    """Largest power-of-two block <= want that divides size (None if
    size has no power-of-two divisor >= 8 small enough to tile)."""
    b = 1 << (want.bit_length() - 1)
    while b >= 8:
        if b <= size and size % b == 0:
            return b
        b //= 2
    return None


def _fa_fwd(q, k, v, causal, scale, block_q, block_k):
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    interpret = jax.default_backend() != "tpu"
    sq, sk = q.shape[1], k.shape[1]
    bq = _pick_block(sq, block_q)
    bk = _pick_block(sk, block_k)
    if (bq is None or bk is None or q.shape[-1] % 8 != 0
            or v.shape[-1] % 8 != 0):
        out = _xla_attention(q, k, v, causal, scale)  # shape rule
        return out, (q, k, v, None, None)
    out, lse = _flash_forward(q, k, v, causal, scale, bq, bk, interpret,
                              save_lse=True)
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, scale, block_q, block_k, res, g):
    """Blocked Pallas backward using the saved logsumexp; peak memory
    O(S·block) (the round-2 recompute backward re-materialized the full
    [Sq, Sk] probs and gave back the forward's memory win)."""
    q, k, v, o, lse = res
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if lse is None:
        # forward took the XLA fallback (odd shapes): recompute backward
        def f(q, k, v):
            return _xla_attention(q, k, v, causal, scale)

        _, vjp = jax.vjp(f, q, k, v)
        return vjp(g)
    sq, sk = q.shape[1], k.shape[1]
    bq = _pick_block(sq, block_q)
    bk = _pick_block(sk, block_k)
    interpret = jax.default_backend() != "tpu"
    return _flash_backward(q, k, v, o, lse, g, causal, scale, bq, bk,
                           interpret)


_flash_attention_vjp.defvjp(_fa_fwd, _fa_bwd)
