"""EQuARX-style quantized allreduce (arXiv:2506.17615).

The weight-gradient allreduce is the dominant term of sync-bound data
parallelism (per-device batch 1, full widths).  EQuARX shows a
block-scaled int8 allreduce inside XLA cuts that wire time ~2-4x; the
cross-replica weight-update sharding paper (arXiv:2004.13336, our
ZeRO-1 path) already treats sync cost as a first-class lever.  This
module is the execution half: a quantized allreduce built from
``psum_scatter``/``all_gather`` with per-chunk scales, an exact-fp32
fallback, and an error-bound contract the tests assert.

Shape of the collective (both compressed precisions):

    quantize(local) → all_to_all of the COMPRESSED payload
    → dequantize+sum the owned shard → requantize
    → all_gather of the COMPRESSED reduced shards → dequantize

The reduce phase is an all_to_all of int8 chunks (+ their fp32
scales): each device ships shard j of its quantized addend to device
j — the same (n-1)/n·bytes a reduce-scatter moves, but the wire
genuinely carries the compressed format (psum_scatter would force a
dequantized fp32 operand, silently un-realizing the priced win).  The
owner dequantizes its n received shards and accumulates in fp32 —
EQuARX's per-hop dequant-accumulate — then requantizes for the
all-gather phase, whose payload is int8 too.  Exactly the two
compressed wire phases the cost model prices
(search/machine_model.py ``allreduce(precision=...)``).  fp32 is a
plain ``lax.psum``: bit-exact with the uncompressed lowering.

Honesty note: under GSPMD the backward's own psum has already reduced
the gradient by the time the optimizer sees it, so execution routes the
*reduced* gradient through this collective round-trip over the
replication axes — on top of, not instead of, XLA's internal reduce.
Numerics and wire format are real; the net step-time win is the priced
number, and a CPU-mesh executed ratio measures the compression
overhead, not the ICI saving.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

SYNC_PRECISIONS = ("fp32", "bf16", "int8")

# elements per int8 scale block.  256 keeps the scale overhead at
# 4/256 = 1.6% of the compressed payload while bounding the blast
# radius of one outlier element to its own chunk (EQuARX block scaling)
DEFAULT_CHUNK = 256

# weight groups below this many elements never compress: their sync is
# latency-bound (nothing to win) and bias/scale vectors are exactly
# these.  THE shared floor — the search's safety heuristic
# (search/sync_precision.py) and the execution path (quantized_grad_sync
# skips sub-floor leaves even inside a compressed op) both import it,
# as does the cost model's per-weight pricing.
MIN_COMPRESS_ELEMS = 1 << 16

_AxisNames = Union[str, Tuple[str, ...]]


def quantize_chunked(x: jax.Array, chunk: int = DEFAULT_CHUNK):
    """Flatten ``x`` and quantize per-chunk to symmetric int8.

    Returns ``(q [nchunks, chunk] int8, scale [nchunks, 1] fp32)``.
    The tail is zero-padded to a whole chunk; all-zero chunks get scale
    1 so their round trip is exact.  |q| <= 127 by construction (the
    scale is amax/127, so the largest magnitude maps to ±127)."""
    flat = x.reshape(-1).astype(jnp.float32)
    pad = (-flat.shape[0]) % chunk
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
    blocks = flat.reshape(-1, chunk)
    amax = jnp.max(jnp.abs(blocks), axis=1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.round(blocks / scale).astype(jnp.int8)
    return q, scale


def dequantize_chunked(
    q: jax.Array, scale: jax.Array, size: int, shape: Tuple[int, ...]
):
    """Inverse of quantize_chunked: drop the tail padding and restore
    ``shape`` (``size`` = number of real elements)."""
    blocks = q.astype(jnp.float32) * scale
    return blocks.reshape(-1)[:size].reshape(shape)


def quantized_allreduce(
    x: jax.Array,
    axis_name: _AxisNames,
    precision: str = "fp32",
    chunk: int = DEFAULT_CHUNK,
    mean: bool = False,
    axis_size: Optional[int] = None,
) -> jax.Array:
    """Allreduce of ``x`` over ``axis_name`` — call inside shard_map.

    ``precision`` one of SYNC_PRECISIONS.  fp32 is an exact
    ``lax.psum``.  bf16/int8 compress both wire phases (see module
    docstring); the result satisfies the ``allreduce_error_bound``
    contract.  ``axis_size`` (product of the named axes' sizes) is
    required for the compressed precisions and for ``mean`` — it shapes
    the scatter and must be static."""
    if precision not in SYNC_PRECISIONS:
        raise ValueError(
            f"precision must be one of {SYNC_PRECISIONS}, got {precision!r}"
        )
    axes = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    if precision == "fp32":
        y = lax.psum(x, axes)
        if mean:
            if axis_size is None:
                raise ValueError("mean=True requires axis_size")
            y = y / axis_size
        return y
    if axis_size is None:
        raise ValueError(f"precision={precision!r} requires axis_size")
    n = int(axis_size)
    orig_shape, size, orig_dtype = x.shape, x.size, x.dtype
    flat = x.reshape(-1).astype(jnp.float32)
    # pad so every device's owned share is a whole number of chunks
    pad = (-flat.shape[0]) % (n * chunk)
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
    if precision == "int8":
        # stage 1: quantize locally, then EXCHANGE THE INT8 PAYLOAD —
        # shard j of every device's addend lands on device j
        # (all_to_all moves the same (n-1)/n·bytes a reduce-scatter
        # would, in the compressed format the cost model prices)
        q, s = quantize_chunked(flat, chunk)          # [C, chunk], [C, 1]
        qn = q.reshape(n, -1, chunk)
        sn = s.reshape(n, -1, 1)
        q_recv = lax.all_to_all(qn, axes, split_axis=0, concat_axis=0,
                                tiled=True).reshape(n, -1, chunk)
        s_recv = lax.all_to_all(sn, axes, split_axis=0, concat_axis=0,
                                tiled=True).reshape(n, -1, 1)
        # owner-side dequantize + fp32 accumulate (EQuARX's per-hop
        # dequant-accumulate), then requantize for the gather phase
        part = jnp.sum(q_recv.astype(jnp.float32) * s_recv, axis=0)
        q2, s2 = quantize_chunked(part, chunk)
        # stage 2: all-gather of the still-compressed reduced shards
        full_q = lax.all_gather(q2, axes, axis=0, tiled=True)
        full_s = lax.all_gather(s2, axes, axis=0, tiled=True)
        full = (full_q.astype(jnp.float32) * full_s).reshape(-1)
    else:
        bn = flat.astype(jnp.bfloat16).reshape(n, -1)
        b_recv = lax.all_to_all(bn, axes, split_axis=0, concat_axis=0,
                                tiled=True).reshape(n, -1)
        part = jnp.sum(b_recv.astype(jnp.float32), axis=0)
        full = lax.all_gather(
            part.astype(jnp.bfloat16), axes, axis=0, tiled=True
        ).astype(jnp.float32)
    out = full[:size].reshape(orig_shape)
    if mean:
        out = out / n
    return out.astype(orig_dtype)


def quantized_allreduce_ef(
    x: jax.Array,
    residual: jax.Array,
    axis_name: _AxisNames,
    precision: str = "int8",
    chunk: int = DEFAULT_CHUNK,
    mean: bool = False,
    axis_size: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Error-feedback (residual) variant of ``quantized_allreduce``:
    each device transmits ``quantize(x + residual)`` and carries the
    local quantization error forward — ``residual' = (x + residual) -
    dequantize(quantize(x + residual))`` — so the compression error is
    re-injected instead of lost (EF-SGD; what keeps int8 sync safe at
    large replica counts, where n independent per-step roundings would
    otherwise accumulate a bias the lone-step error bound does not
    see).  Returns ``(reduced, new_residual)``; the caller threads the
    residual across steps like optimizer state.  fp32 is the exact
    psum with a zero residual.  The feedback compensates the entry
    (stage-1) quantization — the per-addend error EF-SGD corrects; the
    reduced-shard requantize of stage 2 remains bounded by the
    one-step contract (``allreduce_error_bound``)."""
    if precision not in SYNC_PRECISIONS:
        raise ValueError(
            f"precision must be one of {SYNC_PRECISIONS}, got {precision!r}"
        )
    if precision == "fp32":
        return (
            quantized_allreduce(x, axis_name, "fp32", chunk, mean,
                                axis_size),
            jnp.zeros_like(x, dtype=jnp.float32),
        )
    carry = x.astype(jnp.float32) + residual.astype(jnp.float32)
    if precision == "int8":
        q, s = quantize_chunked(carry, chunk)
        approx = dequantize_chunked(q, s, carry.size, carry.shape)
    else:
        approx = carry.astype(jnp.bfloat16).astype(jnp.float32)
    new_residual = carry - approx
    out = quantized_allreduce(
        carry, axis_name, precision=precision, chunk=chunk, mean=mean,
        axis_size=axis_size,
    ).astype(x.dtype)
    return out, new_residual


def allreduce_error_bound(
    per_device_inputs, precision: str, chunk: int = DEFAULT_CHUNK
) -> float:
    """Max-abs error bound of ``quantized_allreduce`` vs the exact fp32
    psum of ``per_device_inputs`` (a sequence of the n local addends).

    int8: stage 1 rounds each addend to its chunk scale (half-ulp error
    <= amax_i/254 per element, summed over addends); stage 2 rounds the
    reduced value once more (<= amax(sum)/254 <= sum_i amax_i/254).
    Global-amax form — per-chunk scales only tighten it.  bf16: same
    two stages at half-ulp relative error 2^-8 for an 8-bit
    significand.  A 5% headroom absorbs the fp32 accumulation rounding
    of the reduction itself."""
    if precision not in SYNC_PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}")
    if precision == "fp32":
        return 0.0
    total = float(
        sum(np.max(np.abs(np.asarray(x))) for x in per_device_inputs)
    )
    per_stage = total / 254.0 if precision == "int8" else total * 2.0 ** -8
    return 1.05 * 2.0 * per_stage + 1e-12


def replication_axes(sharding, mesh) -> Tuple[Tuple[str, ...], int]:
    """The mesh axes a param's PartitionSpec does NOT consume (its
    gradient is replicated — and psummed by GSPMD — across exactly
    these), plus their total extent.  THE shared rule between the
    per-group quantized sync below and the bucketed fused sync
    (comm/bucketed.py)."""
    used = set()
    for entry in sharding.spec:
        if entry is None:
            continue
        for a in (entry if isinstance(entry, tuple) else (entry,)):
            used.add(a)
    rep = tuple(
        a for a, s in mesh.shape.items() if a not in used and s > 1
    )
    n = 1
    for a in rep:
        n *= mesh.shape[a]
    return rep, n


def quantized_grad_sync(
    grads: Dict[str, Dict[str, jax.Array]],
    mesh,
    param_shardings: Dict[str, Dict[str, "jax.sharding.NamedSharding"]],
    precision_map: Dict[str, str],
    chunk: int = DEFAULT_CHUNK,
    residuals: Optional[Dict[str, Dict[str, jax.Array]]] = None,
):
    """Route the weight groups named by ``precision_map`` (op name →
    bf16/int8/int8_ef) through the quantized collective over their
    replication axes — the mesh axes the param's PartitionSpec does not
    consume.

    Gradients arrive already reduced (replicated across those axes), so
    the round trip sums n identical addends and divides by n: the value
    is preserved up to the two quantization stages, which run for real.
    Groups whose params consume the whole mesh (nothing replicated),
    fp32 groups, and sub-MIN_COMPRESS_ELEMS weights (the bias/scale
    vectors of an otherwise-compressed op — latency-bound sync, nothing
    to win) pass through untouched — with an empty map the function is
    an identity and the lowering is bit-exact with history.

    ``residuals`` — the error-feedback state tree (op → weight →
    residual array, sharded like the param) for ``int8_ef`` groups:
    each is threaded through ``quantized_allreduce_ef`` and the call
    then returns ``(merged_grads, new_residuals)`` so the training loop
    can persist the updated residuals (compiler/lowering.py carries
    them in the model-state dict).  With ``residuals=None`` (legacy
    callers) the signature and return value are unchanged and
    ``int8_ef`` degrades to the plain int8 wire — EF without its state
    would silently re-zero the residual every step."""
    from jax.sharding import PartitionSpec

    from flexflow_tpu.comm.compat import shard_map

    sel: Dict[str, Dict[str, jax.Array]] = {}
    res_sel: Dict[str, Dict[str, jax.Array]] = {}
    specs: Dict[str, Dict[str, PartitionSpec]] = {}
    res_specs: Dict[str, Dict[str, PartitionSpec]] = {}
    plan: Dict[str, Dict[str, Tuple[Tuple[str, ...], str, int]]] = {}
    for op_name, prec in precision_map.items():
        if prec == "fp32":
            continue
        for w_name, g in grads.get(op_name, {}).items():
            if g.size < MIN_COMPRESS_ELEMS:
                continue
            sh = param_shardings.get(op_name, {}).get(w_name)
            if sh is None:
                continue
            rep, n = replication_axes(sh, mesh)
            if not rep:
                continue
            p = prec
            if p == "int8_ef":
                r = (residuals or {}).get(op_name, {}).get(w_name)
                if r is None:
                    p = "int8"  # no state to thread — plain wire
                else:
                    res_sel.setdefault(op_name, {})[w_name] = r
                    res_specs.setdefault(op_name, {})[w_name] = sh.spec
            sel.setdefault(op_name, {})[w_name] = g
            specs.setdefault(op_name, {})[w_name] = sh.spec
            plan.setdefault(op_name, {})[w_name] = (rep, p, n)
    if not sel:
        return grads if residuals is None else (grads, {})

    def local(gs, rs):
        out: Dict[str, Dict[str, jax.Array]] = {}
        rout: Dict[str, Dict[str, jax.Array]] = {}
        for op_name, ws in gs.items():
            for w_name, g in ws.items():
                rep, prec, n = plan[op_name][w_name]
                if prec == "int8_ef":
                    y, nr = quantized_allreduce_ef(
                        g, rs[op_name][w_name], rep, precision="int8",
                        chunk=chunk, mean=True, axis_size=n,
                    )
                    out.setdefault(op_name, {})[w_name] = y
                    rout.setdefault(op_name, {})[w_name] = nr
                else:
                    out.setdefault(op_name, {})[w_name] = (
                        quantized_allreduce(
                            g, rep, precision=prec, chunk=chunk,
                            mean=True, axis_size=n,
                        ))
        return out, rout

    synced, new_res = shard_map(
        local, mesh=mesh, in_specs=(specs, res_specs),
        out_specs=(specs, res_specs),
    )(sel, res_sel)
    merged = {op: dict(ws) for op, ws in grads.items()}
    for op_name, ws in synced.items():
        for w_name, g in ws.items():
            merged[op_name][w_name] = g
    if residuals is None:
        return merged
    return merged, new_res
