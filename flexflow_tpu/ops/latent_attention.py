"""Latent (low-rank) causal self-attention with a rotary part that all
heads share — the attention of the DeepSeek-V2/V3 family.

    c_q = RMS(x W_qa)                       [q_lora_rank]
    q   = c_q W_qb -> heads x [q_nope | q_rope]
    [c_kv | k_rope] = x W_kva               [kv_lora_rank | rope]
    [k_nope | v] x heads = RMS(c_kv) W_kvb
    rotary (interleaved pairs (2i, 2i+1)) on q_rope of each head and on
    the ONE k_rope the heads share
    scores = (q_nope . k_nope + q_rope . k_rope) / sqrt(nope + rope)
    out    = heads x v-width values, times W_o

The query/key heads are ``nope + rope`` wide and the value heads
``v_head_dim``: the flash kernels take the two widths as they are
(kernels/flash_attention.py), nothing is padded.  Matmuls run in the
compute dtype with float32 accumulation; the two inner norms and the
rotary embedding in float32.
"""

from __future__ import annotations

import math
from typing import Sequence, Tuple

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
    LoweringContext,
    Operator,
    OpSharding,
    ShardAnnot,
    WeightSpec,
    register_op,
)
from flexflow_tpu.ops.norm import rms_norm


def interleaved_rotary(x, theta: float):
    """Rotary embedding of ``x`` [B, S, H, R] at positions 0..S-1, the
    pair i being lanes (2i, 2i+1) and its frequency theta^(-2i/R); in
    float32.  Written with two lane shifts instead of a [.., R/2, 2]
    reshape, which would split the lane dimension."""
    s, r = x.shape[1], x.shape[-1]
    inv_freq = theta ** (-np.arange(0, r, 2, dtype=np.float64) / r)
    angle = np.arange(s, dtype=np.float64)[:, None] * inv_freq[None, :]
    cos = np.repeat(np.cos(angle), 2, axis=-1).astype(np.float32)
    sin = np.repeat(np.sin(angle), 2, axis=-1).astype(np.float32)
    x = x.astype(jnp.float32)
    even = (np.arange(r) % 2 == 0)
    # lane 2i gets -x[2i+1], lane 2i+1 gets x[2i]
    partner = jnp.where(even, -jnp.roll(x, -1, axis=-1),
                        jnp.roll(x, 1, axis=-1))
    return x * cos[None, :, None, :] + partner * sin[None, :, None, :]


@register_op
class LatentAttentionOp(Operator):
    """x [B, S, E] -> [B, S, E], causal.  attrs: num_heads, q_lora_rank,
    kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
    rope_theta, eps (of the two inner RMS norms)."""

    op_type = OperatorType.LATENT_ATTENTION
    scope = "ff.mla"

    def __init__(
        self,
        name,
        input_shapes,
        num_heads: int,
        q_lora_rank: int,
        kv_lora_rank: int,
        qk_nope_head_dim: int,
        qk_rope_head_dim: int,
        v_head_dim: int,
        rope_theta: float = 10000.0,
        eps: float = 1e-6,
        kernel_initializer: Initializer | None = None,
    ):
        assert qk_rope_head_dim % 2 == 0, "rotary pairs"
        self._kernel_init = kernel_initializer or DEFAULT_WEIGHT_INIT
        super().__init__(
            name, input_shapes, num_heads=int(num_heads),
            q_lora_rank=int(q_lora_rank), kv_lora_rank=int(kv_lora_rank),
            qk_nope_head_dim=int(qk_nope_head_dim),
            qk_rope_head_dim=int(qk_rope_head_dim),
            v_head_dim=int(v_head_dim), rope_theta=float(rope_theta),
            eps=float(eps))

    def infer(self) -> Sequence[ParallelTensorShape]:
        return (self.input_shapes[0],)

    def weight_specs(self) -> Sequence[WeightSpec]:
        a = self.attrs
        e, h = self.input_shapes[0].sizes[-1], a["num_heads"]
        rq, rkv = a["q_lora_rank"], a["kv_lora_rank"]
        dn, dr, dv = (a["qk_nope_head_dim"], a["qk_rope_head_dim"],
                      a["v_head_dim"])
        f32, init, one = DataType.FLOAT32, self._kernel_init, ConstantInitializer(1.0)
        return (
            WeightSpec("w_qa", (e, rq), f32, init),
            WeightSpec("q_norm", (rq,), f32, one),
            WeightSpec("w_qb", (rq, h, dn + dr), f32, init),
            WeightSpec("w_kva", (e, rkv + dr), f32, init),
            WeightSpec("kv_norm", (rkv,), f32, one),
            WeightSpec("w_kvb", (rkv, h, dn + dv), f32, init),
            WeightSpec("w_o", (h, dv, e), f32, init),
        )

    def forward(self, ctx: LoweringContext, inputs, weights):
        a = self.attrs
        cd, f32 = ctx.compute_dtype, jnp.float32
        h, rkv = a["num_heads"], a["kv_lora_rank"]
        dn, dr = a["qk_nope_head_dim"], a["qk_rope_head_dim"]
        x = inputs[0].astype(cd)
        w = {n: weights[n].astype(cd) for n in
             ("w_qa", "w_qb", "w_kva", "w_kvb", "w_o")}

        c_q = jnp.dot(x, w["w_qa"], preferred_element_type=f32)
        c_q = rms_norm(c_q, weights["q_norm"], a["eps"]).astype(cd)
        q = jnp.einsum("bsr,rhd->bshd", c_q, w["w_qb"],
                       preferred_element_type=f32)
        kv = jnp.dot(x, w["w_kva"], preferred_element_type=f32)
        c_kv = rms_norm(kv[..., :rkv], weights["kv_norm"], a["eps"]).astype(cd)
        k_v = jnp.einsum("bsr,rhd->bshd", c_kv, w["w_kvb"],
                         preferred_element_type=f32)

        q_rope = interleaved_rotary(q[..., dn:], a["rope_theta"])
        k_rope = interleaved_rotary(kv[..., None, rkv:], a["rope_theta"])
        qf = jnp.concatenate([q[..., :dn], q_rope], axis=-1).astype(cd)
        kf = jnp.concatenate(
            [k_v[..., :dn],
             jnp.broadcast_to(k_rope, k_rope.shape[:2] + (h, dr))],
            axis=-1).astype(cd)
        out = self._attention(ctx, qf, kf, k_v[..., dn:].astype(cd))
        y = jnp.einsum("bshd,hde->bse", out, w["w_o"],
                       preferred_element_type=f32)
        return [y.astype(inputs[0].dtype)]

    def _attention(self, ctx, q, k, v):
        from flexflow_tpu.kernels.flash_attention import (
            _xla_attention,
            flash_attention,
            flash_attention_sharded,
            flash_profitable,
        )

        scale = 1.0 / math.sqrt(q.shape[-1])
        if not flash_profitable(q.shape[1], k.shape[1]):
            return _xla_attention(q, k, v, True, scale)
        if ctx.mesh is None:
            return flash_attention(q, k, v, causal=True, scale=scale)
        # a Mosaic call is not partitioned by GSPMD: per batch shard
        return flash_attention_sharded(
            q, k, v, ctx.mesh, batch_axes=(ctx.slot_axes or {}).get(0, ()),
            causal=True, scale=scale)

    def propagate(self, mv: MachineView) -> OpSharding:
        b, s, e = mv.dim_degrees
        assert s == 1 and e == 1, "only the batch dim of latent attention splits"
        act = ShardAnnot((b, 1, 1), mv.replica_degree)
        ws = tuple(ShardAnnot((1,) * len(w.shape), mv.num_parts)
                   for w in self._weight_specs)
        return OpSharding(inputs=(act,), weights=ws, outputs=(act,))

    def splittable_output_dims(self) -> Tuple[int, ...]:
        return (0,)

    def flops(self) -> float:
        a = self.attrs
        b, s, e = self.output_shapes[0].sizes
        h = a["num_heads"]
        dqk = a["qk_nope_head_dim"] + a["qk_rope_head_dim"]
        dv = a["v_head_dim"]
        weights = (e * a["q_lora_rank"] + a["q_lora_rank"] * h * dqk
                   + e * (a["kv_lora_rank"] + a["qk_rope_head_dim"])
                   + a["kv_lora_rank"] * h * (a["qk_nope_head_dim"] + dv)
                   + h * dv * e)
        return 2.0 * b * s * weights + 2.0 * b * h * s * s * (dqk + dv)
