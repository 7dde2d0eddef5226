"""Normalization + regularization ops: Softmax, LayerNorm, BatchNorm, Dropout.

Reference: src/ops/{softmax,layer_norm,batch_norm,dropout}.*.
BatchNorm running statistics are framework *state* (non-trainable
collection threaded through the jitted step) rather than cuDNN-side
buffers; Dropout draws from the step PRNG key instead of per-device
cuRAND states (reference: dropout.cc per-device rng).
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from flexflow_tpu.core.machine import MachineView
from flexflow_tpu.core.optype import OperatorType
from flexflow_tpu.core.ptensor import DataType, ParallelTensorShape
from flexflow_tpu.initializers import ConstantInitializer, ZeroInitializer
from flexflow_tpu.ops.base import (
    LoweringContext,
    Operator,
    OpSharding,
    ShardAnnot,
    WeightSpec,
    register_op,
)


@register_op
class SoftmaxOp(Operator):
    op_type = OperatorType.SOFTMAX

    def __init__(self, name, input_shapes, axis: int = -1):
        super().__init__(name, input_shapes, axis=int(axis))

    def infer(self) -> Sequence[ParallelTensorShape]:
        return (self.input_shapes[0],)

    def forward(self, ctx, inputs, weights):
        return [jax.nn.softmax(inputs[0].astype(jnp.float32), axis=self.attrs["axis"]).astype(inputs[0].dtype)]

    def propagate(self, mv: MachineView) -> OpSharding:
        ax = self.attrs["axis"] % self.output_shapes[0].ndim
        degs = list(mv.dim_degrees)
        degs[ax] = 1  # softmax dim needs the full row
        a = ShardAnnot(tuple(degs), mv.replica_degree)
        return OpSharding(inputs=(a,), weights=(), outputs=(a,))

    def splittable_output_dims(self) -> Tuple[int, ...]:
        ax = self.attrs["axis"] % self.output_shapes[0].ndim
        return tuple(i for i in range(self.output_shapes[0].ndim) if i != ax)


@register_op
class LayerNormOp(Operator):
    """attrs: axes (normalized trailing axes), elementwise_affine, eps.
    Reference: src/ops/layer_norm.cc."""

    op_type = OperatorType.LAYERNORM

    def __init__(
        self,
        name,
        input_shapes,
        axes: Tuple[int, ...] = (-1,),
        elementwise_affine: bool = True,
        eps: float = 1e-5,
    ):
        nd = len(input_shapes[0].sizes)
        axes = tuple(sorted(a % nd for a in axes))
        super().__init__(
            name, input_shapes, axes=axes, elementwise_affine=elementwise_affine, eps=eps
        )

    def infer(self) -> Sequence[ParallelTensorShape]:
        return (self.input_shapes[0],)

    def _param_shape(self) -> Tuple[int, ...]:
        x = self.input_shapes[0]
        return tuple(x.sizes[a] for a in self.attrs["axes"])

    def weight_specs(self) -> Sequence[WeightSpec]:
        if not self.attrs["elementwise_affine"]:
            return ()
        shp = self._param_shape()
        return (
            WeightSpec("gamma", shp, DataType.FLOAT32, ConstantInitializer(1.0)),
            WeightSpec("beta", shp, DataType.FLOAT32, ZeroInitializer()),
        )

    def forward(self, ctx, inputs, weights):
        x = inputs[0].astype(jnp.float32)
        axes = self.attrs["axes"]
        mean = jnp.mean(x, axis=axes, keepdims=True)
        var = jnp.mean(jnp.square(x - mean), axis=axes, keepdims=True)
        y = (x - mean) * jax.lax.rsqrt(var + self.attrs["eps"])
        if self.attrs["elementwise_affine"]:
            bshape = [1] * x.ndim
            for a in axes:
                bshape[a] = x.shape[a]
            y = y * weights["gamma"].reshape(bshape) + weights["beta"].reshape(bshape)
        return [y.astype(inputs[0].dtype)]

    def propagate(self, mv: MachineView) -> OpSharding:
        degs = list(mv.dim_degrees)
        for a in self.attrs["axes"]:
            degs[a] = 1  # normalized dims stay whole
        a = ShardAnnot(tuple(degs), mv.replica_degree)
        w = ()
        if self.attrs["elementwise_affine"]:
            wa = ShardAnnot((1,) * len(self._param_shape()), mv.num_parts)
            w = (wa, wa)
        return OpSharding(inputs=(a,), weights=w, outputs=(a,))

    def splittable_output_dims(self) -> Tuple[int, ...]:
        return tuple(
            i
            for i in range(self.output_shapes[0].ndim)
            if i not in self.attrs["axes"]
        )


@register_op
class RMSNormOp(Operator):
    """``x * rsqrt(mean(x^2) + eps) * gamma`` over the last axis, in
    float32; no mean is subtracted and there is no bias."""

    op_type = OperatorType.RMSNORM

    def __init__(self, name, input_shapes, eps: float = 1e-6,
                 weights_of: str | None = None):
        super().__init__(name, input_shapes, eps=float(eps),
                         weights_of=weights_of)

    def infer(self) -> Sequence[ParallelTensorShape]:
        return (self.input_shapes[0],)

    def weight_specs(self) -> Sequence[WeightSpec]:
        width = (self.input_shapes[0].sizes[-1],)
        return (WeightSpec("gamma", width, DataType.FLOAT32,
                           ConstantInitializer(1.0)),)

    def forward(self, ctx, inputs, weights):
        return [rms_norm(inputs[0], weights["gamma"],
                         self.attrs["eps"]).astype(inputs[0].dtype)]

    def propagate(self, mv: MachineView) -> OpSharding:
        degs = mv.dim_degrees[:-1] + (1,)  # the normalized dim stays whole
        a = ShardAnnot(degs, mv.replica_degree)
        return OpSharding(inputs=(a,), weights=(ShardAnnot((1,), mv.num_parts),),
                          outputs=(a,))

    def splittable_output_dims(self) -> Tuple[int, ...]:
        return tuple(range(self.output_shapes[0].ndim - 1))

    def flops(self) -> float:
        return 4.0 * self.output_shapes[0].num_elements


def rms_norm(x, gamma, eps: float):
    """float32 RMS norm of the last axis (also the latent attention's
    two inner norms)."""
    x = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return x * scale * gamma.astype(jnp.float32)


@register_op
class BatchNormOp(Operator):
    """NHWC batch norm over (N, H, W) per channel; also accepts 2-D
    [N, C]. attrs: relu, momentum, eps. Reference: src/ops/batch_norm.cc."""

    op_type = OperatorType.BATCHNORM

    def __init__(self, name, input_shapes, relu: bool = True, momentum: float = 0.9, eps: float = 1e-5):
        super().__init__(name, input_shapes, relu=relu, momentum=momentum, eps=eps)

    def infer(self) -> Sequence[ParallelTensorShape]:
        return (self.input_shapes[0],)

    @property
    def channels(self) -> int:
        return self.input_shapes[0].sizes[-1]

    def weight_specs(self) -> Sequence[WeightSpec]:
        c = (self.channels,)
        return (
            WeightSpec("scale", c, DataType.FLOAT32, ConstantInitializer(1.0)),
            WeightSpec("bias", c, DataType.FLOAT32, ZeroInitializer()),
        )

    def state_specs(self):
        c = (self.channels,)
        return (
            ("running_mean", c, jnp.float32, 0.0),
            ("running_var", c, jnp.float32, 1.0),
        )

    def forward(self, ctx: LoweringContext, inputs, weights):
        x = inputs[0].astype(jnp.float32)
        axes = tuple(range(x.ndim - 1))
        m = self.attrs["momentum"]
        rm = ctx.state_in[f"{self.name}/running_mean"]
        rv = ctx.state_in[f"{self.name}/running_var"]
        if ctx.train:
            mean = jnp.mean(x, axis=axes)
            var = jnp.mean(jnp.square(x - mean.reshape((1,) * (x.ndim - 1) + (-1,))), axis=axes)
            ctx.state_out[f"{self.name}/running_mean"] = m * rm + (1 - m) * mean
            ctx.state_out[f"{self.name}/running_var"] = m * rv + (1 - m) * var
        else:
            mean, var = rm, rv
            ctx.state_out[f"{self.name}/running_mean"] = rm
            ctx.state_out[f"{self.name}/running_var"] = rv
        shape = (1,) * (x.ndim - 1) + (-1,)
        y = (x - mean.reshape(shape)) * jax.lax.rsqrt(var.reshape(shape) + self.attrs["eps"])
        y = y * weights["scale"].reshape(shape) + weights["bias"].reshape(shape)
        if self.attrs["relu"]:
            y = jax.nn.relu(y)
        return [y.astype(inputs[0].dtype)]

    def propagate(self, mv: MachineView) -> OpSharding:
        a = ShardAnnot(mv.dim_degrees, mv.replica_degree)
        c_deg = mv.dim_degrees[-1]
        rep = mv.num_parts // max(c_deg, 1)
        wa = ShardAnnot((c_deg,), rep, idx=(len(mv.dim_degrees) - 1,))
        return OpSharding(inputs=(a,), weights=(wa, wa), outputs=(a,))

    def splittable_output_dims(self) -> Tuple[int, ...]:
        return tuple(range(self.output_shapes[0].ndim))


@register_op
class DropoutOp(Operator):
    op_type = OperatorType.DROPOUT

    def __init__(self, name, input_shapes, rate: float = 0.5, seed: int = 0):
        super().__init__(name, input_shapes, rate=float(rate), seed=int(seed))

    def infer(self) -> Sequence[ParallelTensorShape]:
        return (self.input_shapes[0],)

    def forward(self, ctx: LoweringContext, inputs, weights):
        x = inputs[0]
        rate = self.attrs["rate"]
        if not ctx.train or rate <= 0.0:
            return [x]
        keep = 1.0 - rate
        mask = jax.random.bernoulli(ctx.op_rng(self.name), keep, x.shape)
        return [jnp.where(mask, x / keep, 0).astype(x.dtype)]

    def splittable_output_dims(self) -> Tuple[int, ...]:
        return tuple(range(self.output_shapes[0].ndim))
