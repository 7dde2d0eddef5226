"""Linear (dense) operator — the TP workhorse.

Reference: src/ops/linear.cc (shape/replica-dim solving :109-203 and
:948-1135; cuBLAS kernels linear.cu).  Here the kernel is one
``jnp.dot`` — XLA tiles it onto the MXU in bf16 — and the three
parallel forms fall out of degree propagation:

* batch split        → data parallel (weight replicated)
* out-dim split      → column parallel (input replicated over TP axis)
* contraction split  → row parallel (output in partial-sum state; a
  Reduction parallel-op psums it — reference pairs Linear with
  Reduction the same way, substitution.cc:70-81)
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from flexflow_tpu.core.machine import MachineView
from flexflow_tpu.core.optype import OperatorType
from flexflow_tpu.core.ptensor import DataType, ParallelTensorShape
from flexflow_tpu.initializers import (
    DEFAULT_BIAS_INIT,
    DEFAULT_WEIGHT_INIT,
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

_ACTIVATIONS = {
    None: lambda x: x,
    "relu": jax.nn.relu,
    "sigmoid": jax.nn.sigmoid,
    "tanh": jnp.tanh,
    "gelu": lambda x: jax.nn.gelu(x, approximate=True),
    "silu": jax.nn.silu,
    "softmax": lambda x: jax.nn.softmax(x, axis=-1),
}


@register_op
class LinearOp(Operator):
    op_type = OperatorType.LINEAR

    def __init__(
        self,
        name,
        input_shapes,
        out_dim: int,
        activation: str | None = None,
        use_bias: bool = True,
        kernel_initializer: Initializer | None = None,
        bias_initializer: Initializer | None = None,
        param_dtype: str = "float32",
        weights_of: str | None = None,
    ):
        if activation not in _ACTIVATIONS:
            # same contract as conv/pool (_check_activation): fail at
            # graph construction, survive python -O, one exception type
            raise NotImplementedError(
                f"LinearOp activation {activation!r} not supported; "
                f"one of {sorted(k for k in _ACTIVATIONS if k)}"
            )
        self._kernel_init = kernel_initializer or DEFAULT_WEIGHT_INIT
        self._bias_init = bias_initializer or DEFAULT_BIAS_INIT
        super().__init__(
            name,
            input_shapes,
            out_dim=out_dim,
            activation=activation,
            use_bias=use_bias,
            param_dtype=param_dtype,
            weights_of=weights_of,
        )

    # ---- shapes ----------------------------------------------------------
    def infer(self) -> Sequence[ParallelTensorShape]:
        x = self.input_shapes[0]
        return (
            ParallelTensorShape.make(
                x.sizes[:-1] + (self.attrs["out_dim"],), x.dtype
            ),
        )

    @property
    def in_dim(self) -> int:
        return self.input_shapes[0].sizes[-1]

    def weight_specs(self) -> Sequence[WeightSpec]:
        pd = DataType.from_any(self.attrs["param_dtype"])
        specs = [
            WeightSpec("kernel", (self.in_dim, self.attrs["out_dim"]), pd, self._kernel_init)
        ]
        if self.attrs["use_bias"]:
            specs.append(WeightSpec("bias", (self.attrs["out_dim"],), pd, self._bias_init))
        return specs

    # ---- lowering --------------------------------------------------------
    def serving_weights(self, weights, compute_dtype):
        """The kernel in the dtype the matmul reads; the bias stays as
        it is (it is added to the fp32 accumulator)."""
        return {**weights,
                "kernel": weights["kernel"].astype(compute_dtype)}

    def forward(self, ctx: LoweringContext, inputs, weights):
        x = inputs[0].astype(ctx.compute_dtype)
        k = self.serving_weights(weights, ctx.compute_dtype)["kernel"]
        y = jnp.dot(x, k, preferred_element_type=jnp.float32)
        if self.attrs["use_bias"]:
            y = y + weights["bias"].astype(jnp.float32)
        y = _ACTIVATIONS[self.attrs["activation"]](y)
        return [y.astype(inputs[0].dtype)]

    # ---- parallelization -------------------------------------------------
    def propagate(self, mv: MachineView) -> OpSharding:
        degs = mv.dim_degrees
        r = mv.replica_degree  # contraction split
        t = degs[-1]  # out-dim split
        batch_parts = 1
        for d in degs[:-1]:
            batch_parts *= d
        nd = len(degs)
        x_annot = ShardAnnot(
            degs[:-1] + (r,),
            replica=t,
            idx=tuple(range(nd - 1)) + (REPLICA_SLOT,),
        )
        out = ShardAnnot(degs, replica=r, partial=r > 1)
        w = [ShardAnnot((r, t), replica=batch_parts, idx=(REPLICA_SLOT, nd - 1))]
        if self.attrs["use_bias"]:
            w.append(ShardAnnot((t,), replica=batch_parts * r, idx=(nd - 1,)))
        w = w[:len(self._weight_specs)]
        return OpSharding(inputs=(x_annot,), weights=tuple(w), outputs=(out,))

    def splittable_output_dims(self) -> Tuple[int, ...]:
        # any batch dim + the out-channel dim
        return tuple(range(self.output_shapes[0].ndim))

    def max_replica_degree(self) -> int:
        # a kernel read from another op is laid out by that op's view
        return self.in_dim if self._weight_specs else 1

    def flops(self) -> float:
        out = self.output_shapes[0]
        return 2.0 * out.num_elements * self.in_dim
