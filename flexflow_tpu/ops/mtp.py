"""Multi-token prediction (depth 1, the DeepSeek-V3 form): the ids one
position ahead, and a second cross-entropy against the ids two ahead.

Both are made from the model's ONE input inside the graph, so
``fit(x, y)`` keeps one input and one label tensor.  The second loss
reaches the optimizer through the ``/aux_loss`` hook of the lowering
(``CompiledModel._loss_from``)."""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from flexflow_tpu.core.machine import MachineView
from flexflow_tpu.core.optype import OperatorType
from flexflow_tpu.core.ptensor import ParallelTensorShape
from flexflow_tpu.ops.base import (
    LoweringContext,
    Operator,
    OpSharding,
    ShardAnnot,
    register_op,
)


def batch_only(op: Operator, mv: MachineView) -> OpSharding:
    """Every input and output split over the batch dim alone."""
    b = mv.dim_degrees[0]

    def annot(shape):
        return ShardAnnot((b,) + (1,) * (shape.ndim - 1), mv.replica_degree)

    return OpSharding(inputs=tuple(annot(s) for s in op.input_shapes),
                      weights=(),
                      outputs=tuple(annot(s) for s in op.output_shapes))


@register_op
class ShiftOp(Operator):
    """x [B, S, ...] -> out[:, i] = x[:, i + by]; the last ``by``
    positions, which have nothing ahead of them, read 0."""

    op_type = OperatorType.SHIFT

    def __init__(self, name, input_shapes, by: int = 1):
        super().__init__(name, input_shapes, by=int(by))

    def infer(self) -> Sequence[ParallelTensorShape]:
        return (self.input_shapes[0],)

    def forward(self, ctx: LoweringContext, inputs, weights):
        x, by = inputs[0], self.attrs["by"]
        pad = jnp.zeros(x.shape[:1] + (by,) + x.shape[2:], x.dtype)
        return [jnp.concatenate([x[:, by:], pad], axis=1)]

    def propagate(self, mv: MachineView) -> OpSharding:
        return batch_only(self, mv)


@register_op
class NextTokenLossOp(Operator):
    """(logits [B, S, V], ahead_logits [B, S, V], ids [B, S]) -> logits.

    ``ahead_logits[:, i]`` predicts ``ids[:, i + shift]``; the mean token
    cross-entropy over the positions that have such a token (the last
    ``shift`` are masked out) is the second loss ``L``.  ``weight * L``
    goes into ``{name}/aux_loss``, which the lowering adds to the loss of
    ``logits`` — handed through unchanged, so the op is the graph's
    sink; ``L`` itself into the gauge ``fit.mtp_loss``."""

    op_type = OperatorType.NEXT_TOKEN_LOSS
    writes_state = True

    def __init__(self, name, input_shapes, shift: int = 2, weight: float = 0.3):
        super().__init__(name, input_shapes, shift=int(shift),
                         weight=float(weight))

    def infer(self) -> Sequence[ParallelTensorShape]:
        return (self.input_shapes[0],)

    def state_specs(self):
        return (("aux_loss", (), jnp.float32, 0.0),
                ("obs/fit.mtp_loss", (), jnp.float32, 0.0))

    def forward(self, ctx: LoweringContext, inputs, weights):
        logits, ahead, ids = inputs
        shift, seq = self.attrs["shift"], ids.shape[1]
        targets = jnp.roll(ids.astype(jnp.int32), -shift, axis=1)
        logp = jax.nn.log_softmax(ahead.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        has_target = jnp.arange(seq) < seq - shift
        loss = jnp.sum(jnp.where(has_target, nll, 0.0)) / (
            ids.shape[0] * (seq - shift))
        ctx.state_out[f"{self.name}/aux_loss"] = self.attrs["weight"] * loss
        ctx.state_out[f"{self.name}/obs/fit.mtp_loss"] = loss
        return [logits]

    def propagate(self, mv: MachineView) -> OpSharding:
        return batch_only(self, mv)

    def flops(self) -> float:
        return 5.0 * self.input_shapes[1].num_elements
