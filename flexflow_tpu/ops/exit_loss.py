"""The objective of a model that answers after every loop step of a
looped stack (one exit a step, one gate read at every exit): the
entropy-regularised expected loss under the exit distribution.

    lam_t = sigmoid(z_t)                     z_t: the gate's output at step t
    p_1 = lam_1;  p_t = lam_t prod_{j<t}(1 - lam_j)  (1 < t < T);
    p_T = prod_{j<T}(1 - lam_j)              sums to 1; z_T is not read
    objective = mean over positions [ sum_t p_t CE(logits_t, target)
                                      - beta H(p) ],  H(p) = -sum_t p_t ln p_t

Position i predicts ``ids[i + 1]``, made here from the model's ONE input
(as ``NextTokenLossOp`` makes its own); the last position has no target
and is masked out of every term.  The objective goes into
``{name}/loss``, which the lowering takes INSTEAD of the loss of the
op's output (``CompiledModel._loss_from``); the output is the last
exit's logits, handed through, so the op is the graph's sink.
"""

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
    register_op,
)
from flexflow_tpu.ops.mtp import batch_only


def exit_distribution(z):
    """z [T, ...] gate outputs -> (p [T, ...], ln p [T, ...]), float32,
    through log-sigmoids so that a shut gate (z = -30) gives 0 ln 0 = 0
    and not a NaN."""
    z = z.astype(jnp.float32)
    stay = jnp.cumsum(jax.nn.log_sigmoid(-z[:-1]), axis=0)  # ln prod(1 - lam)
    before = jnp.concatenate([jnp.zeros_like(stay[:1]), stay[:-1]], axis=0)
    logp = jnp.concatenate([jax.nn.log_sigmoid(z[:-1]) + before, stay[-1:]],
                           axis=0)
    return jnp.exp(logp), logp


@jax.checkpoint
def _token_nll(logits, targets):
    """Cross-entropy a position, float32.  Checkpointed: the backward
    pass makes the softmax of ONE exit again from its logits instead of
    holding every exit's [B, S, V] beside them."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


@register_op
class ExitLossOp(Operator):
    """(logits_1..T [B, S, V], z_1..T [B, S, 1], ids [B, S]) -> logits_T.

    Device counters (obs/device_counters.py): ``loop.gated_tokens``
    (counted positions), ``loop.exit_step_milli`` (sum over positions of
    round(1000 sum_t t p_t)), ``loop.exit_entropy_milli`` (sum of
    round(1000 H(p))); gauges ``fit.exit_loss.<t>`` (mean CE of exit t)
    and ``fit.exit_mass_last`` (mean p_T)."""

    op_type = OperatorType.EXIT_LOSS
    writes_state = True

    def __init__(self, name, input_shapes, beta: float = 0.1):
        assert len(input_shapes) % 2 == 1 and len(input_shapes) >= 5, (
            "T >= 2 logits, T gate outputs and the ids")
        super().__init__(name, input_shapes, beta=float(beta))

    @property
    def exits(self) -> int:
        return len(self.input_shapes) // 2

    def infer(self) -> Sequence[ParallelTensorShape]:
        return (self.input_shapes[self.exits - 1],)

    def state_specs(self):
        gauges = [f"fit.exit_loss.{t + 1}" for t in range(self.exits)]
        return (
            ("loss", (), jnp.float32, 0.0),
            *((f"obs/{c}", (), jnp.int32, 0) for c in (
                "loop.gated_tokens", "loop.exit_step_milli",
                "loop.exit_entropy_milli")),
            *((f"obs/{g}", (), jnp.float32, 0.0) for g in (
                *gauges, "fit.exit_mass_last")),
        )

    def forward(self, ctx: LoweringContext, inputs, weights):
        T = self.exits
        logits, ids = inputs[:T], inputs[-1]
        seq = ids.shape[1]
        targets = jnp.roll(ids.astype(jnp.int32), -1, axis=1)
        counted = jnp.broadcast_to(jnp.arange(seq) < seq - 1, ids.shape)
        n = ids.shape[0] * (seq - 1)

        def mean(x):
            return jnp.sum(jnp.where(counted, x, 0.0)) / n

        nll = jnp.stack([_token_nll(lg, targets) for lg in logits])
        p, logp = exit_distribution(
            jnp.stack([z[..., 0] for z in inputs[T:2 * T]]))
        entropy = -jnp.sum(p * logp, axis=0)
        step = jnp.tensordot(jnp.arange(1.0, T + 1.0), p, axes=1)
        out = {
            "loss": mean(jnp.sum(p * nll, axis=0)
                         - self.attrs["beta"] * entropy),
            "obs/fit.exit_mass_last": mean(p[-1]),
            **{f"obs/fit.exit_loss.{t + 1}": mean(nll[t]) for t in range(T)},
        }
        milli = {"loop.exit_step_milli": step,
                 "loop.exit_entropy_milli": entropy}
        counts = {"loop.gated_tokens": n, **{
            k: jnp.sum(jnp.where(counted, jnp.round(1000.0 * v), 0.0)
                       .astype(jnp.int32)) for k, v in milli.items()}}
        for k, v in counts.items():
            out[f"obs/{k}"] = ctx.state_in[f"{self.name}/obs/{k}"] + v
        for k, v in out.items():
            ctx.state_out[f"{self.name}/{k}"] = v
        return [logits[-1]]

    def propagate(self, mv: MachineView) -> OpSharding:
        return batch_only(self, mv)

    def flops(self) -> float:
        return 5.0 * sum(s.num_elements for s in self.input_shapes[:self.exits])
