"""Mixture-of-Experts ops: GroupBy (dispatch), Aggregate (combine),
AggregateSpec, Cache.

Reference: src/ops/{group_by,aggregate,aggregate_spec,cache}.{cc,cu} and
examples/cpp/mixture_of_experts/moe.cc.  The reference scatters samples
into per-expert tensors with a capacity factor alpha
(group_by.cc, alpha = capacity factor) and places expert subgraphs on
different devices via the search.

TPU-native re-design: experts are one *batched* tensor [E, cap, D] so
the expert dim is a real shardable dim (expert parallelism = sharding
dim 0 over a mesh axis; the dispatch becomes an XLA all-to-all).
Capacity padding keeps every shape static for XLA — the reference's
dynamic max_size trick (moe recompile) becomes a plain static bound.
Dispatch is sort-based (kernels/moe_dispatch.py): stable-sort of the
token→expert assignment + narrow int scatter of slot indices + one wide
row gather — the standard TPU MoE formulation (O(T log T), vs O(T·E)
for the one-hot cumsum alternative).
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from flexflow_tpu.core.machine import MachineView
from flexflow_tpu.core.optype import OperatorType
from flexflow_tpu.core.ptensor import DataType, ParallelTensorShape
from flexflow_tpu.initializers import DEFAULT_BIAS_INIT, DEFAULT_WEIGHT_INIT
from flexflow_tpu.ops.base import (
    LoweringContext,
    Operator,
    OpSharding,
    ShardAnnot,
    WeightSpec,
    register_op,
)
from flexflow_tpu.ops.linear import _ACTIVATIONS


@register_op
class GroupByOp(Operator):
    """(data [B, D], assign [B, K]) -> (grouped [E, cap, D],
    expert_idx [B, K], pos [B, K], valid [B, K]).

    cap = ceil(alpha * K * B / E) — alpha is the reference's capacity
    factor (group_by.cc).  Tokens overflowing an expert's capacity are
    dropped (valid=0), matching the reference's truncation.
    """

    op_type = OperatorType.GROUP_BY

    def __init__(self, name, input_shapes, n_experts: int, alpha: float = 1.0):
        super().__init__(name, input_shapes, n_experts=int(n_experts), alpha=float(alpha))

    @property
    def capacity(self) -> int:
        import math

        b = self.input_shapes[0].sizes[0]
        k = self.input_shapes[1].sizes[1]
        e = self.attrs["n_experts"]
        return max(1, math.ceil(self.attrs["alpha"] * k * b / e))

    def infer(self) -> Sequence[ParallelTensorShape]:
        data, assign = self.input_shapes
        b, d = data.sizes
        k = assign.sizes[1]
        e = self.attrs["n_experts"]
        return (
            ParallelTensorShape.make((e, self.capacity, d), data.dtype),
            ParallelTensorShape.make((b, k), DataType.INT32),
            ParallelTensorShape.make((b, k), DataType.INT32),
            ParallelTensorShape.make((b, k), data.dtype),
        )

    def forward(self, ctx: LoweringContext, inputs, weights):
        from flexflow_tpu.kernels.moe_dispatch import moe_dispatch

        data, assign = inputs
        assign = assign.astype(jnp.int32)
        b, k = assign.shape
        e, cap = self.attrs["n_experts"], self.capacity
        flat_e = assign.reshape(-1)  # [B*K] expert ids, row-major (b major)
        grouped, pos_flat, valid_flat = moe_dispatch(data, flat_e, e, cap, k=k)
        return [
            grouped,
            assign,
            jnp.clip(pos_flat, 0, cap - 1).reshape(b, k).astype(jnp.int32),
            valid_flat.reshape(b, k).astype(data.dtype),
        ]

    def propagate(self, mv: MachineView) -> OpSharding:
        e_deg, cap_deg, d_deg = mv.dim_degrees
        assert cap_deg == 1, "capacity dim stays whole"
        data, assign = self.input_shapes
        b, k = assign.sizes
        aux = ShardAnnot((1, 1), replica=mv.num_parts)
        return OpSharding(
            inputs=(
                ShardAnnot((1, d_deg), replica=e_deg * mv.replica_degree, idx=(-1, 2)),
                ShardAnnot((1, 1), replica=mv.num_parts),
            ),
            weights=(),
            outputs=(
                ShardAnnot(mv.dim_degrees, mv.replica_degree),
                aux,
                aux,
                aux,
            ),
        )

    def splittable_output_dims(self) -> Tuple[int, ...]:
        return (0, 2)  # expert dim (EP) and feature dim


@register_op
class AggregateOp(Operator):
    """(gates [B,K], expert_idx [B,K], pos [B,K], valid [B,K],
    expert_out [E, cap, D]) -> [B, D].

    Reference: src/ops/aggregate.cc (weighted combine with
    load-balancing lambda; the balance loss is exposed via ctx state
    as ``{name}/aux_loss``).
    """

    op_type = OperatorType.AGGREGATE

    def __init__(self, name, input_shapes, lambda_bal: float = 0.0):
        super().__init__(name, input_shapes, lambda_bal=float(lambda_bal))

    def infer(self) -> Sequence[ParallelTensorShape]:
        gates = self.input_shapes[0]
        expert_out = self.input_shapes[4]
        b = gates.sizes[0]
        d = expert_out.sizes[2]
        return (ParallelTensorShape.make((b, d), expert_out.dtype),)

    def forward(self, ctx: LoweringContext, inputs, weights):
        gates, expert_idx, pos, valid, expert_out = inputs
        rows = expert_out[expert_idx.astype(jnp.int32), pos.astype(jnp.int32)]  # [B,K,D]
        w = (gates * valid).astype(rows.dtype)[..., None]
        out = jnp.sum(rows * w, axis=1)
        if self.attrs["lambda_bal"] > 0.0:
            e = expert_out.shape[0]
            counts = jnp.zeros((e,), jnp.float32).at[expert_idx.reshape(-1)].add(
                valid.reshape(-1).astype(jnp.float32)
            )
            frac = counts / jnp.maximum(jnp.sum(counts), 1.0)
            ctx.state_out[f"{self.name}/aux_loss"] = (
                self.attrs["lambda_bal"] * e * jnp.sum(frac * frac)
            )
        return [out]

    def propagate(self, mv: MachineView) -> OpSharding:
        b_deg, d_deg = mv.dim_degrees
        parts = mv.num_parts
        return OpSharding(
            inputs=(
                ShardAnnot((1, 1), replica=parts),
                ShardAnnot((1, 1), replica=parts),
                ShardAnnot((1, 1), replica=parts),
                ShardAnnot((1, 1), replica=parts),
                ShardAnnot((1, 1, d_deg), replica=parts // max(d_deg, 1), idx=(-1, -1, 1)),
            ),
            weights=(),
            outputs=(ShardAnnot(mv.dim_degrees, mv.replica_degree),),
        )

    def splittable_output_dims(self) -> Tuple[int, ...]:
        return (0, 1)


@register_op
class AggregateSpecOp(AggregateOp):
    """Uniform-weight variant (reference: src/ops/aggregate_spec.cc)."""

    op_type = OperatorType.AGGREGATE_SPEC

    def forward(self, ctx, inputs, weights):
        gates, expert_idx, pos, valid, expert_out = inputs
        uniform = jnp.ones_like(gates) / gates.shape[1]
        return super().forward(ctx, [uniform, expert_idx, pos, valid, expert_out], weights)


@register_op
class CacheOp(Operator):
    """Cache a tensor across iterations (reference: src/ops/cache.cc —
    MoE caches expert assignments; a score function drives the
    recompile trigger, moe.cc:46-92).

    attrs: use_cached — when True, forward returns the cached value
    (state) instead of the live input; the live input always refreshes
    the cache.  The per-iteration score (mean abs difference between
    live and cached) is written to state as ``{name}/score``.
    """

    op_type = OperatorType.CACHE

    def __init__(self, name, input_shapes, use_cached: bool = False):
        super().__init__(name, input_shapes, use_cached=bool(use_cached))

    def infer(self) -> Sequence[ParallelTensorShape]:
        return (self.input_shapes[0],)

    def state_specs(self):
        x = self.input_shapes[0]
        return (("cached", x.sizes, x.dtype.to_numpy(), 0.0),)

    def forward(self, ctx: LoweringContext, inputs, weights):
        x = inputs[0]
        cached = ctx.state_in[f"{self.name}/cached"]
        score = jnp.mean(jnp.abs(x.astype(jnp.float32) - cached.astype(jnp.float32)))
        ctx.state_out[f"{self.name}/score"] = score
        ctx.state_out[f"{self.name}/cached"] = x
        if self.attrs["use_cached"]:
            return [cached.astype(x.dtype)]
        return [x]

    def splittable_output_dims(self) -> Tuple[int, ...]:
        return tuple(range(self.output_shapes[0].ndim))


# ---- an expert layer that is told which experts it holds -----------------
# Router -> dispatch -> per-expert products -> combine.  The router keeps
# its published width (it scores ALL experts); the layer holds
# ``experts_held`` of them from ``expert_offset`` on — one chip's share of
# an expert-parallel group — and computes their part of the result for
# the tokens routed to them.  What the absent experts would add is left
# out; nothing stands in for the absent chips or their exchange.
# Shapes stay static through ``rows``: a bound on the assignments the
# CHIP's experts take together in one step, laid out sorted by expert, so
# one expert may take any share of it.  Every assignment past it is
# COUNTED (``moe.assignments_dropped``), never dropped silently.  The
# counters live in the model's state (int32, on the device) and ``fit``
# publishes them where it already reads the loss.

# device counters: state keys are ``{op}/obs/{metric}`` (obs/device_counters.py)
_DISPATCH_COUNTERS = ("moe.assignments", "moe.assignments_dropped",
                      "moe.rows_filled", "moe.row_slots",
                      "moe.expert_load_max", "moe.rows_at_fullest_load",
                      "moe.experts_touched", "moe.experts_held")


@register_op
class MoERouterOp(Operator):
    """x [..., D] -> (weights [..., k] float32, experts [..., k] int32).

    ``s = sigmoid(x W)``; the top ``k`` of ``s + b`` are CHOSEN and
    weighed by ``s`` itself: ``s_i / (sum of the chosen s + 1e-20)``
    times ``scale``.  In float32 with the matmul at
    ``HIGHEST`` whatever the compute dtype, as published implementations
    cast their gate to float32: the choice is discrete.

    ``b`` is the correction bias of auxiliary-loss-free balancing
    (``noaux_tc``).  It takes no gradient and is no parameter: it lives
    in the model's state (``{name}/bias``, zeros at first) and is held
    constant — the rule that moves it between steps is a training-loop
    heuristic no published config gives; whoever has one sets the state.

    ``experts_held`` says how many of the ``n_experts`` answer in this
    layer (all by default).  Where only SOME do, the weights are handed
    on without a gradient: the one learning signal such a router gets is
    the partial sum of the experts that happen to live here, and through
    the normalisation it pulls every token toward them (measured on the
    chip: 40 % of all assignments on 6 % of the experts within 60 steps,
    one expert chosen by every token) — where all experts answer, the
    pulls balance."""

    op_type = OperatorType.MOE_ROUTER
    scope = "ff.moe.route"

    def __init__(self, name, input_shapes, n_experts: int, k: int,
                 scale: float = 1.0, experts_held: int | None = None,
                 kernel_initializer=None):
        self._kernel_init = kernel_initializer or DEFAULT_WEIGHT_INIT
        held = n_experts if experts_held is None else experts_held
        super().__init__(name, input_shapes, n_experts=int(n_experts),
                         k=int(k), scale=float(scale), experts_held=int(held))

    def infer(self) -> Sequence[ParallelTensorShape]:
        lead = self.input_shapes[0].sizes[:-1] + (self.attrs["k"],)
        return (ParallelTensorShape.make(lead, DataType.FLOAT32),
                ParallelTensorShape.make(lead, DataType.INT32))

    def weight_specs(self):
        d, e = self.input_shapes[0].sizes[-1], self.attrs["n_experts"]
        return (WeightSpec("kernel", (d, e), DataType.FLOAT32, self._kernel_init),)

    def state_specs(self):
        return (("bias", (self.attrs["n_experts"],), jnp.float32, 0.0),)

    def forward(self, ctx: LoweringContext, inputs, weights):
        a = self.attrs
        scores = jax.nn.sigmoid(jnp.dot(
            inputs[0].astype(jnp.float32), weights["kernel"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        _, chosen = jax.lax.top_k(
            scores + ctx.state_in[f"{self.name}/bias"], a["k"])
        w = jnp.take_along_axis(scores, chosen, axis=-1)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        if a["experts_held"] < a["n_experts"]:
            w = jax.lax.stop_gradient(w)
        return [w * a["scale"], chosen.astype(jnp.int32)]

    def propagate(self, mv: MachineView) -> OpSharding:
        degs = mv.dim_degrees[:-1] + (1,)  # a token's k choices stay together
        a = ShardAnnot(degs, mv.replica_degree)
        return OpSharding(inputs=(a,),
                          weights=(ShardAnnot((1, 1), mv.num_parts),),
                          outputs=(a, a))

    def splittable_output_dims(self) -> Tuple[int, ...]:
        return tuple(range(self.output_shapes[0].ndim - 1))

    def flops(self) -> float:
        x = self.input_shapes[0]
        return 2.0 * x.num_elements * self.attrs["n_experts"]


@register_op
class ExpertDispatchOp(Operator):
    """(x [..., D], experts [..., K]) -> (sorted [rows, D], source [rows]
    int32, sizes [E_held] int32).

    attrs: ``n_experts`` (the router's width), ``experts_held`` and
    ``expert_offset`` (which of them live here), ``rows`` (the chip's
    row bound; never more rows than there are assignments, so one graph
    serves a decode frame's [B, 1, D] and a prefill chunk's [1, C, D]
    under one bound).  ``sorted`` holds the token row of every
    assignment to a held expert, the experts' rows one after the other;
    ``sizes`` counts each expert's; ``source`` names the assignment
    (token * K + choice) in each row, or T * K where the row is empty (a
    zero row)."""

    op_type = OperatorType.EXPERT_DISPATCH
    scope = "ff.moe.dispatch"
    writes_state = True

    def __init__(self, name, input_shapes, n_experts: int, experts_held: int,
                 rows: int, expert_offset: int = 0):
        assert 0 <= expert_offset and expert_offset + experts_held <= n_experts
        super().__init__(name, input_shapes, n_experts=int(n_experts),
                         experts_held=int(experts_held),
                         expert_offset=int(expert_offset), rows=int(rows))

    def infer(self) -> Sequence[ParallelTensorShape]:
        x, experts = self.input_shapes
        held = self.attrs["experts_held"]
        rows = min(self.attrs["rows"], experts.num_elements)
        return (ParallelTensorShape.make((rows, x.sizes[-1]), x.dtype),
                ParallelTensorShape.make((rows,), DataType.INT32),
                ParallelTensorShape.make((held,), DataType.INT32))

    def state_specs(self):
        return tuple((f"obs/{c}", (), jnp.int32, 0) for c in _DISPATCH_COUNTERS)

    def forward(self, ctx: LoweringContext, inputs, weights):
        from flexflow_tpu.kernels.moe_dispatch import held_rows

        x, experts = inputs
        a = self.attrs
        k = experts.shape[-1]
        rows = min(a["rows"], experts.size)
        source, sizes, load = held_rows(experts.reshape(-1), a["experts_held"],
                                        a["expert_offset"], rows)
        tokens = x.reshape(-1, x.shape[-1])
        sorted_rows = tokens.at[source // k].get(mode="fill", fill_value=0)
        counted = {
            "moe.assignments": jnp.sum(load),
            "moe.assignments_dropped": jnp.sum(load - sizes),
            "moe.rows_filled": jnp.sum(sizes),
            "moe.row_slots": jnp.int32(rows),
            "moe.expert_load_max": jnp.max(load),
            # what padding every held expert to the fullest would take:
            # over ``moe.assignments`` it reads the fullest against the mean
            "moe.rows_at_fullest_load": jnp.max(load) * a["experts_held"],
            # held experts with a row this step, of the experts held: what
            # of the experts' weights a weight-bound step has to read
            "moe.experts_touched": jnp.sum(sizes > 0),
            "moe.experts_held": jnp.int32(a["experts_held"]),
        }
        for name in _DISPATCH_COUNTERS:
            key = f"{self.name}/obs/{name}"
            ctx.state_out[key] = ctx.state_in[key] + counted[name].astype(
                jnp.int32)
        return [sorted_rows, source, sizes]

    def propagate(self, mv: MachineView) -> OpSharding:
        # the sort sees every assignment: inputs whole, outputs unsplit
        x, experts = self.input_shapes
        parts = mv.num_parts
        return OpSharding(
            inputs=(ShardAnnot((1,) * x.ndim, parts),
                    ShardAnnot((1,) * experts.ndim, parts)),
            weights=(),
            outputs=(ShardAnnot((1, 1), parts), ShardAnnot((1,), parts),
                     ShardAnnot((1,), parts)))

    def splittable_output_dims(self) -> Tuple[int, ...]:
        return ()

    def flops(self) -> float:
        return float(self.output_shapes[0].num_elements)


@register_op
class ExpertLinearOp(Operator):
    """Every expert its OWN kernel ``[E, D, out]`` (and bias ``[E, out]``).

    One input ``x [E, rows, D]`` (each expert's rows padded to one
    length, ``GroupByOp``'s layout) -> ``[E, rows, out]``: one batched
    product over the expert dim — the shardable dim of expert
    parallelism.  Two inputs ``(x [rows, D], sizes [E])`` (rows sorted by
    expert, ``ExpertDispatchOp``'s layout) -> ``[rows, out]``: one grouped
    product (``jax.lax.ragged_dot``), no padding between experts; rows
    past the sizes' sum come out zero."""

    op_type = OperatorType.EXPERT_LINEAR
    scope = "ff.moe.experts"

    def __init__(self, name, input_shapes, out_dim: int,
                 activation: str | None = None, use_bias: bool = False,
                 kernel_initializer=None, param_dtype: str = "float32"):
        if activation not in _ACTIVATIONS:
            raise NotImplementedError(
                f"ExpertLinearOp activation {activation!r} not supported")
        # extension-only: float32 kernels add NO attr (signatures stay)
        extra = {} if param_dtype == "float32" else {"param_dtype": param_dtype}
        # Glorot over the STACKED kernel [E, D, out]: the expert dim counts
        # as receptive field, so every expert starts at 1/sqrt(E) of a lone
        # Linear's scale — E experts' outputs are summed into one stream
        self._kernel_init = kernel_initializer or DEFAULT_WEIGHT_INIT
        super().__init__(name, input_shapes, out_dim=int(out_dim),
                         activation=activation, use_bias=bool(use_bias),
                         **extra)
        assert not (self.grouped and use_bias), (
            "a bias on sorted rows needs each row's expert: no caller has one")

    @property
    def grouped(self) -> bool:
        return len(self.input_shapes) == 2

    @property
    def n_experts(self) -> int:
        return self.input_shapes[1 if self.grouped else 0].sizes[0]

    def infer(self) -> Sequence[ParallelTensorShape]:
        x = self.input_shapes[0]
        assert x.ndim == (2 if self.grouped else 3), (
            "ExpertLinearOp takes [experts, rows, width], or [rows, width] "
            "sorted by expert with the experts' sizes")
        return (ParallelTensorShape.make(
            x.sizes[:-1] + (self.attrs["out_dim"],), x.dtype),)

    def weight_specs(self):
        e, d = self.n_experts, self.input_shapes[0].sizes[-1]
        out = self.attrs["out_dim"]
        pd = DataType.from_any(self.attrs.get("param_dtype", "float32"))
        specs = [WeightSpec("kernel", (e, d, out), pd, self._kernel_init)]
        if self.attrs["use_bias"]:
            specs.append(WeightSpec("bias", (e, out), pd, DEFAULT_BIAS_INIT))
        return specs

    def serving_weights(self, weights, compute_dtype):
        """The stacked kernel in the dtype the grouped product reads."""
        return {**weights,
                "kernel": weights["kernel"].astype(compute_dtype)}

    def forward(self, ctx: LoweringContext, inputs, weights):
        cd = ctx.compute_dtype
        x = inputs[0].astype(cd)
        kernel = self.serving_weights(weights, cd)["kernel"]
        act = _ACTIVATIONS[self.attrs["activation"]]
        if self.grouped:
            sizes = inputs[1]
            y = act(jax.lax.ragged_dot(x, kernel, sizes,
                                       preferred_element_type=jnp.float32))
            # whatever the grouped kernel leaves in the rows no expert has
            filled = jnp.arange(x.shape[0], dtype=sizes.dtype) < jnp.sum(sizes)
            y = jnp.where(filled[:, None], y, 0.0)
        else:
            y = jnp.einsum("erd,edf->erf", x, kernel,
                           preferred_element_type=jnp.float32)
            if self.attrs["use_bias"]:
                y = y + weights["bias"].astype(jnp.float32)[:, None, :]
            y = act(y)
        return [y.astype(inputs[0].dtype)]

    def propagate(self, mv: MachineView) -> OpSharding:
        if self.grouped:  # one grouped kernel: rows, sizes and kernels whole
            parts = mv.num_parts
            assert mv.dim_degrees == (1, 1), "sorted rows stay whole"
            return OpSharding(
                inputs=(ShardAnnot((1, 1), parts), ShardAnnot((1,), parts)),
                weights=(ShardAnnot((1, 1, 1), parts),),
                outputs=(ShardAnnot((1, 1), parts),))
        e, rows, out = mv.dim_degrees
        assert rows == 1, "an expert's rows stay whole"
        w = [ShardAnnot((e, 1, out), replica=mv.replica_degree, idx=(0, -1, 2))]
        if self.attrs["use_bias"]:
            w.append(ShardAnnot((e, out), replica=mv.replica_degree, idx=(0, 2)))
        return OpSharding(
            inputs=(ShardAnnot((e, 1, 1), replica=out * mv.replica_degree),),
            weights=tuple(w),
            outputs=(ShardAnnot(mv.dim_degrees, mv.replica_degree),))

    def splittable_output_dims(self) -> Tuple[int, ...]:
        # padded: the expert dim (EP) and the out dim
        return () if self.grouped else (0, 2)

    def flops(self) -> float:
        return 2.0 * self.output_shapes[0].num_elements * self.input_shapes[0].sizes[-1]


@register_op
class ExpertCombineOp(Operator):
    """(weights [..., K], source [rows], expert_out [rows, D]) ->
    [..., D]: every filled row times its assignment's routing weight,
    added into its token's row.  A token none of whose experts is held
    here gets zeros."""

    op_type = OperatorType.EXPERT_COMBINE
    scope = "ff.moe.combine"

    def infer(self) -> Sequence[ParallelTensorShape]:
        w, _, out = self.input_shapes
        return (ParallelTensorShape.make(w.sizes[:-1] + (out.sizes[-1],),
                                         out.dtype),)

    def forward(self, ctx: LoweringContext, inputs, weights):
        w, source, expert_out = inputs
        k, d = w.shape[-1], expert_out.shape[-1]
        tokens = w.size // k
        # an empty row (source = T * K) reads weight 0 and adds to no token
        w_row = w.reshape(-1).astype(jnp.float32).at[source].get(
            mode="fill", fill_value=0)
        rows = expert_out.astype(jnp.float32) * w_row[:, None]
        y = jnp.zeros((tokens, d), jnp.float32).at[source // k].add(
            rows, mode="drop")
        return [y.reshape(w.shape[:-1] + (d,)).astype(expert_out.dtype)]

    def propagate(self, mv: MachineView) -> OpSharding:
        w, source, out = self.input_shapes
        parts = mv.num_parts
        whole = ShardAnnot((1,) * len(mv.dim_degrees), parts)
        return OpSharding(
            inputs=(ShardAnnot((1,) * w.ndim, parts),
                    ShardAnnot((1,) * source.ndim, parts),
                    ShardAnnot((1,) * out.ndim, parts)),
            weights=(), outputs=(whole,))

    def splittable_output_dims(self) -> Tuple[int, ...]:
        return ()

    def flops(self) -> float:
        return 2.0 * self.input_shapes[2].num_elements
