"""Embedding lookup — the parameter-parallel workhorse (DLRM).

Reference: src/ops/embedding.{cc,cu} (table partitioned over vocab or
channel, embedding.cc:123-190; aggr none/sum/avg).  TPU-native: the
lookup is ``jnp.take``; under a vocab-partitioned strategy the lowering
keeps the gather local per shard with masking + partial-sum state so
XLA emits a reduce-scatter/psum over table shards instead of
all-gathering the table (SURVEY.md §7 hard part (e)).
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from flexflow_tpu.core.machine import MachineView
from flexflow_tpu.core.optype import OperatorType
from flexflow_tpu.core.ptensor import DataType, ParallelTensorShape
from flexflow_tpu.initializers import Initializer, NormInitializer
from flexflow_tpu.ops.base import (
    REPLICA_SLOT,
    LoweringContext,
    Operator,
    OpSharding,
    ShardAnnot,
    WeightSpec,
    register_op,
)


@register_op
class EmbeddingOp(Operator):
    """ids [B] or [B, S] (int) -> [B, D] (aggr sum/avg over S, or no S)
    or [B, S, D] (aggr none).

    attrs: num_entries (vocab), out_dim, aggr ('none'|'sum'|'avg').
    """

    op_type = OperatorType.EMBEDDING

    def __init__(
        self,
        name,
        input_shapes,
        num_entries: int,
        out_dim: int,
        aggr: str = "none",
        kernel_initializer: Initializer | None = None,
        param_dtype: str = "float32",
        weights_of: str | None = None,
    ):
        assert aggr in ("none", "sum", "avg")
        self._kernel_init = kernel_initializer or NormInitializer(stddev=0.05)
        super().__init__(
            name,
            input_shapes,
            num_entries=num_entries,
            out_dim=out_dim,
            aggr=aggr,
            param_dtype=param_dtype,
            weights_of=weights_of,
        )

    def infer(self) -> Sequence[ParallelTensorShape]:
        x = self.input_shapes[0]
        a = self.attrs
        if a["aggr"] == "none":
            sizes = x.sizes + (a["out_dim"],)
        else:
            sizes = x.sizes[:-1] + (a["out_dim"],) if x.ndim > 1 else (x.sizes[0], a["out_dim"])
        return (ParallelTensorShape.make(sizes, DataType.from_any(a["param_dtype"])),)

    def weight_specs(self) -> Sequence[WeightSpec]:
        a = self.attrs
        return (
            WeightSpec(
                "table",
                (a["num_entries"], a["out_dim"]),
                DataType.from_any(a["param_dtype"]),
                self._kernel_init,
            ),
        )

    def forward(self, ctx: LoweringContext, inputs, weights):
        ids = inputs[0].astype(jnp.int32)
        table = weights["table"]
        a = self.attrs
        y = jnp.take(table, ids, axis=0)  # [..., S?, D]
        if a["aggr"] == "sum" and ids.ndim > 1:
            y = jnp.sum(y, axis=-2)
        elif a["aggr"] == "avg" and ids.ndim > 1:
            y = jnp.mean(y, axis=-2)
        return [y]

    def forward_sharded(self, ctx, inputs, weights, osh):
        """Vocab-split lowering (reference: table partitioned over vocab,
        embedding.cc:123-190): shard_map over the vocab mesh axes does a
        masked LOCAL gather on each table shard and a psum across
        shards — XLA emits one allreduce of [.., D]-shaped activations
        and never gathers the table (GSPMD's default for a global
        jnp.take on a vocab-sharded operand can replicate the table).
        The gradient of the masked local gather is a local scatter-add
        into the shard, so table grads stay sharded too."""
        vocab_axes = (ctx.slot_axes or {}).get(REPLICA_SLOT, ())
        if not vocab_axes or ctx.mesh is None or not self._weight_specs:
            return None
        from flexflow_tpu.comm.compat import shard_map
        from jax.sharding import NamedSharding, PartitionSpec

        from flexflow_tpu.parallel.mesh import annot_partition_spec

        a = self.attrs
        mesh = ctx.mesh
        ids_spec = annot_partition_spec(osh.inputs[0], ctx.slot_axes)
        w_spec = annot_partition_spec(osh.weights[0], ctx.slot_axes)
        out_spec = annot_partition_spec(osh.outputs[0], ctx.slot_axes)
        r = 1
        for ax in vocab_axes:
            r *= mesh.shape[ax]
        if a["num_entries"] % r != 0:
            # uneven vocab split: shard_map cannot tile the table dim;
            # fall back to the GSPMD path, which pads
            return None
        vshard = a["num_entries"] // r

        def local(ids, table):
            ids = ids.astype(jnp.int32)
            idx = jnp.int32(0)
            for ax in vocab_axes:
                idx = idx * mesh.shape[ax] + jax.lax.axis_index(ax)
            lo = idx * vshard
            local_ids = ids - lo
            valid = (local_ids >= 0) & (local_ids < vshard)
            rows = jnp.where(valid, local_ids, 0)
            y = jnp.take(table, rows, axis=0)
            y = jnp.where(valid[..., None], y, jnp.zeros((), table.dtype))
            if a["aggr"] in ("sum", "avg") and ids.ndim > 1:
                y = jnp.sum(y, axis=-2)
            y = jax.lax.psum(y, vocab_axes)
            if a["aggr"] == "avg" and ids.ndim > 1:
                y = y / ids.shape[-1]
            return y

        # the ids are constrained to their annot first so shard_map sees
        # the layout its in_spec declares
        ids = jax.lax.with_sharding_constraint(
            inputs[0], NamedSharding(mesh, ids_spec)
        )
        fn = shard_map(
            local, mesh=mesh,
            in_specs=(ids_spec, w_spec),
            out_specs=out_spec,
        )
        return [fn(ids, weights["table"])]

    def propagate(self, mv: MachineView) -> OpSharding:
        degs = mv.dim_degrees
        r = mv.replica_degree  # vocab split -> partial-sum rows
        d_deg = degs[-1]  # channel split of the table
        batch_parts = 1
        for d in degs[:-1]:
            batch_parts *= d
        x = self.input_shapes[0]
        if self.attrs["aggr"] == "none":
            in_degs = degs[:-1]  # output = input dims + (D,)
        else:
            # output drops the aggregated seq dim: ids [B, S] -> out [B, D]
            in_degs = degs[:-1] + (1,) * (x.ndim - (len(degs) - 1))
        out_nd = len(degs)
        return OpSharding(
            inputs=(ShardAnnot(in_degs, replica=d_deg * r),),
            weights=(
                ShardAnnot(
                    (r, d_deg), replica=batch_parts, idx=(REPLICA_SLOT, out_nd - 1)
                ),
            )[:len(self._weight_specs)],
            outputs=(ShardAnnot(degs, replica=r, partial=r > 1),),
        )

    def splittable_output_dims(self) -> Tuple[int, ...]:
        return tuple(range(self.output_shapes[0].ndim))

    def max_replica_degree(self) -> int:
        # a table read from another op is laid out by that op's view
        return self.attrs["num_entries"] if self._weight_specs else 1

    def flops(self) -> float:
        return float(self.output_shapes[0].num_elements)

    def bytes_accessed(self) -> float:
        # gather traffic dominates: one row per id
        x = self.input_shapes[0]
        rows = x.num_elements
        return float(rows * self.attrs["out_dim"] * 4 + self.output_shapes[0].num_bytes)


@register_op
class BatchedEmbeddingOp(Operator):
    """K stacked lookups: ids [K, B(, S)] (int), table [K, V, D] ->
    [K, B, D] (aggr sum/avg) or [K, B, S, D] (none).

    TPU-native fusion target for K parallel same-shaped embedding
    tables (DLRM): splitting the leading BRANCH dim shards whole
    tables onto disjoint devices — the pure-SPMD realization of the
    reference's per-table placement (its search places each table's
    subgraph on different GPUs via MachineViews, mapper.cc:371-475;
    GSPMD cannot place, but it can shard a stacked branch dim)."""

    op_type = OperatorType.BATCHED_EMBEDDING

    def __init__(
        self,
        name,
        input_shapes,
        num_tables: int,
        num_entries: int,
        out_dim: int,
        aggr: str = "none",
        kernel_initializer: Initializer | None = None,
        param_dtype: str = "float32",
    ):
        assert aggr in ("none", "sum", "avg")
        self._kernel_init = kernel_initializer or NormInitializer(stddev=0.05)
        super().__init__(
            name,
            input_shapes,
            num_tables=num_tables,
            num_entries=num_entries,
            out_dim=out_dim,
            aggr=aggr,
            param_dtype=param_dtype,
        )

    def infer(self) -> Sequence[ParallelTensorShape]:
        x = self.input_shapes[0]  # [K, B(, S)]
        a = self.attrs
        if a["aggr"] == "none":
            sizes = x.sizes + (a["out_dim"],)
        else:
            sizes = x.sizes[:2] + (a["out_dim"],)
        return (ParallelTensorShape.make(sizes, DataType.from_any(a["param_dtype"])),)

    def weight_specs(self) -> Sequence[WeightSpec]:
        a = self.attrs
        return (
            WeightSpec(
                "table",
                (a["num_tables"], a["num_entries"], a["out_dim"]),
                DataType.from_any(a["param_dtype"]),
                self._kernel_init,
            ),
        )

    def forward(self, ctx: LoweringContext, inputs, weights):
        ids = inputs[0].astype(jnp.int32)
        table = weights["table"]
        a = self.attrs

        def one(t, i):
            y = jnp.take(t, i, axis=0)
            if a["aggr"] == "sum" and i.ndim > 1:
                y = jnp.sum(y, axis=-2)
            elif a["aggr"] == "avg" and i.ndim > 1:
                y = jnp.mean(y, axis=-2)
            return y

        return [jax.vmap(one)(table, ids)]

    def propagate(self, mv: MachineView) -> OpSharding:
        degs = mv.dim_degrees  # over output [K, B, D] (or [K, B, S, D])
        r = mv.replica_degree  # vocab split -> partial rows
        k_deg, d_deg = degs[0], degs[-1]
        batch_parts = 1
        for d in degs[1:-1]:
            batch_parts *= d
        x = self.input_shapes[0]
        if self.attrs["aggr"] == "none":
            in_degs = degs[:-1]
        else:
            in_degs = degs[:-1] + (1,) * (x.ndim - (len(degs) - 1))
        out_nd = len(degs)
        return OpSharding(
            inputs=(ShardAnnot(in_degs, replica=d_deg * r),),
            weights=(
                ShardAnnot(
                    (k_deg, r, d_deg),
                    replica=batch_parts,
                    idx=(0, REPLICA_SLOT, out_nd - 1),
                ),
            ),
            outputs=(ShardAnnot(degs, replica=r, partial=r > 1),),
        )

    def splittable_output_dims(self) -> Tuple[int, ...]:
        return tuple(range(self.output_shapes[0].ndim))

    def max_replica_degree(self) -> int:
        return self.attrs["num_entries"]

    def flops(self) -> float:
        return float(self.output_shapes[0].num_elements)

    def bytes_accessed(self) -> float:
        x = self.input_shapes[0]
        rows = x.num_elements
        return float(
            rows * self.attrs["out_dim"] * 4 + self.output_shapes[0].num_bytes
        )
