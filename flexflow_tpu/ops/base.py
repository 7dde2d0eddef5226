"""Operator base machinery.

An ``Operator`` is an immutable descriptor: op type + attributes +
logical input/output shapes + weight specs.  It provides three things
the framework needs:

1. **Shape inference** — at graph-build time (role of the reference's
   per-op constructors, e.g. linear.cc:109-203).
2. **Lowering** — ``forward(ctx, inputs, weights)``: pure JAX on
   *global* (logical) arrays.  There are no device kernels to write:
   XLA maps these onto MXU/VPU, and GSPMD partitions them according to
   the sharding constraints the strategy attaches at tensor edges.
   Autodiff replaces all the reference's hand-written backward tasks.
3. **Degree propagation** — ``propagate(mv)``: given the op's
   MachineView (partition degrees of its output), derive the partition
   degrees of inputs and weights.  This is the TPU re-expression of the
   reference's ParallelDimMappingRecord solver
   (reference: include/flexflow/operator.h:21-48, model.cc:234-243,
   linear.cc:948-1135) — but in logical dim order and with replica /
   partial-sum state explicit.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple, Type

import jax
import jax.numpy as jnp

from flexflow_tpu.core.machine import MachineView
from flexflow_tpu.core.optype import OperatorType
from flexflow_tpu.core.ptensor import DataType, ParallelTensorShape
from flexflow_tpu.initializers import Initializer


@dataclass(frozen=True)
class WeightSpec:
    """A named trainable weight owned by an op (reference: per-op
    create_weight calls, e.g. linear.cc weight/bias)."""

    name: str
    shape: Tuple[int, ...]
    dtype: DataType
    initializer: Initializer
    # degree of each weight dim under the *trivial* view is 1; propagate()
    # fills real degrees per strategy.


REPLICA_SLOT = -2  # parallel_idx value meaning "the view's replica slot"


@dataclass(frozen=True)
class ShardAnnot:
    """Sharding annotation of one tensor under an op's MachineView.

    ``degrees[i]``  — partition degree of tensor dim i.
    ``idx[i]``      — *parallel index*: which view slot dim i derives
                      from — an output-dim index, ``REPLICA_SLOT`` for
                      the view's contraction/replica slot, or -1 when
                      unsharded.  This is the reference's
                      ``ParallelDim::parallel_idx``
                      (parallel_tensor.h:35-63): it guarantees that,
                      e.g., a Linear weight's out-dim lands on the SAME
                      mesh axes as the activation's out-dim.
                      Defaults to identity by position.
    ``replica``     — replication count of this tensor over the rest of
                      the view (memory accounting; lowering derives
                      replication implicitly from unused axes).
    ``partial=True``— partial-sum state: the value still needs a psum
                      over ``replica`` addends, so it is NOT expressible
                      as a GSPMD constraint and lowering skips it.
    """

    degrees: Tuple[int, ...]
    replica: int = 1
    partial: bool = False
    idx: Tuple[int, ...] = ()

    def __hash__(self):
        # cached: ShardAnnots key the cost model's memo dicts and are
        # hashed millions of times per search; the dataclass-generated
        # hash rebuilds the field tuple every call
        h = self.__dict__.get("_hash")
        if h is None:
            h = hash((self.degrees, self.replica, self.partial, self.idx))
            object.__setattr__(self, "_hash", h)
        return h

    def parallel_idx(self) -> Tuple[int, ...]:
        if self.idx:
            return self.idx
        return tuple(
            i if d > 1 else -1 for i, d in enumerate(self.degrees)
        )

    @property
    def num_parts(self) -> int:
        p = self.replica
        for d in self.degrees:
            p *= d
        return p

    @staticmethod
    def trivial(ndim: int) -> "ShardAnnot":
        return ShardAnnot((1,) * ndim)


@dataclass(frozen=True)
class OpSharding:
    """Result of degree propagation for one op under one MachineView.

    An ``inputs`` entry may be ``None`` = *unconstrained*: the producer's
    sharding governs and no constraint is applied (parallel ops use this
    — the sharding delta at the edge IS their data movement).  Every
    consumer of OpSharding.inputs must handle None.
    """

    inputs: Tuple[Optional[ShardAnnot], ...]
    weights: Tuple[ShardAnnot, ...]
    outputs: Tuple[ShardAnnot, ...]


class LoweringContext:
    """Carried through lowering of the whole PCG."""

    def __init__(
        self,
        compute_dtype=jnp.bfloat16,
        train: bool = True,
        rng: Optional[jax.Array] = None,
        seq_length: int = -1,
        state_in: Optional[Dict[str, Any]] = None,
        mesh=None,
    ):
        self.compute_dtype = compute_dtype
        self.train = train
        self.rng = rng
        self.seq_length = seq_length
        self.state_in = state_in or {}
        self.state_out: Dict[str, Any] = {}
        self.mesh = mesh  # global device mesh (None on single device)
        self.slot_axes: Optional[Dict[int, tuple]] = None  # current op's view axes

    def op_rng(self, op_name: str) -> jax.Array:
        if self.rng is None:
            return jax.random.key(0)
        return jax.random.fold_in(self.rng, hash(op_name) & 0x7FFFFFFF)


class Operator:
    """Immutable operator descriptor (PCG node payload)."""

    op_type: OperatorType = OperatorType.NOOP
    # True when forward() writes ctx.state_out — such ops are impure and
    # must not be wrapped in jax.checkpoint (remat); set by every op
    # that mutates state, with or without state_specs
    writes_state: bool = False
    # True for graph sources (inputs/constants) whose output edges carry
    # no cotangent in training — the cost model charges such edges the
    # forward reshard only, not the 2x fwd+bwd factor
    is_gradient_free: bool = False

    def __init__(
        self,
        name: str,
        input_shapes: Sequence[ParallelTensorShape],
        weights_of: Optional[str] = None,
        **attrs,
    ):
        self.name = name
        self.input_shapes: Tuple[ParallelTensorShape, ...] = tuple(
            s.drop_parallelism() for s in input_shapes
        )
        self.attrs: Dict[str, Any] = dict(attrs)
        if weights_of:
            # absent unless set, so every op built before the key
            # existed keeps its signature (cost cache, calibration)
            self.attrs["weights_of"] = weights_of
        self.output_shapes: Tuple[ParallelTensorShape, ...] = tuple(self.infer())
        # an op that reads another's weights declares none of its own
        self._weight_specs: Tuple[WeightSpec, ...] = (
            () if weights_of else tuple(self.weight_specs()))

    # ``jax.named_scope`` of the op's lowering (``ff.mla``,
    # ``ff.moe.route`` ...): device time can be charged to it in a trace
    scope: Optional[str] = None
    # ... and of the block of the model the builder put it in
    # (``FFModel.block_scope``: ``ff.mtp``), outside ``scope``
    block_scope: Optional[str] = None
    # ... and the ``FFModel.remat_block`` it was added in, whose ops
    # the lowering recomputes together under ``FFConfig.remat``
    remat_block: Optional[int] = None

    @property
    def weights_key(self) -> str:
        """The entry of the parameter tree this op reads: its own name,
        or — ``weights_of``, which every weighted op takes — the op
        whose weights it shares (it then declares none of its own: one
        copy is initialised, optimised and counted, and every reader's
        gradient adds up in it; ``FFModel`` refuses a sharer whose
        ``weight_specs()`` differ from its owner's)."""
        return self.attrs.get("weights_of") or self.name

    # ---- hooks -----------------------------------------------------------
    def infer(self) -> Sequence[ParallelTensorShape]:
        raise NotImplementedError(type(self).__name__)

    def weight_specs(self) -> Sequence[WeightSpec]:
        return ()

    def forward(
        self,
        ctx: LoweringContext,
        inputs: List[jax.Array],
        weights: Dict[str, jax.Array],
    ) -> List[jax.Array]:
        raise NotImplementedError(type(self).__name__)

    def serving_weights(
        self, weights: Dict[str, jax.Array], compute_dtype
    ) -> Dict[str, jax.Array]:
        """The op's own weights in the dtype and layout ``forward``
        computes with, for a program that is called many times on the
        same weights (a decode frame, a prefill chunk): derived ONCE
        (runtime/decode.py ``compiled_decode_step``) instead of inside
        every call.  ``forward`` takes either tree — an op that
        overrides this calls it from ``forward`` too, where it is a
        no-op on leaves already served, so the two cannot drift.
        Default: the weights as they are."""
        return weights

    def forward_sharded(
        self,
        ctx: LoweringContext,
        inputs: List[jax.Array],
        weights: Dict[str, jax.Array],
        osh: "OpSharding",
    ) -> Optional[List[jax.Array]]:
        """Optional explicit-SPMD lowering: return outputs computed with
        shard_map/collectives when GSPMD's default partitioning of
        ``forward`` would be wrong or slow for this op's sharding (e.g.
        a vocab-split embedding gather), or None to use ``forward``.
        Only called on multi-device meshes."""
        return None

    def propagate(self, mv: MachineView) -> OpSharding:
        """Default rule: elementwise-style — every input shares the
        output's annotation (valid only when input rank == output rank);
        weights replicated over all parts."""
        out = ShardAnnot(mv.dim_degrees, mv.replica_degree)
        ins = tuple(
            ShardAnnot(mv.dim_degrees, mv.replica_degree) for _ in self.input_shapes
        )
        w = tuple(
            ShardAnnot((1,) * len(ws.shape), mv.num_parts) for ws in self._weight_specs
        )
        return OpSharding(inputs=ins, weights=w, outputs=(out,))

    def flops(self) -> float:
        """Forward FLOPs estimate for the cost model (role of the
        reference's measure_operator_cost, simulator.cc:515)."""
        return sum(s.num_elements for s in self.output_shapes)

    def bytes_accessed(self) -> float:
        b = sum(s.num_bytes for s in self.input_shapes)
        b += sum(s.num_bytes for s in self.output_shapes)
        for w in self._weight_specs:
            n = 1
            for d in w.shape:
                n *= d
            b += n * w.dtype.itemsize
        return float(b)

    # ---- search hooks ----------------------------------------------------
    def fixed_machine_view(self) -> Optional["MachineView"]:
        """Non-None when the op's attributes pin its view (parallel ops:
        a Repartition to degree d MUST be viewed with degree d).  Default
        strategy builders honor this instead of guessing."""
        return None

    def splittable_output_dims(self) -> Tuple[int, ...]:
        """Output dims the search may partition. Default: dim 0 (batch)."""
        return (0,) if self.output_shapes[0].ndim else ()

    def max_replica_degree(self) -> int:
        """>1 if the op supports partial-sum (row-parallel) execution."""
        return 1

    # ---- identity --------------------------------------------------------
    # attrs that never change the lone-op kernel a single-chip probe
    # measures (they select a multi-device execution scheme): excluded
    # from calibration_signature so one probe record serves every mode
    _CALIBRATION_INERT_ATTRS: frozenset = frozenset()

    def signature(self) -> Tuple:
        """Structural identity: two ops with equal signatures have equal
        shapes/costs/propagation.  Cached — Operator is immutable."""
        sig = getattr(self, "_sig_cache", None)
        if sig is None:
            sig = (
                self.op_type.value,
                tuple(s.sizes for s in self.input_shapes),
                tuple(s.dtype.value for s in self.input_shapes),
                tuple(sorted((k, _sig_value(v)) for k, v in self.attrs.items())),
            )
            self._sig_cache = sig
        return sig

    def calibration_signature(self) -> Tuple:
        """Probe-record identity: ``signature()`` minus the
        _CALIBRATION_INERT_ATTRS — a single-chip measurement cannot
        depend on them, so keying records by them would fragment the
        table (e.g. three copies of every attention record, one per
        sp_mode)."""
        if not self._CALIBRATION_INERT_ATTRS:
            return self.signature()
        sig = self.signature()
        attrs = tuple(
            (k, v) for k, v in sig[3]
            if k not in self._CALIBRATION_INERT_ATTRS
        )
        # sig[4:] preserves anything a subclass APPENDS to signature():
        # truncating here would alias calibration records of ops that
        # differ only in the appended components
        return sig[:3] + (attrs,) + sig[4:]

    def __repr__(self) -> str:
        return f"{type(self).__name__}({self.name})"


def _sig_value(v):  # noqa: C901 — simple type dispatch
    if isinstance(v, Initializer):
        return v.signature()
    if isinstance(v, (list, tuple)):
        return tuple(_sig_value(x) for x in v)
    if isinstance(v, (int, float, str, bool, type(None))):
        return v
    if isinstance(v, DataType):
        return v.value
    return repr(v)


# ---- registry ------------------------------------------------------------
OP_REGISTRY: Dict[OperatorType, Type[Operator]] = {}


def register_op(cls: Type[Operator]) -> Type[Operator]:
    OP_REGISTRY[cls.op_type] = cls
    return cls


