"""FFModel — the public model-building and training API.

Mirrors the surface of the reference's FFModel
(reference: include/flexflow/model.h:316-700 layer methods;
python/flexflow/core/flexflow_cffi.py:784-1900): ``create_tensor`` +
layer methods build a lazy graph; ``compile`` turns it into a PCG,
picks a parallelization strategy, and lowers to one jitted SPMD
program; ``fit``/``eval`` run the training loop.

Differences by design (TPU-native):
* no init/forward/backward/update verbs per op — one fused train step;
* the parallelization strategy is sharding degrees over a global mesh,
  searched by flexflow_tpu.search (Unity algorithm) or data-parallel;
* NHWC conv layout.
"""

from __future__ import annotations

import contextlib
import functools
import itertools
import math as _math
import time
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from flexflow_tpu.config import FFConfig
from flexflow_tpu.core.graph import Graph, Node
from flexflow_tpu.core.machine import MachineView
from flexflow_tpu.core.optype import OperatorType
from flexflow_tpu.core.ptensor import DataType, ParallelTensorShape, Tensor
from flexflow_tpu.initializers import Initializer
from flexflow_tpu.losses import LossType
from flexflow_tpu.metrics import MetricsType, PerfMetrics
from flexflow_tpu import ops as O
from flexflow_tpu.optimizers import Optimizer, SGDOptimizer


def _merge_matching(new, old):
    """Recursively keep ``new``'s structure, copying ``old``'s values at
    key paths present in both with matching array shapes."""
    if isinstance(new, dict) and isinstance(old, dict):
        return {
            k: _merge_matching(v, old[k]) if k in old else v
            for k, v in new.items()
        }
    if hasattr(new, "shape") and hasattr(old, "shape") and new.shape == old.shape:
        return old
    return new


def _adopt_kv_dtype(graph, dtype) -> None:
    """Retype the graph's decode-attention page pools IN PLACE to the
    ``__meta__.kv`` dtype (searched or imported, both SHD168/169-gated
    before this runs).  Called strictly AFTER the strategy export's
    digest computation — exported artifacts stay keyed to the attr-free
    frontend graph, so the import-side digest gate still passes — and
    before lowering, so ``state_specs``/``state_shardings`` build the
    quantized pool (+ per-(page, slot) scales under int8) the pricing
    chose.  fp32 is the attr-free default: nothing to adopt, the
    lowered program stays bit-identical to history."""
    if dtype in (None, "fp32"):
        return
    from flexflow_tpu.core.graph import Node
    from flexflow_tpu.core.optype import OperatorType

    changed = False
    for guid, node in list(graph.nodes.items()):
        op = node.op
        if op.op_type != OperatorType.DECODE_ATTENTION:
            continue
        if op.attrs.get("kv_dtype", "fp32") == dtype:
            continue
        a = op.attrs
        clone = type(op)(
            op.name, op.input_shapes,
            embed_dim=a["embed_dim"], num_heads=a["num_heads"],
            page_size=a["page_size"], pages_per_seq=a["pages_per_seq"],
            num_pages=a["num_pages"], use_kernel=a["use_kernel"],
            kv_dtype=dtype, kernel_initializer=op._kernel_init,
        )
        graph.nodes[guid] = Node(guid, clone)
        changed = True
    if changed:
        graph._invalidate()


class FFModel:
    def __init__(self, config: Optional[FFConfig] = None):
        from flexflow_tpu.search.plan import StrategyPlan

        self.config = config or FFConfig()
        # the one record compile() decides and the lowering reads
        # (search/plan.py); until then it holds the graph being built
        self.plan = StrategyPlan(Graph(), None, "caller")
        self._producer: Dict[int, Tuple[Node, int]] = {}  # tensor.guid -> (node, out_idx)
        self._input_tensors: List[Tensor] = []
        self._name_counts: Dict[str, int] = {}
        self.compiled = None
        self.params = None
        self.opt_state = None
        self.state = None
        self.optimizer: Optional[Optimizer] = None
        self._rng_counter = 0
        self._block_scope: Optional[str] = None  # see block_scope()
        self._remat_block: Optional[int] = None  # see remat_block()
        self._remat_ids = itertools.count()
        # device counters' totals already published (obs/device_counters.py)
        self._obs_seen: Dict[str, int] = {}

    # ------------------------------------------------------------------
    def _fresh_name(self, base: str, name: Optional[str]) -> str:
        if name:
            return name
        i = self._name_counts.get(base, 0)
        self._name_counts[base] = i + 1
        return f"{base}_{i}"

    def _shape_of(self, t: Tensor) -> ParallelTensorShape:
        return ParallelTensorShape.make(t.sizes, t.dtype)

    @contextlib.contextmanager
    def block_scope(self, scope: str):
        """Ops added inside lower under ``jax.named_scope(scope)`` (outside
        their own ``Operator.scope``): a block of the model — ``ff.mtp`` —
        that device time can be charged to in a trace."""
        outer, self._block_scope = self._block_scope, scope
        try:
            yield
        finally:
            self._block_scope = outer

    @contextlib.contextmanager
    def remat_block(self):
        """Ops added inside are recomputed TOGETHER under
        ``FFConfig.remat``: the lowering saves what enters the block and
        nothing inside it (one layer of a deep or looped stack), where
        it would otherwise save every weighted op's inputs.  Without
        ``remat`` it says nothing."""
        outer, self._remat_block = self._remat_block, next(self._remat_ids)
        try:
            yield
        finally:
            self._remat_block = outer

    def _add_op(self, op: O.Operator, inputs: Sequence[Tensor]) -> List[Tensor]:
        if self._block_scope:
            op.block_scope = self._block_scope
        if self._remat_block is not None:
            op.remat_block = self._remat_block
        if op.weights_key != op.name:
            self._check_sharer(op)
        node = self.graph.new_node(op)
        for i, t in enumerate(inputs):
            src_node, src_idx = self._producer[t.guid]
            self.graph.add_edge(src_node, node, src_idx, i)
        outs = []
        for i, shape in enumerate(op.output_shapes):
            t = Tensor(shape.sizes, shape.dtype, owner_layer=node, owner_idx=i,
                       name=f"{op.name}:{i}")
            self._producer[t.guid] = (node, i)
            outs.append(t)
        return outs

    def _check_sharer(self, op: O.Operator) -> None:
        """A ``weights_of`` op reads leaves its owner declared: refuse
        at graph construction one that would declare other names,
        shapes or dtypes."""
        def leaves(specs):
            return [(w.name, w.shape, w.dtype) for w in specs]

        owner = next((n.op for n in self.graph.nodes.values()
                      if n.op.name == op.weights_key), None)
        if owner is None or not owner._weight_specs:
            raise ValueError(
                f"{op.name}: weights_of={op.weights_key!r} names no op "
                f"added before it that owns weights")
        if leaves(op.weight_specs()) != leaves(owner._weight_specs):
            raise ValueError(
                f"{op.name} cannot read the weights of {owner.name}: it "
                f"would declare {leaves(op.weight_specs())}, the owner "
                f"holds {leaves(owner._weight_specs)}")

    # ------------------------------------------------------------------
    def create_tensor(self, dims: Sequence[int], dtype="float32", name=None) -> Tensor:
        """Frontend input tensor (reference: FFModel::create_tensor)."""
        name = self._fresh_name("input", name)
        t = Tensor(dims, dtype, name=name)
        op = O.InputOp(name, ParallelTensorShape.make(t.sizes, t.dtype), tensor_guid=t.guid)
        node = self.graph.new_node(op)
        self._producer[t.guid] = (node, 0)
        self._input_tensors.append(t)
        return t

    def create_constant(self, value, dtype=None, name=None) -> Tensor:
        """Compile-time constant tensor (baked into the program; XLA
        folds it).  Serves imported frontend graphs whose buffers —
        position ids, token-type ids — are constants, a case the
        reference routes through host-initialized Legion regions."""
        arr = np.asarray(value)
        if dtype is not None:
            arr = arr.astype(DataType.from_any(dtype).to_numpy())
        name = self._fresh_name("constant", name)
        dt = str(arr.dtype)
        t = Tensor(list(arr.shape), dt, name=name)
        op = O.ConstantOp(
            name, ParallelTensorShape.make(t.sizes, t.dtype), value=arr
        )
        node = self.graph.new_node(op)
        self._producer[t.guid] = (node, 0)
        return t

    # ---- layers (reference: model.h layer-method block) ----------------
    def dense(self, input: Tensor, out_dim: int, activation=None, use_bias=True,
              kernel_initializer=None, bias_initializer=None, name=None,
              weights_of: Optional[str] = None) -> Tensor:
        """``weights_of`` names another dense layer whose kernel (and
        bias) this one reads instead of owning its own."""
        op = O.LinearOp(self._fresh_name("dense", name), [self._shape_of(input)],
                        out_dim=out_dim, activation=activation, use_bias=use_bias,
                        kernel_initializer=kernel_initializer,
                        bias_initializer=bias_initializer,
                        param_dtype=self.config.param_dtype,
                        weights_of=weights_of)
        return self._add_op(op, [input])[0]

    def conv2d(self, input: Tensor, out_channels: int, kernel_h: int, kernel_w: int,
               stride_h: int = 1, stride_w: int = 1, padding_h: int = 0,
               padding_w: int = 0, activation=None, groups: int = 1, use_bias=True,
               kernel_initializer=None, bias_initializer=None, name=None) -> Tensor:
        op = O.Conv2DOp(self._fresh_name("conv2d", name), [self._shape_of(input)],
                        out_channels=out_channels, kernel_h=kernel_h, kernel_w=kernel_w,
                        stride_h=stride_h, stride_w=stride_w, padding_h=padding_h,
                        padding_w=padding_w, groups=groups, activation=activation,
                        use_bias=use_bias, kernel_initializer=kernel_initializer,
                        bias_initializer=bias_initializer)
        return self._add_op(op, [input])[0]

    def pool2d(self, input: Tensor, kernel_h: int, kernel_w: int, stride_h: int = 1,
               stride_w: int = 1, padding_h: int = 0, padding_w: int = 0,
               pool_type: str = "max", activation=None, name=None) -> Tensor:
        op = O.Pool2DOp(self._fresh_name("pool2d", name), [self._shape_of(input)],
                        kernel_h=kernel_h, kernel_w=kernel_w, stride_h=stride_h,
                        stride_w=stride_w, padding_h=padding_h, padding_w=padding_w,
                        pool_type=pool_type, activation=activation)
        return self._add_op(op, [input])[0]

    def batch_norm(self, input: Tensor, relu: bool = True, momentum: float = 0.9,
                   name=None) -> Tensor:
        op = O.BatchNormOp(self._fresh_name("batchnorm", name), [self._shape_of(input)],
                           relu=relu, momentum=momentum)
        return self._add_op(op, [input])[0]

    def layer_norm(self, input: Tensor, axes=(-1,), elementwise_affine=True,
                   eps=1e-5, name=None) -> Tensor:
        op = O.LayerNormOp(self._fresh_name("layernorm", name), [self._shape_of(input)],
                           axes=tuple(axes), elementwise_affine=elementwise_affine, eps=eps)
        return self._add_op(op, [input])[0]

    def rms_norm(self, input: Tensor, eps: float = 1e-6, name=None,
                 weights_of: Optional[str] = None) -> Tensor:
        op = O.RMSNormOp(self._fresh_name("rmsnorm", name),
                         [self._shape_of(input)], eps=eps,
                         weights_of=weights_of)
        return self._add_op(op, [input])[0]

    def embedding(self, input: Tensor, num_entries: int, out_dim: int,
                  aggr: str = "none", kernel_initializer=None, name=None,
                  weights_of: Optional[str] = None) -> Tensor:
        """``weights_of`` names another embedding whose table this one
        reads instead of owning its own."""
        op = O.EmbeddingOp(self._fresh_name("embedding", name), [self._shape_of(input)],
                           num_entries=num_entries, out_dim=out_dim, aggr=aggr,
                           kernel_initializer=kernel_initializer,
                           param_dtype=self.config.param_dtype,
                           weights_of=weights_of)
        return self._add_op(op, [input])[0]

    def latent_attention(self, input: Tensor, num_heads: int, q_lora_rank: int,
                         kv_lora_rank: int, qk_nope_head_dim: int,
                         qk_rope_head_dim: int, v_head_dim: int,
                         rope_theta: float = 10000.0, eps: float = 1e-6,
                         kernel_initializer=None, name=None) -> Tensor:
        op = O.LatentAttentionOp(
            self._fresh_name("latent_attention", name), [self._shape_of(input)],
            num_heads=num_heads, q_lora_rank=q_lora_rank,
            kv_lora_rank=kv_lora_rank, qk_nope_head_dim=qk_nope_head_dim,
            qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
            rope_theta=rope_theta, eps=eps,
            kernel_initializer=kernel_initializer)
        return self._add_op(op, [input])[0]

    def multihead_attention(self, query: Tensor, key: Tensor, value: Tensor,
                            embed_dim: int, num_heads: int, kdim: int = 0,
                            vdim: int = 0, dropout: float = 0.0, bias: bool = False,
                            causal: bool = False, sp_mode: str = "ring",
                            kernel_initializer=None,
                            name=None, rope_theta: Optional[float] = None,
                            weights_of: Optional[str] = None) -> Tensor:
        """``rope_theta`` turns the q and k heads by their positions
        (half-split rotary) before attention; ``weights_of`` names
        another attention op whose projections this one reads."""
        op = O.MultiHeadAttentionOp(
            self._fresh_name("attention", name),
            [self._shape_of(query), self._shape_of(key), self._shape_of(value)],
            embed_dim=embed_dim, num_heads=num_heads, kdim=kdim, vdim=vdim,
            dropout=dropout, use_bias=bias, causal=causal, sp_mode=sp_mode,
            kernel_initializer=kernel_initializer, rope_theta=rope_theta,
            weights_of=weights_of)
        return self._add_op(op, [query, key, value])[0]

    def decode_attention(self, hidden: Tensor, page_table: Tensor,
                         seq_lens: Tensor, embed_dim: int, num_heads: int,
                         page_size: int = 16, pages_per_seq: int = 8,
                         num_pages: int = 0, use_kernel: bool = True,
                         kernel_initializer=None, name=None) -> Tensor:
        """Single-token decode attention over this layer's paged KV
        cache (ops/decode_attention.py — the serving-side sibling of
        multihead_attention; no reference equivalent)."""
        op = O.DecodeAttentionOp(
            self._fresh_name("decode_attention", name),
            [self._shape_of(hidden), self._shape_of(page_table),
             self._shape_of(seq_lens)],
            embed_dim=embed_dim, num_heads=num_heads, page_size=page_size,
            pages_per_seq=pages_per_seq, num_pages=num_pages,
            use_kernel=use_kernel, kernel_initializer=kernel_initializer)
        return self._add_op(op, [hidden, page_table, seq_lens])[0]

    def grouped_decode_attention(self, hidden: Tensor, page_table: Tensor,
                                 seq_lens: Tensor, num_heads: int,
                                 num_kv_heads: int, head_dim: int,
                                 page_size: int = 16, pages_per_seq: int = 8,
                                 num_pages: int = 0, window: int = 0,
                                 ring_pages: int = 0,
                                 rope_theta: Optional[float] = None,
                                 qk_norm_eps: Optional[float] = None,
                                 gated: bool = False, use_kernel: bool = True,
                                 kv_dtype: str = "fp32",
                                 kernel_initializer=None,
                                 qk_norm_initializer=None,
                                 name=None) -> Tensor:
        """Decode attention with grouped query heads, per-head q/k norms,
        rotary, a sliding window over a ring of pages and an output gate,
        each by argument (ops/decode_attention.py
        ``GroupedDecodeAttentionOp``); projections in
        ``config.param_dtype``."""
        op = O.GroupedDecodeAttentionOp(
            self._fresh_name("decode_attention", name),
            [self._shape_of(hidden), self._shape_of(page_table),
             self._shape_of(seq_lens)],
            num_heads=num_heads, num_kv_heads=num_kv_heads,
            head_dim=head_dim, page_size=page_size,
            pages_per_seq=pages_per_seq, num_pages=num_pages, window=window,
            ring_pages=ring_pages, rope_theta=rope_theta,
            qk_norm_eps=qk_norm_eps, gated=gated, use_kernel=use_kernel,
            kv_dtype=kv_dtype, param_dtype=self.config.param_dtype,
            kernel_initializer=kernel_initializer,
            qk_norm_initializer=qk_norm_initializer)
        return self._add_op(op, [hidden, page_table, seq_lens])[0]

    def sparse_decode_attention(self, hidden: Tensor, page_table: Tensor,
                                seq_lens: Tensor, num_heads: int,
                                num_kv_heads: int, head_dim: int,
                                index_heads: int, index_head_dim: int,
                                index_topk: int,
                                index_rope_dim: Optional[int] = None,
                                index_block: int = 512,
                                index_norm_eps: float = 1e-6,
                                page_size: int = 16, pages_per_seq: int = 8,
                                num_pages: int = 0,
                                rope_theta: Optional[float] = None,
                                qk_norm_eps: Optional[float] = None,
                                kv_dtype: str = "bf16",
                                kernel_initializer=None,
                                name=None) -> Tensor:
        """Grouped decode attention behind a learned indexer that chooses
        ``index_topk`` cached positions a query (ops/decode_attention.py
        ``SparseDecodeAttentionOp``); projections in
        ``config.param_dtype``."""
        op = O.SparseDecodeAttentionOp(
            self._fresh_name("decode_attention", name),
            [self._shape_of(hidden), self._shape_of(page_table),
             self._shape_of(seq_lens)],
            num_heads=num_heads, num_kv_heads=num_kv_heads,
            head_dim=head_dim, index_heads=index_heads,
            index_head_dim=index_head_dim, index_topk=index_topk,
            index_rope_dim=index_rope_dim, index_block=index_block,
            index_norm_eps=index_norm_eps, page_size=page_size,
            pages_per_seq=pages_per_seq, num_pages=num_pages,
            rope_theta=rope_theta, qk_norm_eps=qk_norm_eps,
            kv_dtype=kv_dtype, param_dtype=self.config.param_dtype,
            kernel_initializer=kernel_initializer)
        return self._add_op(op, [hidden, page_table, seq_lens])[0]

    def batch_matmul(self, A: Tensor, B: Tensor, a_seq_length_dim: int = -1,
                     b_seq_length_dim: int = -1, name=None) -> Tensor:
        op = O.BatchMatmulOp(self._fresh_name("bmm", name),
                             [self._shape_of(A), self._shape_of(B)],
                             a_seq_length_dim=a_seq_length_dim,
                             b_seq_length_dim=b_seq_length_dim)
        return self._add_op(op, [A, B])[0]

    def dropout(self, input: Tensor, rate: float = 0.5, seed: int = 0, name=None) -> Tensor:
        op = O.DropoutOp(self._fresh_name("dropout", name), [self._shape_of(input)],
                         rate=rate, seed=seed)
        return self._add_op(op, [input])[0]

    def softmax(self, input: Tensor, axis: int = -1, name=None) -> Tensor:
        op = O.SoftmaxOp(self._fresh_name("softmax", name), [self._shape_of(input)], axis=axis)
        return self._add_op(op, [input])[0]

    def concat(self, tensors: Sequence[Tensor], axis: int, name=None) -> Tensor:
        op = O.ConcatOp(self._fresh_name("concat", name),
                        [self._shape_of(t) for t in tensors], axis=axis)
        return self._add_op(op, list(tensors))[0]

    def split(self, input: Tensor, sizes: Union[int, Sequence[int]], axis: int,
              name=None) -> List[Tensor]:
        if isinstance(sizes, int):
            total = input.sizes[axis]
            assert total % sizes == 0
            sizes = [total // sizes] * sizes
        op = O.SplitOp(self._fresh_name("split", name), [self._shape_of(input)],
                       sizes=tuple(sizes), axis=axis)
        return self._add_op(op, [input])

    def flat(self, input: Tensor, name=None) -> Tensor:
        op = O.FlatOp(self._fresh_name("flat", name), [self._shape_of(input)])
        return self._add_op(op, [input])[0]

    def reshape(self, input: Tensor, shape: Sequence[int], name=None) -> Tensor:
        op = O.ReshapeOp(self._fresh_name("reshape", name), [self._shape_of(input)],
                         shape=tuple(shape))
        return self._add_op(op, [input])[0]

    def transpose(self, input: Tensor, perm: Sequence[int], name=None) -> Tensor:
        op = O.TransposeOp(self._fresh_name("transpose", name), [self._shape_of(input)],
                           perm=tuple(perm))
        return self._add_op(op, [input])[0]

    def reverse(self, input: Tensor, axis: int, name=None) -> Tensor:
        op = O.ReverseOp(self._fresh_name("reverse", name), [self._shape_of(input)], axis=axis)
        return self._add_op(op, [input])[0]

    def cast(self, input: Tensor, dtype, name=None) -> Tensor:
        op = O.CastOp(self._fresh_name("cast", name), [self._shape_of(input)], dtype=dtype)
        return self._add_op(op, [input])[0]

    def mean(self, input: Tensor, dims: Sequence[int], keepdims: bool = False,
             name=None) -> Tensor:
        op = O.MeanOp(self._fresh_name("mean", name), [self._shape_of(input)],
                      dims=tuple(dims), keepdims=keepdims)
        return self._add_op(op, [input])[0]

    def top_k(self, input: Tensor, k: int, sorted: bool = True, name=None) -> Tuple[Tensor, Tensor]:
        op = O.TopKOp(self._fresh_name("topk", name), [self._shape_of(input)], k=k, sorted=sorted)
        outs = self._add_op(op, [input])
        return outs[0], outs[1]

    def gather(self, input: Tensor, indices: Tensor, axis: int = 0, name=None) -> Tensor:
        op = O.GatherOp(self._fresh_name("gather", name),
                        [self._shape_of(input), self._shape_of(indices)], axis=axis)
        return self._add_op(op, [input, indices])[0]

    def group_by(self, data: Tensor, assign: Tensor, n_experts: int, alpha: float = 1.0,
                 name=None) -> List[Tensor]:
        op = O.GroupByOp(self._fresh_name("group_by", name),
                         [self._shape_of(data), self._shape_of(assign)],
                         n_experts=n_experts, alpha=alpha)
        return self._add_op(op, [data, assign])

    def aggregate(self, gates: Tensor, expert_idx: Tensor, pos: Tensor, valid: Tensor,
                  expert_out: Tensor, lambda_bal: float = 0.0, name=None) -> Tensor:
        op = O.AggregateOp(
            self._fresh_name("aggregate", name),
            [self._shape_of(t) for t in (gates, expert_idx, pos, valid, expert_out)],
            lambda_bal=lambda_bal)
        return self._add_op(op, [gates, expert_idx, pos, valid, expert_out])[0]

    def aggregate_spec(self, gates, expert_idx, pos, valid, expert_out,
                       lambda_bal: float = 0.0, name=None) -> Tensor:
        op = O.AggregateSpecOp(
            self._fresh_name("aggregate_spec", name),
            [self._shape_of(t) for t in (gates, expert_idx, pos, valid, expert_out)],
            lambda_bal=lambda_bal)
        return self._add_op(op, [gates, expert_idx, pos, valid, expert_out])[0]

    # an expert layer that is told which experts it holds (ops/moe.py)
    def moe_router(self, input: Tensor, n_experts: int, k: int,
                   scale: float = 1.0, experts_held: Optional[int] = None,
                   kernel_initializer=None, score: str = "sigmoid",
                   name=None) -> Tuple[Tensor, Tensor]:
        """(weights, experts) of ``ops/moe.py MoERouterOp``; ``score``
        ``"sigmoid"`` (bias state, ``scale``) or ``"softmax"`` (the
        chosen probabilities renormalised)."""
        op = O.MoERouterOp(self._fresh_name("moe_router", name),
                           [self._shape_of(input)], n_experts=n_experts, k=k,
                           scale=scale, experts_held=experts_held,
                           kernel_initializer=kernel_initializer, score=score)
        outs = self._add_op(op, [input])
        return outs[0], outs[1]

    def expert_dispatch(self, data: Tensor, experts: Tensor, n_experts: int,
                        experts_held: int, rows: int, expert_offset: int = 0,
                        name=None) -> Tuple[Tensor, Tensor, Tensor]:
        op = O.ExpertDispatchOp(
            self._fresh_name("expert_dispatch", name),
            [self._shape_of(data), self._shape_of(experts)],
            n_experts=n_experts, experts_held=experts_held, rows=rows,
            expert_offset=expert_offset)
        outs = self._add_op(op, [data, experts])
        return outs[0], outs[1], outs[2]

    def expert_linear(self, input: Tensor, out_dim: int, activation=None,
                      use_bias: bool = False, sizes: Optional[Tensor] = None,
                      kernel_initializer=None, name=None) -> Tensor:
        """``input`` [E, rows, D], or [rows, D] sorted by expert with the
        experts' ``sizes`` [E] (``expert_dispatch``'s)."""
        inputs = [input] if sizes is None else [input, sizes]
        op = O.ExpertLinearOp(self._fresh_name("expert_linear", name),
                              [self._shape_of(t) for t in inputs],
                              out_dim=out_dim, activation=activation,
                              use_bias=use_bias,
                              kernel_initializer=kernel_initializer,
                              param_dtype=self.config.param_dtype)
        return self._add_op(op, inputs)[0]

    def expert_combine(self, weights: Tensor, source: Tensor,
                       expert_out: Tensor, name=None) -> Tensor:
        op = O.ExpertCombineOp(
            self._fresh_name("expert_combine", name),
            [self._shape_of(t) for t in (weights, source, expert_out)])
        return self._add_op(op, [weights, source, expert_out])[0]

    # multi-token prediction (ops/mtp.py)
    def shift(self, input: Tensor, by: int = 1, name=None) -> Tensor:
        op = O.ShiftOp(self._fresh_name("shift", name),
                       [self._shape_of(input)], by=by)
        return self._add_op(op, [input])[0]

    def next_token_loss(self, logits: Tensor, ahead_logits: Tensor,
                        ids: Tensor, shift: int = 2, weight: float = 0.3,
                        name=None) -> Tensor:
        op = O.NextTokenLossOp(
            self._fresh_name("next_token_loss", name),
            [self._shape_of(t) for t in (logits, ahead_logits, ids)],
            shift=shift, weight=weight)
        return self._add_op(op, [logits, ahead_logits, ids])[0]

    def exit_loss(self, logits: Sequence[Tensor], gates: Sequence[Tensor],
                  ids: Tensor, beta: float = 0.1, name=None) -> Tensor:
        """The objective of a model with one exit a loop step
        (ops/exit_loss.py): the graph's sink, which hands the last
        exit's logits through."""
        op = O.ExitLossOp(
            self._fresh_name("exit_loss", name),
            [self._shape_of(t) for t in (*logits, *gates, ids)], beta=beta)
        return self._add_op(op, [*logits, *gates, ids])[0]

    def cache(self, input: Tensor, use_cached: bool = False, name=None) -> Tensor:
        op = O.CacheOp(self._fresh_name("cache", name), [self._shape_of(input)],
                       use_cached=use_cached)
        return self._add_op(op, [input])[0]

    # parallel ops (reference: src/parallel_ops/*; inserted by the search
    # or placed manually for hand-written strategies) -------------------
    def repartition(self, input: Tensor, dim: int, degree: int, name=None) -> Tensor:
        from flexflow_tpu.parallel.parallel_ops import RepartitionOp

        op = RepartitionOp(self._fresh_name("repartition", name),
                           [self._shape_of(input)], dim=dim, degree=degree)
        return self._add_op(op, [input])[0]

    def combine(self, input: Tensor, dim: int, degree: int = 1, name=None) -> Tensor:
        from flexflow_tpu.parallel.parallel_ops import CombineOp

        op = CombineOp(self._fresh_name("combine", name),
                       [self._shape_of(input)], dim=dim, degree=degree)
        return self._add_op(op, [input])[0]

    def replicate(self, input: Tensor, degree: int, name=None) -> Tensor:
        from flexflow_tpu.parallel.parallel_ops import ReplicateOp

        op = ReplicateOp(self._fresh_name("replicate", name),
                         [self._shape_of(input)], degree=degree)
        return self._add_op(op, [input])[0]

    def reduction(self, input: Tensor, degree: int, name=None) -> Tensor:
        from flexflow_tpu.parallel.parallel_ops import ReductionOp

        op = ReductionOp(self._fresh_name("reduction", name),
                         [self._shape_of(input)], degree=degree)
        return self._add_op(op, [input])[0]

    def node_by_name(self, name: str) -> Node:
        for node in self.graph.nodes.values():
            if node.op.name == name:
                return node
        raise KeyError(name)

    # elementwise -------------------------------------------------------
    def _unary(self, t: OperatorType, input: Tensor, name=None, scalar=0.0,
               base=None, approximate=True):
        op = O.ElementUnaryOp(self._fresh_name(base or t.value, name),
                              [self._shape_of(input)], unary_type=t,
                              scalar=scalar, approximate=approximate)
        return self._add_op(op, [input])[0]

    def _binary(self, t: OperatorType, a: Tensor, b: Tensor, name=None):
        op = O.ElementBinaryOp(self._fresh_name(t.value, name),
                               [self._shape_of(a), self._shape_of(b)], binary_type=t)
        return self._add_op(op, [a, b])[0]

    def relu(self, x, name=None):
        return self._unary(OperatorType.RELU, x, name)

    def sigmoid(self, x, name=None):
        return self._unary(OperatorType.SIGMOID, x, name)

    def tanh(self, x, name=None):
        return self._unary(OperatorType.TANH, x, name)

    def elu(self, x, name=None):
        return self._unary(OperatorType.ELU, x, name)

    def gelu(self, x, name=None, approximate=True):
        """tanh-approximate by default (the TPU-friendly form); pass
        approximate=False for the exact erf GELU that tf.keras and
        torch default to."""
        return self._unary(OperatorType.GELU, x, name, approximate=approximate)

    def exp(self, x, name=None):
        return self._unary(OperatorType.EXP, x, name)

    def log(self, x, name=None):
        return self._unary(OperatorType.LOG, x, name)

    def identity(self, x, name=None):
        return self._unary(OperatorType.IDENTITY, x, name)

    def rsqrt(self, x, name=None):
        return self._unary(OperatorType.RSQRT, x, name)

    def pow(self, x, exponent: float, name=None):
        return self._unary(OperatorType.POW, x, name, scalar=exponent)

    def scalar_add(self, x, scalar: float, name=None):
        return self._unary(OperatorType.SCALAR_ADD, x, name, scalar=scalar)

    def scalar_sub(self, x, scalar: float, name=None):
        return self._unary(OperatorType.SCALAR_SUB, x, name, scalar=scalar)

    def scalar_multiply(self, x, scalar: float, name=None):
        return self._unary(OperatorType.SCALAR_MUL, x, name, scalar=scalar)

    def scalar_true_divide(self, x, scalar: float, name=None):
        return self._unary(OperatorType.SCALAR_TRUE_DIV, x, name, scalar=scalar)

    def add(self, a, b, name=None):
        return self._binary(OperatorType.EW_ADD, a, b, name)

    def subtract(self, a, b, name=None):
        return self._binary(OperatorType.EW_SUB, a, b, name)

    def multiply(self, a, b, name=None):
        return self._binary(OperatorType.EW_MUL, a, b, name)

    def divide(self, a, b, name=None):
        return self._binary(OperatorType.EW_DIV, a, b, name)

    def max(self, a, b, name=None):
        return self._binary(OperatorType.EW_MAX, a, b, name)

    def min(self, a, b, name=None):
        return self._binary(OperatorType.EW_MIN, a, b, name)

    # ------------------------------------------------------------------
    # the plan's dimensions, under the names tests and tools read
    @property
    def graph(self) -> Graph:
        return self.plan.graph

    @graph.setter
    def graph(self, graph: Graph) -> None:
        self.plan.graph = graph

    @property
    def strategy(self):
        return self.plan.strategy

    @property
    def pipeline_proposal(self):
        return self.plan.staged

    @property
    def sync_precision_map(self) -> Dict[str, str]:
        return self.plan.sync_precision

    @property
    def sync_schedule(self):
        return self.plan.sync_schedule

    @property
    def zero_groups(self) -> tuple:
        return self.plan.zero_groups

    @property
    def disaggregation(self):
        # the live searched proposal; an imported block is provenance
        # the import re-linted, nothing the runtime can act on
        p = self.plan.disaggregation
        return p if hasattr(p, "adopted") else None

    @property
    def fleet(self):
        p = self.plan.fleet
        return p if hasattr(p, "adopted") else None

    @property
    def fleet_base_graph(self):
        return self.plan.base_graph

    # ------------------------------------------------------------------
    def compile(
        self,
        optimizer: Optional[Optimizer] = None,
        loss_type="sparse_categorical_crossentropy",
        metrics=("accuracy",),
        comp_mode: str = "training",
        strategy: Optional[Dict[int, MachineView]] = None,
        pipeline=None,
        block_of: Optional[Dict[int, int]] = None,
        mesh=None,
    ):
        """Pick a parallelization strategy and lower
        (reference: FFModel::compile model.cc:2587).  ``pipeline`` — a
        flexflow_tpu.parallel.pipeline.PipelineConfig enables the
        S-stage microbatched pipeline over a ``pp`` mesh axis (a
        capability the reference only stubbed: OP_PIPELINE,
        ffconst.h:148).

        A pipeline over one record (search/plan.py StrategyPlan): a
        SOURCE yields the plan (caller-supplied / imported / data-
        parallel / searched), the post-search proposals and the comm
        plan EXTEND it, it is EXPORTED, the KV dtype it chose is
        adopted, ``compiler.lower`` picks the executor, and what that
        executor cannot run is dropped from the record."""
        from flexflow_tpu.compiler.lower import lower
        from flexflow_tpu.obs.annotate import PHASE_PREFIX, phase_span
        from flexflow_tpu.runtime.compile_cache import watch_jax_compiles

        watch_jax_compiles()
        if comp_mode not in ("training", "inference"):
            raise ValueError(
                f"comp_mode must be 'training' or 'inference', got {comp_mode!r}"
            )
        cfg = self.config
        cfg.comp_mode = comp_mode
        if cfg.verify:
            # prove the frontend-built graph well-formed before anything
            # consumes it (flexflow_tpu/analysis).  The per-rewrite hook
            # inside the search is armed by search_plan's own
            # scoped_verify — config.verify never becomes a sticky
            # process-wide latch.
            from flexflow_tpu.analysis import assert_graph_ok

            assert_graph_ok(self.graph, context="at compile entry")
        from flexflow_tpu.obs.events import BUS as _obs_bus

        if cfg.obs_log_file:
            # FFConfig-gated unified telemetry (flexflow_tpu/obs): the
            # search, compile, and fit paths below all emit through the
            # same bus once it is armed
            _obs_bus.configure(cfg.obs_log_file)
        self.optimizer = optimizer or SGDOptimizer(
            lr=cfg.learning_rate, weight_decay=cfg.weight_decay
        )
        if pipeline is not None and (
            pipeline.num_stages < 1
            or cfg.num_devices % pipeline.num_stages != 0
        ):
            raise ValueError(
                f"pipeline.num_stages={pipeline.num_stages} must divide "
                f"num_devices={cfg.num_devices}"
            )
        if pipeline is not None and mesh is not None:
            raise ValueError(
                "mesh= is not supported with pipeline= (the pipelined "
                "lowering builds its own pp-leading mesh)"
            )
        if pipeline is not None and cfg.zero_dp_shard:
            raise NotImplementedError(
                "zero_dp_shard is not supported with pipeline= yet — the "
                "pipelined lowering manages its own per-stage placement; "
                "silently ignoring the flag would leave optimizer state "
                "replicated while the user expects 1/N memory"
            )
        # source: a fresh record each compile — a stale proposal from
        # an earlier compile must not hijack this one's lowering
        plan = self.plan = self._source_plan(strategy, pipeline)
        # the strategy object the search's own gates ran against — a
        # pipeline/placement proposal below may REPLACE plan.strategy,
        # and the gated schedule and zero map must not follow it onto
        # a strategy they were never linted for
        searched = plan.source == "searched"
        gated = plan.strategy if searched else None
        if searched:
            self._propose(plan, mesh)
        plan.tie_views()
        self._plan_comm(plan, gated)
        # predicted step breakdown + strategy-explanation telemetry —
        # the predicted half of the DriftReport fit() completes.  Only
        # computed when something will consume it (profiling, the obs
        # bus, a strategy/trace export): one extra simulate per compile
        # is cheap but not free.
        self.predicted_breakdown = None
        self.drift_report = None
        self.lane_drift_report = None  # filled by fit's device-trace
        # capture (config.device_trace_dir) via obs/trace_ingest.py
        pred_cal = None  # the coherent table the prediction was priced
        # under — the export digests THIS object (STR210) instead of
        # re-parsing the file a second time
        if (
            plan.strategy
            and plan.pipeline is None
            and plan.staged is None
            and (
                cfg.profiling
                or _obs_bus.enabled
                or cfg.export_strategy_file
                or cfg.obs_trace_file
                # a calibrated compile must ALWAYS record its prediction:
                # the drift/healthy-reset loop (fit tail, re-probe
                # allowance) closes on it even when neither profiling nor
                # the obs bus is armed — without this, the allowance
                # reset rode the drift-report path only
                or cfg.calibration_file
            )
        ):
            pred_cal = self._predict(plan, full=True)
        if cfg.export_strategy_file:
            self._export_plan(plan, pred_cal, mesh)
        if cfg.export_strategy_computation_graph_file:
            self.graph.write_dot(
                cfg.export_strategy_computation_graph_file, plan.strategy
            )
        if cfg.export_strategy_task_graph_file:
            from flexflow_tpu.search.simulator import Simulator

            # for_config: search_devices + comp_mode/zero flags match
            # what the search itself costed
            Simulator.for_config(cfg).export_task_graph_dot(
                self.graph, plan.strategy, cfg.export_strategy_task_graph_file
            )
        # KV-lane adoption (searched or imported __meta__.kv, both
        # SHD168/169-gated): the decode ops take the chosen pool dtype
        # NOW — after every export computed its digests against the
        # attr-free graph (so the import-side digest gate still passes
        # and the kv block re-lints there), before any lowering builds
        # state
        _adopt_kv_dtype(self.graph, (plan.kv or {}).get("dtype"))
        self._lower_args = dict(
            loss=LossType.from_any(loss_type), metrics=list(metrics),
            optimizer=self.optimizer, mesh=mesh, block_of=block_of)
        with phase_span(PHASE_PREFIX + "setup.lower"):
            self.compiled = lower(plan, cfg, **self._lower_args)
        plan.drop_unexecutable(self.compiled)
        with phase_span(PHASE_PREFIX + "setup.init_params"):
            self.params, self.state = self.compiled.init_params(cfg.seed)
            self._obs_seen = {}  # the device counters start at 0 again
            self.opt_state = self.optimizer.init_state(self.params)
            self.opt_state = self.compiled.shard_opt_state(self.opt_state)
        return self.compiled

    def _source_plan(self, strategy, pipeline):
        """The plan as its source yields it: the caller's own
        ``strategy=`` / ``pipeline=``, an imported strategy file,
        forced data parallelism, or the search."""
        from flexflow_tpu.compiler.lowering import data_parallel_strategy
        from flexflow_tpu.search.plan import STRATEGY_DIMS, StrategyPlan

        cfg = self.config
        if strategy is not None:
            return StrategyPlan(self.graph, strategy, "caller",
                                pipeline=pipeline)
        if pipeline is not None:
            # dp over the devices left after the pp axis is carved off
            return StrategyPlan(
                self.graph,
                data_parallel_strategy(
                    self.graph, cfg.num_devices // pipeline.num_stages),
                "data_parallel", pipeline=pipeline)
        if cfg.import_strategy_file:
            from flexflow_tpu.analysis import AnalysisError
            from flexflow_tpu.search.strategy_io import (
                import_strategy,
                read_meta,
            )

            # an imported strategy bypasses the search's always-on
            # gate — provenance is checked by import_strategy and every
            # dimension the file carries is re-linted, so an illegal
            # file fails at compile with a finding, not inside XLA
            try:
                strategy = import_strategy(
                    cfg.import_strategy_file, self.graph,
                    allow_partial=cfg.import_strategy_partial)
            except AnalysisError as e:
                err = AnalysisError(
                    f"{e}\n(hint: a strategy exported after a "
                    f"REWRITING search is keyed to the rewritten "
                    f"graph and cannot re-apply to a fresh frontend "
                    f"build — use the persistent cost cache "
                    f"(--cost-cache-file) for cross-process reuse of "
                    f"rewritten searches, or "
                    f"--import-strategy-partial / "
                    f"FFConfig.import_strategy_partial for a "
                    f"best-effort partial apply)")
                err.findings = list(e.findings)
                raise err from e
            plan = StrategyPlan.from_meta(
                read_meta(cfg.import_strategy_file), self.graph, strategy,
                cfg)
            plan.relint(cfg, STRATEGY_DIMS)
            return plan
        if cfg.only_data_parallel:
            return StrategyPlan(
                self.graph,
                data_parallel_strategy(self.graph, cfg.num_devices),
                "data_parallel")
        # the Unity joint search IS the default compile path
        # (reference: FFModel::compile -> graph_optimize,
        # model.cc:2587-2655): graph rewrites compete with view
        # assignment and the best REWRITTEN graph gets lowered —
        # the model's graph is replaced the same way the reference
        # deserializes the optimized PCG into its operator list
        # (convert_graph_to_operators, substitution.cc:3014)
        from flexflow_tpu import native as _native
        from flexflow_tpu.obs.annotate import PHASE_PREFIX, phase_span
        from flexflow_tpu.search.driver import search_plan

        # the search loads the native engine on first use, and
        # builds it (make) in a fresh checkout: its own span
        with phase_span(PHASE_PREFIX + "setup.native_build"):
            _native.get_lib()
        with phase_span(PHASE_PREFIX + "setup.search"):
            plan = search_plan(self.graph, cfg)
        if plan.graph is not self.graph:
            plan.base_graph = self.graph
        return plan

    def _propose(self, plan, mesh) -> None:
        """Extend a SEARCHED plan with the proposals the flat search
        does not cost: a pipelined, a placed or a staged candidate
        (training), a prefill/decode disaggregation and a serving
        fleet (serve objective).  Each was legality-gated where it was
        proposed."""
        from flexflow_tpu.search.driver import coherent_calibration

        cfg = self.config
        # the search also costs pipelined candidates for stacked-block
        # graphs (reference gap: OP_PIPELINE is an enum stub,
        # ffconst.h:148) — a winning PipelineConfig is adopted exactly
        # as if the user had passed it
        if (
            mesh is None
            and (cfg.enable_pipeline_search or cfg.enable_placement_search)
            and not cfg.zero_dp_shard
            and cfg.comp_mode == "training"
        ):
            from flexflow_tpu.compiler.lowering import data_parallel_strategy
            from flexflow_tpu.search.pipeline_search import (
                propose_pipeline,
                propose_pipeline_general,
            )
            from flexflow_tpu.search.simulator import Simulator

            # same cost currency as the flat search that just ran:
            # measured calibration included when coherent
            sim = Simulator.for_config(
                cfg, calibration=coherent_calibration(cfg))
            baseline = sim.simulate(plan.graph, plan.strategy)
            prop = (propose_pipeline(plan.graph, cfg, sim, baseline)
                    if cfg.enable_pipeline_search else None)
            if prop is not None and (
                cfg.num_devices % prop.num_stages == 0
                and cfg.batch_size % prop.num_microbatches == 0
            ):
                plan.pipeline = prop
                plan.strategy = data_parallel_strategy(
                    plan.graph, cfg.num_devices // prop.num_stages)
            elif cfg.enable_placement_search:
                # no pipeline won: cost 2-block inter-op placed
                # candidates in the placed executor's schedule
                # (reference: VERTICAL splits + mapper placement,
                # graph.cc:161-295, mapper.cc:371-475); a
                # margin-beating placeable winner replaces the flat
                # strategy and lowers via the placed path
                from flexflow_tpu.search.placement_search import (
                    propose_placement,
                )

                placed = propose_placement(
                    plan.graph, cfg, baseline,
                    calibration=coherent_calibration(cfg))
                if placed is not None:
                    plan.strategy = placed
                elif not _math.isfinite(baseline):
                    # nothing executable fits: cost the GENERAL
                    # staged-pipeline shape (any graph cut, reference
                    # graph.cc:161-295); a winning proposal lowers via
                    # the heterogeneous staged executor
                    # (compiler/staged_pipeline_lowering.py)
                    plan.staged = propose_pipeline_general(
                        plan.graph, cfg, sim, baseline)
                    if plan.staged is not None:
                        from flexflow_tpu.utils.logging import SEARCH_LOG

                        SEARCH_LOG.log(
                            f"staged-pipeline candidate: S="
                            f"{plan.staged.num_stages} M="
                            f"{plan.staged.num_microbatches} modeled "
                            f"{plan.staged.cost * 1e3:.3f} ms/iter "
                            f"(flat is infeasible)"
                        )
        if not (
            plan.strategy
            and plan.pipeline is None
            and mesh is None
            and cfg.comp_mode == "inference"
            and getattr(cfg, "objective", "train") == "serve"
        ):
            return
        # prefill/decode disaggregation (search/disaggregation.py):
        # under the serve objective, also price placing the prompt
        # graph and this decode graph on disjoint submeshes — the
        # two-block placement with the KV handoff as a cross-block
        # transfer.  The proposal (adopted or honest zero) is public
        # state; adopted winners persist as __meta__.disaggregation.
        # Its narrow-block solves run on the PRE-search graph (rewrites
        # bake full-mesh repartition views narrow blocks can't host).
        if getattr(cfg, "serve_disaggregation", "off") == "search":
            from flexflow_tpu.search.disaggregation import (
                propose_disaggregation,
            )

            plan.disaggregation = propose_disaggregation(
                plan.graph, plan.strategy, cfg,
                calibration=coherent_calibration(cfg),
                base_graph=plan.base_graph)
        # serving fleet (search/fleet.py): also price partitioning the
        # mesh into N replica blocks with per-replica strategies and
        # per-SLO-class routing — the N-block generalization of the
        # disaggregation pass; the controller's elastic re-search
        # solves on the same pre-rewrite graph.  Adopted winners
        # persist as __meta__.fleet.
        if getattr(cfg, "serve_fleet", "off") == "search":
            from flexflow_tpu.search.fleet import propose_fleet

            plan.fleet = propose_fleet(
                plan.graph, plan.strategy, cfg,
                calibration=coherent_calibration(cfg),
                base_graph=plan.base_graph)

    def _plan_comm(self, plan, gated, sim=None) -> None:
        """The comm plan of the strategy actually being lowered.

        Sync precision (EQuARX compressed gradient collectives): the
        per-weight-group wire map, built with the SAME cost model the
        search ranked with, so execution runs exactly what the
        simulation priced.  Gradient-sync SCHEDULE
        (search/sync_schedule.py): bucketed, issue-ordered collectives
        the lowering executes inside the backward (comm/bucketed.py).
        Zero map (search/comm_plan.py): per-group optimizer-state
        sharding.  The joint search chose and legality-gated the last
        two for ITS result (``gated``), an import re-lints its file's;
        every other strategy — forced DP, caller-supplied, imported
        without a schedule, a searched one later REPLACED by a
        proposal — runs the same schedule choice + always-on gate here
        and takes no zero map.  ``sim`` — a shared simulator factory
        (one per compile or swap, not three)."""
        from flexflow_tpu.search import driver as _driver
        from flexflow_tpu.search.plan import COMM_DIMS

        cfg = self.config
        if sim is None:
            from flexflow_tpu.search.simulator import Simulator

            sim = functools.cache(lambda: Simulator.for_config(
                cfg, calibration=_driver.coherent_calibration(cfg)))
        plan.sync_precision = {}
        if (
            cfg.comp_mode == "training"
            and plan.strategy
            and getattr(cfg, "sync_precision", "fp32") != "fp32"
        ):
            from flexflow_tpu.search.sync_precision import (
                choose_sync_precision,
            )

            plan.sync_precision = choose_sync_precision(
                plan.graph, plan.strategy, sim().cost)
        sched_armed, zero_armed = plan.comm_plan_armed(cfg)
        own = gated is not None and plan.strategy is gated
        if plan.source == "imported":
            plan.relint(cfg, COMM_DIMS)
        elif not own:
            plan.sync_schedule, plan.zero_groups = None, ()
        if not sched_armed:
            plan.sync_schedule = None
        elif plan.sync_schedule is None and not own:
            plan.sync_schedule, _ = _driver._build_sync_schedule(
                plan.graph, plan.strategy, sim(), cfg)
        if not zero_armed:
            plan.zero_groups = ()

    def _predict(self, plan, sim=None, full: bool = False):
        """Simulate the plan into ``self.predicted_breakdown``;
        ``full`` also emits the strategy table and the predicted
        timeline.  Telemetry must never fail a compile or a swap.
        Returns the calibration table the prediction was priced under."""
        from flexflow_tpu.obs.events import BUS as _obs_bus

        cfg = self.config
        cal = None
        try:
            if sim is None:
                from flexflow_tpu.search.driver import coherent_calibration
                from flexflow_tpu.search.simulator import Simulator

                cal = coherent_calibration(cfg)
                psim = Simulator.for_config(cfg, calibration=cal)
            else:
                psim = sim()
            bd: Dict = {}
            timeline = dict(schedule=[], comm_schedule=[]) if full else {}
            psim.simulate(plan.graph, plan.strategy, breakdown=bd,
                          sync_schedule=plan.sync_schedule, **timeline)
            bd["calibrated"] = psim.cost.calibration is not None
            bd["machine"] = cfg.machine_spec.name
            self.predicted_breakdown = bd
            if full and _obs_bus.enabled:
                _obs_bus.emit(
                    "strategy.table",
                    rows=psim.strategy_table_rows(
                        plan.graph, plan.strategy, plan.sync_precision),
                    predicted_s=bd.get("total_s"),
                    devices=cfg.search_devices,
                    comp_mode=cfg.comp_mode,
                    # searched=False marks forced-DP / imported /
                    # caller-supplied strategies so report tooling can
                    # prefer the joint-search table when both were
                    # compiled in one run
                    searched=plan.source == "searched",
                )
            if full and cfg.obs_trace_file:
                psim.export_chrome_trace(
                    plan.graph, plan.strategy, cfg.obs_trace_file,
                    total_s=bd.get("total_s"), **timeline)
        except Exception:
            self.predicted_breakdown = None
        return cal

    def _export_plan(self, plan, pred_cal, mesh) -> None:
        """Write the strategy file: the views, the prediction, and the
        plan's blocks — all behind the graph-digest gate import
        enforces."""
        from flexflow_tpu.compiler.lower import placement_frame
        from flexflow_tpu.search.cost_cache import calibration_digest
        from flexflow_tpu.search.strategy_io import export_strategy

        cfg = self.config
        meta = {}
        if self.predicted_breakdown:
            meta["predicted"] = self.predicted_breakdown
        # the calibration signature the strategy was ranked under
        # (content digest of the coherent measured table): fflint
        # strategy compares it against the LIVE CALIBRATION.json
        # (STR210) so a re-probed table flags every strategy file it
        # orphans as stale.  The prediction already loaded the table;
        # digest that exact object — it is BOTH the cheaper path and
        # the honest one (the signature describes the table the
        # predicted numbers were priced under).
        if pred_cal is None and cfg.calibration_file:
            from flexflow_tpu.search.driver import coherent_calibration

            pred_cal = coherent_calibration(cfg)
        cal_sig = calibration_digest(pred_cal)
        if cal_sig is not None:
            meta["calibration_signature"] = cal_sig
        if plan.placement is None:
            plan.placement = placement_frame(plan, cfg, mesh)
        meta.update(plan.to_meta())
        export_strategy(cfg.export_strategy_file, plan.graph,
                        plan.strategy, meta=meta or None)

    def recompile(self):
        """Re-lower the (possibly altered) graph into a fresh XLA
        program under the SAME plan — a placed, pipelined or staged
        model re-lowers as what it was (``compiler.lower`` chooses
        from the record) — carrying params / optimizer state / model
        state over (reference: dynamic re-optimization,
        recompile_state.cc — ops altered in place; here the program is
        rebuilt instead)."""
        from flexflow_tpu.compiler.lower import lower

        self.compiled = lower(self.plan, self.config, **self._lower_args)
        old_params, old_state, old_opt = self.params, self.state, self.opt_state
        self.params, self.state = self.compiled.init_params(self.config.seed)
        # shape-checked carry-over: an alter() that changes a weight's
        # shape keeps the fresh init for that weight
        self.params = _merge_matching(self.params, old_params or {})
        self.state = _merge_matching(self.state, old_state or {})
        # optimizer state must match the NEW param tree structure; re-init
        # and carry over leaves whose key paths survived the alteration
        self.opt_state = self.optimizer.init_state(self.params)
        self.opt_state = _merge_matching(self.opt_state, old_opt)
        self.opt_state = self.compiled.shard_opt_state(self.opt_state)
        return self.compiled

    def swap_strategy(self, strategy: Dict[int, MachineView],
                      graph: Optional[Graph] = None, config=None) -> dict:
        """HOT-swap the parallelization strategy between training steps
        (the always-on loop's core mechanism, runtime/controller.py):
        the full live training state — params, optimizer slots, mutable
        op state including EF residuals and KV page pools — is
        checkpointed in memory, the model re-lowers under the new
        (graph, strategy), and every value is re-sharded live onto the
        new strategy's views (``jax.device_put`` onto the fresh
        shardings — a value-identity operation at fp32, test-enforced
        bit-exact).  ``config=`` additionally swaps the FFConfig, which
        is how elastic mesh-size changes (preemption / added capacity)
        re-home the state onto a different device set.

        Gated always-on by the swap-legality lint (analysis/swap.py,
        SHD170-172 + the flat SHD1xx strategy lint).  The searched comm
        plan is rebuilt for the new pair and must re-pass its own
        legality gates; when it does not, the swap falls back to the
        monolithic fp32 sync path instead of failing the run.  Returns
        ``{"fallback", "fresh", "dropped", "swap_seconds"}``."""
        assert self.compiled is not None, "compile() before swap_strategy"
        from flexflow_tpu.analysis import lint_swap, raise_if_errors
        from flexflow_tpu.compiler.pipeline_lowering import (
            PipelinedCompiledModel,
        )
        from flexflow_tpu.compiler.placement_lowering import (
            PlacedCompiledModel,
            placeable,
        )
        from flexflow_tpu.compiler.staged_pipeline_lowering import (
            StagedPipelinedModel,
        )
        from flexflow_tpu.runtime.checkpoint import snapshot_in_memory

        t0 = time.perf_counter()
        new_config = config if config is not None else self.config
        new_graph = graph if graph is not None else self.graph
        if (
            # gate on the lowering itself: a live inter-op placement
            # (or a pipelined/staged model) must never silently
            # re-lower FLAT mid-run — nor a flat one into a placement
            isinstance(self.compiled, (
                PlacedCompiledModel, PipelinedCompiledModel,
                StagedPipelinedModel))
            or self._lower_args["mesh"] is not None
            or placeable(new_graph, strategy, new_config)
        ):
            raise NotImplementedError(
                "swap_strategy supports the flat SPMD lowering only — "
                "placed/pipelined/staged/user-mesh models manage their "
                "own placement and cannot re-shard live state this way")
        raise_if_errors(
            lint_swap(self.graph, new_graph, strategy,
                      new_config.num_devices),
            "hot-swap target is illegal for the live training state")
        snap = snapshot_in_memory(self)
        rollback = dict(
            config=self.config, plan=self.plan, compiled=self.compiled,
            params=self.params, opt_state=self.opt_state, state=self.state,
        )
        try:
            return self._swap_strategy_inner(
                snap, new_config, new_graph, strategy, t0)
        except Exception:
            # a failed swap (e.g. an elastic GROW past the available
            # device count rejected by mesh construction, or a corrupt
            # cost cache) must leave the model exactly as it was — the
            # OLD program with the OLD state — never half-swapped with
            # config/graph describing a program that does not exist
            for k, v in rollback.items():
                setattr(self, k, v)
            raise

    def _swap_strategy_inner(self, snap, new_config, new_graph, strategy,
                             t0) -> dict:
        from flexflow_tpu.analysis import AnalysisError, errors_only
        from flexflow_tpu.compiler.lower import lower
        from flexflow_tpu.obs.events import BUS as _obs_bus
        from flexflow_tpu.runtime.checkpoint import restore_in_memory
        from flexflow_tpu.search.driver import coherent_calibration
        from flexflow_tpu.search.plan import StrategyPlan
        from flexflow_tpu.search.simulator import Simulator
        from flexflow_tpu.utils.logging import SEARCH_LOG

        old_zero = self.plan.zero_groups
        self.config = new_config
        plan = self.plan = StrategyPlan(new_graph, strategy, "caller")
        # ONE calibration load + at most one Simulator per swap (the
        # compile-path discipline): swap latency is a headline number
        sim = functools.cache(lambda: Simulator.for_config(
            new_config, calibration=coherent_calibration(new_config)))
        # rebuild the comm plan for the new pair.  Every piece re-runs
        # its always-on legality gate against what is ACTUALLY being
        # lowered; a searched plan that fails post-swap costs the run
        # its overlap/compression win, never its life — graceful
        # fallback to the monolithic fp32 sync path.
        fallback = False
        try:
            self._plan_comm(plan, None, sim)
            if (new_config.comp_mode == "training" and old_zero
                    and not new_config.zero_dp_shard):
                # the co-searched per-group optimizer-sharding map rides
                # along only while it still lints for the new pair —
                # remapping the per-group ZeRO shards is the restore's
                # job, keeping an illegal map is nobody's
                from flexflow_tpu.analysis import lint_zero_map
                from flexflow_tpu.search.machine_model import CostModel

                if not errors_only(lint_zero_map(
                        new_graph, strategy, sorted(old_zero),
                        CostModel(new_config.machine_spec,
                                  num_devices=new_config.search_devices))):
                    plan.zero_groups = tuple(old_zero)
        except AnalysisError as e:
            fallback = True
            plan.sync_precision, plan.sync_schedule = {}, None
            plan.zero_groups = ()
            SEARCH_LOG.log(
                f"hot swap: searched comm plan failed its legality gate "
                f"post-swap ({e}); falling back to the monolithic fp32 "
                f"sync path")
        self.compiled = lower(plan, new_config, **self._lower_args)
        self.params, self.state = self.compiled.init_params(new_config.seed)
        self.opt_state = self.optimizer.init_state(self.params)
        self.opt_state = self.compiled.shard_opt_state(self.opt_state)
        report = restore_in_memory(self, snap)
        if report["dropped"]:
            SEARCH_LOG.log(
                f"hot swap: {len(report['dropped'])} state entr(ies) "
                f"have no home under the new comm plan and were dropped "
                f"(e.g. {report['dropped'][:3]})")
        # refresh the predicted side of the drift loop for the NEW
        # strategy (same consumers and same never-fail rule as compile)
        if (new_config.profiling or _obs_bus.enabled
                or new_config.calibration_file):
            self._predict(plan, sim)
        report["fallback"] = fallback
        report["swap_seconds"] = time.perf_counter() - t0
        return report

    # ------------------------------------------------------------------
    def fit(self, x=None, y=None, batch_size: Optional[int] = None,
            epochs: Optional[int] = None, shuffle: bool = True, verbose: bool = True,
            callbacks: Sequence = (), recompile_state=None,
            validation_data=None, validation_split: float = 0.0,
            checkpoint_dir: Optional[str] = None, checkpoint_every: int = 1,
            resume: bool = False):
        """Training loop (reference: flexflow_cffi.py:1832 fit).

        ``callbacks`` follow the keras callback protocol (duck-typed:
        on_train_begin/end, on_epoch_begin, on_epoch_end(epoch, logs) —
        return False from on_epoch_end to stop early).

        ``recompile_state`` — a runtime.recompile.RecompileState checked
        once per iteration (reference: recompile_on_condition,
        model.cc:2273); its alter() may mutate op attrs, after which the
        model re-lowers with params/state carried over.

        ``validation_data=(vx, vy)`` — evaluated after each epoch;
        ``val_*`` keys join the epoch logs/history so callbacks can
        monitor them (keras semantics; the reference's keras frontend
        verifies metrics only on the training set, callbacks.py
        VerifyMetrics).  ``validation_split=f`` holds out the LAST
        fraction of (x, y) — taken before any shuffling, keras's exact
        split formula — as validation_data; mutually exclusive with it.

        ``checkpoint_dir`` — snapshot the full training state (params,
        optimizer state, rng counter) every ``checkpoint_every`` epochs;
        with ``resume=True`` training continues from the latest
        snapshot's next epoch.  Beyond the reference, which has no
        model checkpointing (SURVEY.md §5); runtime/checkpoint.py."""
        import jax

        from flexflow_tpu.runtime.dataloader import SingleDataLoader

        assert self.compiled is not None, "call compile() first"
        if self.config.comp_mode == "inference":
            raise RuntimeError(
                "model was compiled with comp_mode='inference' (forward-"
                "only strategy search, reference COMP_MODE_INFERENCE) — "
                "recompile with comp_mode='training' to fit()"
            )
        if validation_split:
            # keras semantics: the LAST fraction of the data (before any
            # shuffling) becomes the validation set
            if validation_data is not None:
                raise ValueError(
                    "pass either validation_data or validation_split, not both"
                )
            if not 0.0 < validation_split < 1.0:
                raise ValueError(f"validation_split={validation_split} not in (0, 1)")
            xs_all = x if isinstance(x, (list, tuple)) else [x]
            xs_all = [np.asarray(a) for a in xs_all]
            y_all = np.asarray(y)
            n_all = len(y_all)
            cut = int(n_all * (1.0 - validation_split))  # keras's exact formula
            if cut == n_all or cut == 0:
                raise ValueError(
                    f"validation_split={validation_split} of {n_all} samples "
                    "leaves an empty train or validation set"
                )
            validation_data = ([a[cut:] for a in xs_all]
                               if len(xs_all) > 1 else xs_all[0][cut:],
                               y_all[cut:])
            x = [a[:cut] for a in xs_all] if len(xs_all) > 1 else xs_all[0][:cut]
            y = y_all[:cut]
        if validation_data is not None:
            # fail BEFORE training, not after a wasted epoch
            if not isinstance(validation_data, (tuple, list)) or len(
                validation_data
            ) != 2:
                raise ValueError(
                    "validation_data must be an (x, y) pair "
                    "(sample weights are not supported)"
                )
            _vy = np.asarray(validation_data[1])
            _bs = batch_size or self.config.batch_size
            if len(_vy) < _bs:
                raise ValueError(
                    f"validation set ({len(_vy)} samples) is smaller than "
                    f"batch_size ({_bs}) — evaluate() runs full batches "
                    "only, so no validation metric could ever be computed"
                )
            if len(_vy) % _bs:
                print(
                    f"# warning: validation tail of {len(_vy) % _bs} samples "
                    f"(< batch_size {_bs}) is dropped each epoch"
                )
        ckpt_mgr = None
        start_epoch = 0
        if checkpoint_dir is not None:
            # multi-process runs go down CheckpointManager's coordinated
            # orbax multihost path (every process calls save/restore on
            # the same directory; orbax synchronizes the shard writes)
            from flexflow_tpu.runtime.checkpoint import CheckpointManager

            ckpt_mgr = CheckpointManager(checkpoint_dir)
            if resume and ckpt_mgr.latest_step() is not None:
                start_epoch = ckpt_mgr.restore(self) + 1
        elif resume:
            raise ValueError("resume=True requires checkpoint_dir")
        for cb in callbacks:
            # keras callback protocol: bind the model before training
            # (works for both FFModel.fit and the keras Model.fit path,
            # which re-binds with the keras wrapper afterwards)
            if hasattr(cb, "set_model") and getattr(cb, "model", None) is None:
                cb.set_model(self)
        xs = x if isinstance(x, (list, tuple)) else [x]
        batch_size = batch_size or self.config.batch_size
        epochs = epochs or self.config.epochs
        loader = SingleDataLoader(
            self.compiled, [np.asarray(a) for a in xs], np.asarray(y),
            batch_size, shuffle=shuffle, seed=self.config.seed,
        )
        if start_epoch and shuffle:
            # fast-forward the shuffle stream: a resumed epoch N must see
            # the N-th permutation, not replay epoch 0's order
            ff_order = np.arange(loader.num_samples)
            for _ in range(start_epoch):
                loader.rng.shuffle(ff_order)
        if loader.num_batches == 0:
            raise ValueError(
                f"no full batch: {loader.num_samples} samples < batch_size {batch_size}"
            )
        for cb in callbacks:
            cb.on_train_begin()
        profiler = None
        if self.config.profiling:
            from flexflow_tpu.runtime.profiler import StepProfiler

            profiler = StepProfiler()
        # real device-trace capture (obs/annotate.py + trace_ingest.py):
        # the post-compile steps are captured under jax.profiler with
        # the step annotated and the sync buckets lane-stamped (the
        # lowering threaded the markers because device_trace_dir was
        # set at compile); after the run the capture is ingested and
        # tag-matched against the predicted comm lanes.
        from flexflow_tpu.obs import annotate, device_counters
        from flexflow_tpu.obs.metrics import METRICS

        fit_steps = METRICS.counter("fit.steps")
        capture_dir = self.config.device_trace_dir
        trace_active = False
        self.lane_drift_report = None
        metrics = PerfMetrics()
        history = []
        t_start = None
        steps_done = 0
        steps_at_t0 = 0
        stop = False
        # iteration tracing: run config.trace_steps optimizer steps per
        # compiled call (train_steps scan) — the Legion begin/end_trace
        # analogue.  Incompatible with per-step profiling/recompile
        # checks, which need host control between steps.
        trace_n = max(1, int(getattr(self.config, "trace_steps", 1)))
        use_trace = (
            trace_n > 1
            and profiler is None
            and recompile_state is None
            and jax.process_count() == 1
            and loader.num_batches >= trace_n
            # multi-mesh compositions (inter-op placement) have no
            # single traced program — fall back to per-step calls
            and getattr(self.compiled, "supports_trace", True)
        )
        for epoch in range(start_epoch, epochs):
            for cb in callbacks:
                cb.on_epoch_begin(epoch)
            metrics.reset()
            acc = None  # device-side metric accumulation; host sync once/epoch
            batch_iter = (
                loader.iter_traced(trace_n) if use_trace else
                (("single", i, l) for i, l in loader)
            )
            # ff.phase/fit.data: the wait for the loader's next batch
            for kind, inputs, labels in annotate.spanned(
                    annotate.PHASE_PREFIX + "fit.data", batch_iter):
                self._rng_counter += 1
                rng = jax.random.key(self._rng_counter)
                # one ff.phase/step annotation per dispatch: how long
                # the host is held in it — and, inside a
                # device_trace_dir capture, the window trace_ingest
                # assigns lane markers to
                with annotate.phase_span(annotate.STEP_PHASE,
                                         key=self._rng_counter):
                    if profiler is not None:
                        profiler.start_step()
                        profiler.start_phase("dispatch")
                    if kind == "stack":
                        (self.params, self.opt_state, self.state, losses,
                         ms) = self.compiled.train_steps(
                            self.params, self.opt_state, self.state, rng,
                            inputs, labels)
                        loss = losses[-1]
                        # summing the stacked per-step metric trees
                        # equals the single-step accumulation below
                        m = jax.tree.map(lambda a: a.sum(axis=0), ms)
                        n_this = len(losses)
                    else:
                        (self.params, self.opt_state, self.state, loss,
                         m) = self.compiled.train_step(
                            self.params, self.opt_state, self.state, rng,
                            inputs, labels)
                        n_this = 1
                    if profiler is not None:
                        # host phases: enqueue (dispatch) vs device
                        # completion (wait) — the measured side of the
                        # DriftReport; the fence makes the step time real
                        profiler.end_phase("dispatch")
                        profiler.start_phase("wait")
                        float(loss)
                        profiler.end_phase("wait")
                        profiler.end_step()
                    elif trace_active:
                        # inside a capture the step annotation must
                        # cover the device work, so it fences
                        float(loss)
                fit_steps.inc(n_this)
                if recompile_state is not None and recompile_state.check(self):
                    # drop the accumulator AND this step's metrics: the
                    # re-lowered program may emit a different metric tree
                    acc = None
                else:
                    acc = m if acc is None else jax.tree.map(
                        lambda a, b: a + b, acc, m)
                steps_done += n_this
                if t_start is None:
                    float(loss)  # host readback: the compile step is done
                    t_start = time.perf_counter()  # skip compile time
                    steps_at_t0 = steps_done
                    if capture_dir and not trace_active:
                        # start the capture AFTER the compile step so
                        # the trace holds steady-state steps only
                        try:
                            import os as _os

                            _os.makedirs(capture_dir, exist_ok=True)
                            jax.profiler.start_trace(capture_dir)
                            trace_active = True
                        except Exception:
                            pass  # telemetry must never fail a fit
            # ff.phase/fit.epoch_sync: the epoch-end readback, where the
            # host waits for every step it dispatched ahead
            with annotate.phase_span(
                    annotate.PHASE_PREFIX + "fit.epoch_sync"):
                if acc is not None:  # None if a recompile landed on the last batch
                    metrics.update(acc)
                epoch_loss = float(loss)
                # counters the step accumulates on the device (expert
                # loads, the second loss): read where the loss just was,
                # so no step gains a host sync
                device_counters.publish(self.state, self._obs_seen)
            if verbose:
                print(f"epoch {epoch}: loss={epoch_loss:.4f} {metrics}")
            logs = metrics.report()
            logs["loss"] = epoch_loss
            if validation_data is not None:
                vx, vy = validation_data
                val = self.evaluate(x=vx, y=vy, batch_size=batch_size)
                for k, v in val.items():
                    if k != "samples":
                        logs[f"val_{k}"] = v
                if verbose:
                    parts = " ".join(
                        f"{k}: {v:.4f}" for k, v in logs.items()
                        if k.startswith("val_")
                    )
                    print(f"  validation: {parts}")
            history.append(logs)
            for cb in callbacks:
                if cb.on_epoch_end(epoch, logs) is False:
                    stop = True
            if ckpt_mgr is not None and (
                (epoch + 1) % max(1, checkpoint_every) == 0
                or epoch == epochs - 1 or stop
            ):
                ckpt_mgr.save(epoch, self)
            if stop:
                break
        for cb in callbacks:
            cb.on_train_end()
        if trace_active:
            try:
                float(loss)  # fence: the last step must land in-trace
                jax.profiler.stop_trace()
            except Exception:
                trace_active = False
        if steps_done == 0:
            return history
        float(loss)  # readback fence before reading the clock
        elapsed = time.perf_counter() - (t_start or time.perf_counter())
        if steps_done > steps_at_t0 and elapsed > 0:
            thr = (steps_done - steps_at_t0) * batch_size / elapsed
            if verbose:
                print(f"ELAPSED TIME = {elapsed:.4f}s, THROUGHPUT = {thr:.2f} samples/s")
            self.last_throughput = thr
        if profiler is not None:
            self._report_profile(profiler, verbose)
        if trace_active:
            self._ingest_device_trace(capture_dir, verbose)
        if profiler is None and steps_done > steps_at_t0 and elapsed > 0:
            # re-probe-allowance bugfix: a HEALTHY calibrated fit must
            # reset MAX_AUTO_REPROBES even when neither profiling nor
            # the obs bus armed the full drift-report path — fit's own
            # fenced post-compile timer is evidence enough to CLEAR
            # staleness (stale-MARKING stays on the profiler's
            # measurement: a false "stale" poisons the cost cache, a
            # false "healthy" merely re-grants a re-probe)
            self._healthy_calibration_reset(
                elapsed / (steps_done - steps_at_t0))
        return history

    def _ingest_device_trace(self, capture_dir: str, verbose: bool) -> None:
        """Close the measured side of the lane loop: parse the capture
        fit just stopped, tag-match it against the compile-time
        predicted comm lanes, and fill the per-bucket DriftReport
        measured fields that stayed ``None`` while no real trace
        existed.  The report lands on ``self.lane_drift_report`` and
        (when exporting) in the strategy file's ``__meta__``."""
        try:
            from flexflow_tpu.obs.events import BUS
            from flexflow_tpu.obs.trace_ingest import (
                apply_lane_measurements,
                build_lane_drift_report,
            )

            report = build_lane_drift_report(
                capture_dir, getattr(self, "predicted_breakdown", None),
                threshold=self.config.drift_threshold)
            self.lane_drift_report = report
            if report is None:
                return
            apply_lane_measurements(self.drift_report, report)
            if verbose:
                print(f"LANES {report}")
            if self.config.export_strategy_file:
                from flexflow_tpu.search.strategy_io import attach_meta

                try:
                    attach_meta(self.config.export_strategy_file,
                                lane_drift=report.to_dict())
                except (OSError, ValueError):
                    pass
            BUS.flush()
        except Exception:  # telemetry must never fail a fit
            self.lane_drift_report = None

    def _healthy_calibration_reset(self, measured_step_s: float) -> None:
        pred = getattr(self, "predicted_breakdown", None)
        if (not pred or not pred.get("calibrated")
                or not self.config.calibration_file):
            return
        from flexflow_tpu.obs.drift import build_drift_report

        report = build_drift_report(
            pred, measured_step_s=measured_step_s,
            threshold=self.config.drift_threshold, calibrated=True)
        if report is None or report.stale:
            return
        from flexflow_tpu.search.calibration import CalibrationTable

        CalibrationTable.mark_healthy_file(self.config.calibration_file)

    def _report_profile(self, profiler, verbose: bool) -> None:
        """Step-profile reporting through the obs metrics registry +
        event bus (replacing the ad-hoc ``print(f"PROFILE ...")``-only
        path), plus the predicted-vs-measured DriftReport when
        compile() recorded a prediction."""
        from flexflow_tpu.obs.drift import build_drift_report
        from flexflow_tpu.obs.events import BUS
        from flexflow_tpu.obs.metrics import METRICS

        s = profiler.summary()
        if s.get("steps") and not s.get("includes_compile"):
            # compile-contaminated stats stay out of the registry the
            # same way the drift path declines them — a gauge has no
            # honesty flag to carry the caveat
            METRICS.gauge("fit.step_mean_s").set(s["mean_s"])
            METRICS.gauge("fit.step_p95_s").set(s["p95_s"])
            hist = METRICS.histogram("fit.step_s")
            for t in profiler.step_times[1:]:
                hist.observe(t)
        BUS.emit("profile.summary", **s)
        if verbose:
            print(f"PROFILE {profiler}")
        pred = getattr(self, "predicted_breakdown", None)
        if not pred or not s.get("steps") or s.get("includes_compile"):
            # a compile-only measurement would compare apples to the
            # compile step; decline rather than report fiction
            return
        report = build_drift_report(
            pred,
            measured_step_s=s["mean_s"],
            measured_phases=profiler.phase_summary(),
            threshold=self.config.drift_threshold,
            calibrated=bool(pred.get("calibrated")),
        )
        if report is None:
            return
        self.drift_report = report
        BUS.emit("drift.report", **report.to_dict())
        METRICS.gauge("fit.drift_ratio").set(report.ratio)
        if report.calibration_stale:
            BUS.emit("calibration.staleness", ratio=report.ratio,
                     threshold=report.threshold)
            from flexflow_tpu.utils.logging import SEARCH_LOG

            lo = 1.0 / (1.0 + report.threshold)
            hi = 1.0 + report.threshold
            SEARCH_LOG.log(
                f"calibration staleness: measured step is "
                f"{report.ratio:.2f}x the calibrated prediction, "
                f"outside [{lo:.2f}x, {hi:.2f}x]"
            )
            # mark the persisted TABLE stale so the next
            # optimize_strategy re-probes the drifted records
            # automatically (driver re-probe policy) instead of ranking
            # with measurements execution just falsified
            if self.config.calibration_file:
                from flexflow_tpu.search.calibration import (
                    CalibrationTable,
                )

                if CalibrationTable.mark_stale_file(
                        self.config.calibration_file, report.ratio):
                    SEARCH_LOG.log(
                        f"calibration table "
                        f"{self.config.calibration_file} marked stale: "
                        f"the next search re-probes it on the modeled "
                        f"backend (or falls back to the roofline)"
                    )
            # a stale table must also stop seeding future searches: mark
            # the persistent cost cache, which then refuses to serve its
            # rows/results until a recalibration rotates the signature
            from flexflow_tpu.search.cost_cache import (
                mark_calibration_stale,
                resolve_cost_cache_path,
            )

            cache_path = resolve_cost_cache_path(self.config)
            if cache_path and mark_calibration_stale(cache_path):
                SEARCH_LOG.log(
                    f"cost cache {cache_path} marked calibration-stale: "
                    f"recalibrate or pass --no-cost-cache"
                )
        elif report.calibrated and self.config.calibration_file:
            # drift cleared on a calibrated fit: reset the persisted
            # staleness state and the auto-re-probe allowance, so the
            # driver's re-probe cap only counts CONSECUTIVE failures
            from flexflow_tpu.search.calibration import CalibrationTable

            CalibrationTable.mark_healthy_file(self.config.calibration_file)
        if verbose:
            print(f"DRIFT {report}")
        if self.config.export_strategy_file:
            from flexflow_tpu.search.strategy_io import attach_meta

            try:
                attach_meta(self.config.export_strategy_file,
                            drift=report.to_dict())
            except (OSError, ValueError):
                pass
        BUS.flush()  # writes are block-buffered; a fit boundary is
        # where tooling tails the log

    def evaluate(self, x=None, y=None, batch_size: Optional[int] = None):
        """reference: flexflow_cffi.py:1876 eval."""
        from flexflow_tpu.runtime.dataloader import SingleDataLoader

        xs = x if isinstance(x, (list, tuple)) else [x]
        batch_size = batch_size or self.config.batch_size
        loader = SingleDataLoader(
            self.compiled, [np.asarray(a) for a in xs], np.asarray(y),
            batch_size, shuffle=False,
        )
        metrics = PerfMetrics()
        total_loss, batches = 0.0, 0
        for inputs, labels in loader:
            loss, m = self.compiled.eval_step(
                self.params, self.state, inputs, labels
            )
            total_loss += float(loss)
            batches += 1
            metrics.update(m)
        rep = metrics.report()
        if batches:  # equal-sized batches: mean of batch means is exact
            rep["loss"] = total_loss / batches
        return rep

    def predict(self, x, batch_size: Optional[int] = None) -> np.ndarray:
        """Batched forward pass: one output row per input row (a short
        tail batch is padded to batch_size and trimmed — the compiled
        program has static shapes).  The inference verb pairing with
        compile(comp_mode='inference'); reference models predict via
        their eval path only."""
        assert self.compiled is not None, "call compile() first"
        batch_size = batch_size or self.config.batch_size
        xs = x if isinstance(x, (list, tuple)) else [x]
        xs = [np.asarray(a) for a in xs]
        n = xs[0].shape[0]
        fwd = self.compiled.forward_fn()
        outs = []
        for i in range(0, n, batch_size):
            batch = [a[i:i + batch_size] for a in xs]
            got = batch[0].shape[0]
            if got < batch_size:
                batch = [
                    np.concatenate(
                        [b, np.repeat(b[-1:], batch_size - got, axis=0)],
                        axis=0,
                    )
                    for b in batch
                ]
            y = np.asarray(fwd(self.params, self.state, batch))
            outs.append(y[:got])
        if outs:
            return np.concatenate(outs, axis=0)
        import jax

        zero_batch = [
            jax.ShapeDtypeStruct((batch_size,) + a.shape[1:], a.dtype)
            for a in xs
        ]
        spec = jax.eval_shape(fwd, self.params, self.state, zero_batch)
        return np.empty((0,) + tuple(spec.shape[1:]), spec.dtype)

    # ------------------------------------------------------------------
    def get_weight(self, op_name: str, weight_name: str = "kernel") -> np.ndarray:
        """reference: ParallelTensorBase::get_tensor (parallel_tensor.h:157)."""
        return np.asarray(self.params[op_name][weight_name])

    def set_weight(self, op_name: str, weight_name: str, value: np.ndarray) -> None:
        import jax

        old = self.params[op_name][weight_name]
        assert tuple(old.shape) == tuple(value.shape)
        self.params[op_name][weight_name] = jax.device_put(
            value.astype(old.dtype), old.sharding
        )

    def set_state_var(self, key: str, value: np.ndarray) -> None:
        """Overwrite one model-state entry (e.g. a batch-norm running
        statistic, key ``"<op>/running_mean"``)."""
        import jax

        old = self.state[key]
        assert tuple(old.shape) == tuple(value.shape), (key, old.shape, value.shape)
        self.state[key] = jax.device_put(value.astype(old.dtype), old.sharding)
