"""Pipeline-parallel lowering: PCG → prologue + looped-pipeline + epilogue.

The reference reserved OP_PIPELINE (reference: include/flexflow/
ffconst.h:148, model.h:184-186) but shipped no implementation; its DP
search only places subgraphs on disjoint devices without microbatching
(reference: graph.cc:180-205).  Here pipeline parallelism is a real
compile mode: ``FFModel.compile(pipeline=PipelineConfig(...))``.

How the PCG is pipelined
------------------------
1. The graph is partitioned into *blocks* — repeated isomorphic
   subgraphs detected by op-name pattern (``layer<i>_...``, the naming
   convention of every stacked model in flexflow_tpu.models) or given
   explicitly via ``block_of``.  Nodes before the first block form the
   prologue (inputs, embeddings), nodes after the last form the
   epilogue (heads, pooling, loss inputs).
2. Block weights are stacked along a leading [L] axis sharded over the
   mesh's ``pp`` axis, so stage s holds blocks [s·L/S, (s+1)·L/S).
3. The train step runs prologue on the full batch, splits the stream
   tensor into M microbatches, drives the collective pipeline
   (flexflow_tpu.parallel.pipeline.pipeline_spmd — lax.scan of
   compute+ppermute ticks), merges, and runs the epilogue + loss.
   ``jax.grad`` through the scanned schedule yields the pipelined
   backward automatically.

Constraints (checked at compile): blocks must be isomorphic, carry a
single streaming tensor between them, and contain no stateful ops
(BatchNorm running stats / MoE caches live in prologue/epilogue).
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from flexflow_tpu.compiler.lowering import CompiledModel, weight_fold_key
from flexflow_tpu.core.graph import Graph, Node
from flexflow_tpu.ops.base import LoweringContext
from flexflow_tpu.parallel.mesh import mesh_axis_sizes
from flexflow_tpu.parallel.pipeline import (
    PipelineConfig,
    merge_microbatches,
    pipeline_spmd,
    split_microbatches,
)

_BLOCK_RE = re.compile(r"^layer(\d+)_")


def build_pipeline_mesh(devices: Sequence, num_stages: int, axis_name: str = "pp"):
    """Mesh with a leading pipeline axis of size num_stages; remaining
    devices factor into the usual prime-sized data/model axes."""
    from jax.sharding import Mesh

    n = len(devices)
    assert n % num_stages == 0, f"{n} devices not divisible into {num_stages} stages"
    rest = mesh_axis_sizes(n // num_stages)
    names = (axis_name,) + tuple(a for a, _ in rest)
    shape = (num_stages,) + tuple(s for _, s in rest)
    return Mesh(np.array(devices).reshape(shape), names)


def detect_blocks(
    graph: Graph, block_of: Optional[Dict[int, int]] = None
) -> Tuple[List[List[Node]], List[Node], List[Node]]:
    """Partition nodes into (blocks, prologue, epilogue) in topo order."""
    topo = graph.topo_order()
    if block_of is None:
        block_of = {}
        for node in topo:
            m = _BLOCK_RE.match(node.op.name)
            if m:
                block_of[node.guid] = int(m.group(1))
    if not block_of:
        raise ValueError(
            "pipeline compile found no repeated blocks: name block ops "
            "'layer<i>_...' or pass block_of={node_guid: block_idx}"
        )
    n_blocks = max(block_of.values()) + 1
    blocks: List[List[Node]] = [[] for _ in range(n_blocks)]
    prologue: List[Node] = []
    epilogue: List[Node] = []
    seen_block = False
    for node in topo:
        b = block_of.get(node.guid)
        if b is not None:
            seen_block = True
            blocks[b].append(node)
        elif not seen_block:
            prologue.append(node)
        else:
            epilogue.append(node)
    for i, blk in enumerate(blocks):
        if not blk:
            raise ValueError(f"pipeline block {i} is empty")
    return blocks, prologue, epilogue


def _block_signature(block: List[Node], graph: Graph, member: set) -> Tuple:
    sig = []
    for node in block:
        in_edges = sorted(graph.in_edges[node.guid], key=lambda e: e.dst_idx)
        wiring = tuple(
            ("ext",) if e.src not in member else ("int", block_pos(block, e.src), e.src_idx)
        for e in in_edges)
        sig.append((node.op.signature(), wiring))
    return tuple(sig)


def block_pos(block: List[Node], guid: int) -> int:
    for i, n in enumerate(block):
        if n.guid == guid:
            return i
    return -1


class PipelinedCompiledModel(CompiledModel):
    """CompiledModel whose repeated-block stack executes as an S-stage
    collective pipeline over the ``pp`` mesh axis."""

    def __init__(self, *args, pipeline: PipelineConfig,
                 block_of: Optional[Dict[int, int]] = None, **kwargs):
        self.pipeline = pipeline
        graph: Graph = args[0]
        config = args[2]
        if kwargs.get("mesh") is None:
            kwargs["mesh"] = build_pipeline_mesh(
                jax.devices()[: config.num_devices], pipeline.num_stages,
                axis_name=pipeline.axis_name,
            )
        super().__init__(*args, **kwargs)

        self._blocks, self._prologue, self._epilogue = detect_blocks(
            graph, block_of
        )
        L, S = len(self._blocks), pipeline.num_stages
        if L % S:
            raise ValueError(f"{L} blocks not divisible into {S} stages")

        member0 = {n.guid for n in self._blocks[0]}
        sig0 = _block_signature(self._blocks[0], graph, member0)
        for i, blk in enumerate(self._blocks[1:], 1):
            member = {n.guid for n in blk}
            if _block_signature(blk, graph, member) != sig0:
                raise ValueError(f"pipeline block {i} is not isomorphic to block 0")

        # streaming tensor: the unique external value entering each block
        self._block_entry: List[Tuple[int, int]] = []
        for blk in self._blocks:
            member = {n.guid for n in blk}
            ext = set()
            for node in blk:
                for e in graph.in_edges[node.guid]:
                    if e.src not in member:
                        ext.add((e.src, e.src_idx))
            if len(ext) != 1:
                raise ValueError(
                    f"pipeline block has {len(ext)} external inputs; need exactly 1"
                )
            self._block_entry.append(next(iter(ext)))
        # block exit = the (unique) block value consumed outside the block
        self._block_exit: List[Tuple[int, int]] = []
        all_members = [
            {n.guid for n in blk} for blk in self._blocks
        ]
        topo = graph.topo_order()
        for bi, blk in enumerate(self._blocks):
            member = all_members[bi]
            exits = set()
            for node in topo:
                if node.guid in member:
                    continue
                for e in graph.in_edges[node.guid]:
                    if e.src in member:
                        exits.add((e.src, e.src_idx))
            if len(exits) != 1:
                raise ValueError(
                    f"pipeline block {bi} has {len(exits)} external consumers; need 1"
                )
            self._block_exit.append(next(iter(exits)))
        for bi in range(1, L):
            if self._block_entry[bi] != self._block_exit[bi - 1]:
                raise ValueError("pipeline blocks must chain linearly")

        for node in self._blocks[0] + [n for b in self._blocks[1:] for n in b]:
            if getattr(node.op, "state_specs", None) is not None:
                raise ValueError(
                    f"stateful op {node.op.name} not supported inside a pipeline block"
                )

        # template maps: block-0 op name <-> per-block op names
        self._tmpl_names = [n.op.name for n in self._blocks[0]]
        self._block_op_names: List[List[str]] = [
            [n.op.name for n in blk] for blk in self._blocks
        ]
        self._block_guids = {g for m in all_members for g in m}

    # ------------------------------------------------------------------
    def _run_block_template(self, ctx: LoweringContext, x: jax.Array,
                            params_one: Dict[str, Dict[str, jax.Array]]):
        """Execute block 0's subgraph with substituted params; the single
        external input is ``x``; returns the block's exit value."""
        blk = self._blocks[0]
        member = {n.guid for n in blk}
        values: Dict[Tuple[int, int], jax.Array] = {}
        for node in blk:
            in_edges = sorted(self.graph.in_edges[node.guid], key=lambda e: e.dst_idx)
            ins = []
            for e in in_edges:
                if e.src in member:
                    ins.append(values[(e.src, e.src_idx)])
                else:
                    ins.append(x)
            outs = node.op.forward(ctx, ins, params_one.get(node.op.weights_key, {}))
            for i, y in enumerate(outs):
                values[(node.guid, i)] = y
        assert not ctx.state_out, "stateful ops inside pipeline blocks"
        exit_guid, exit_idx = self._block_exit[0]
        return values[(exit_guid, exit_idx)]

    # ------------------------------------------------------------------
    def apply(self, params, state, inputs, rng, train):
        ctx = LoweringContext(
            compute_dtype=self.compute_dtype,
            train=train,
            rng=rng,
            seq_length=self.config.iteration.seq_length,
            state_in=state,
            mesh=self.mesh if self._multi_device else None,
        )
        values: Dict[Tuple[int, int], jax.Array] = {}
        input_pos = {n.guid: i for i, n in enumerate(self._input_nodes)}
        pipeline_done = False

        for node in self._topo:
            if node.guid in self._block_guids:
                if pipeline_done:
                    continue
                pipeline_done = True
                values[self._block_exit[-1]] = self._run_pipeline(
                    values[self._block_entry[0]], params, rng, train
                )
                continue
            self._run_node(node, ctx, values, params, inputs, input_pos)

        logits = values[(self._sink.guid, 0)]
        new_state = dict(state)
        new_state.update(ctx.state_out)
        return logits, new_state

    # ------------------------------------------------------------------
    def _run_pipeline(self, stream, params, rng, train):
        M = self.pipeline.num_microbatches
        L, S = len(self._blocks), self.pipeline.num_stages
        stacked = {tn: params[tn] for tn in self._tmpl_names if tn in params}
        rng_c = rng if rng is not None else jax.random.key(0)

        def stage_fn(p_stage, x, const, mb_index):
            # p_stage leaves: [L/S, ...] — scan over this stage's blocks.
            key = const
            s_idx = jax.lax.axis_index(self.pipeline.axis_name) if S > 1 else 0
            # distinct key per (stage, block, microbatch): stochastic ops
            # must not reuse masks across microbatches
            key = jax.random.fold_in(jax.random.fold_in(key, s_idx), mb_index)

            def one_block(x, blk):
                p_blk, local_i = blk
                bctx = LoweringContext(
                    compute_dtype=self.compute_dtype,
                    train=train,
                    rng=jax.random.fold_in(key, local_i),
                    seq_length=self.config.iteration.seq_length,
                    state_in={},
                    mesh=None,
                )
                if self.config.remat:
                    # per-block activation rematerialization — the
                    # standard memory/FLOPs trade under a scanned stack
                    y = jax.checkpoint(
                        lambda xx, pp: self._run_block_template(bctx, xx, pp)
                    )(x, p_blk)
                    return y, None
                return self._run_block_template(bctx, x, p_blk), None

            x, _ = jax.lax.scan(
                one_block, x, (p_stage, jnp.arange(L // S))
            )
            return x

        xm = split_microbatches(stream, M)
        ym = pipeline_spmd(
            stage_fn,
            stacked,
            xm,
            mesh=self.mesh,
            axis_name=self.pipeline.axis_name,
            x_const=rng_c,
        )
        return merge_microbatches(ym)

    # ------------------------------------------------------------------
    def init_params(self, seed: int = 0):
        """Stack block weights [L, ...] sharded over pp; everything else
        as in the base lowering."""
        from flexflow_tpu.parallel.mesh import annot_partition_spec

        L = len(self._blocks)
        specs = []  # (op_name, w_name, shape(incl stack), dtype, init, sharding, stacked)
        tmpl_set = set(self._tmpl_names)
        for node in self._topo:
            if node.guid in self._block_guids:
                if node.op.name not in tmpl_set:
                    continue  # blocks >0 share the stacked entries
                for ws in node.op._weight_specs:
                    spec = jax.sharding.PartitionSpec(
                        self.pipeline.axis_name, *([None] * len(ws.shape))
                    )
                    specs.append(
                        (node.op.name, ws.name, (L,) + ws.shape,
                         ws.dtype.to_numpy(), ws.initializer,
                         jax.sharding.NamedSharding(self.mesh, spec), True)
                    )
                continue
            osh = self._shardings[node.guid]
            axes = self._slot_axes[node.guid]
            for wi, ws in enumerate(node.op._weight_specs):
                annot = osh.weights[wi] if wi < len(osh.weights) else None
                pspec = (
                    annot_partition_spec(annot, axes)
                    if annot is not None
                    else jax.sharding.PartitionSpec()
                )
                specs.append(
                    (node.op.name, ws.name, ws.shape, ws.dtype.to_numpy(),
                     ws.initializer,
                     jax.sharding.NamedSharding(self.mesh, pspec), False)
                )

        def _init(key):
            out = {}
            for op_name, w_name, shape, dtype, init, _, stacked in specs:
                k = weight_fold_key(key, op_name, w_name)
                if stacked:
                    w = jnp.stack(
                        [init.init(jax.random.fold_in(k, b), shape[1:], dtype)
                         for b in range(shape[0])]
                    )
                else:
                    w = init.init(k, shape, dtype)
                out.setdefault(op_name, {})[w_name] = w
            return out

        shardings = {}
        for op_name, w_name, _, _, _, sh, _ in specs:
            shardings.setdefault(op_name, {})[w_name] = sh
        key = jax.random.key(seed)
        params = jax.jit(_init, out_shardings=(shardings or None))(key)

        state: Dict[str, jax.Array] = {}
        for node in self._topo:
            if node.guid in self._block_guids:
                continue
            ss = getattr(node.op, "state_specs", None)
            if ss is None:
                continue
            for name, shape, dtype, fill in ss():
                state[f"{node.op.name}/{name}"] = jnp.full(shape, fill, dtype)
        self.param_shardings = shardings
        return params, state
