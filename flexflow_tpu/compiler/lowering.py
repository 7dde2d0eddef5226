"""PCG → XLA lowering.

The TPU counterpart of the entire execution half of the reference
(FFModel::compile region mapping model.cc:2703-2836 + per-op Legion
index launches + Legion tracing): the whole training iteration becomes
ONE jitted SPMD program over the global mesh.  Per-op "machine views"
are realized as GSPMD sharding constraints on tensor edges; XLA inserts
the collectives the reference delegated to Legion/Realm (activations)
and NCCL (gradients), fuses elementwise chains (the reference's FusedOp
pass, model.cc:2343, is obsolete by construction), and overlaps
compute/communication in its scheduler.

There are no backward methods anywhere: ``jax.value_and_grad`` of the
lowered forward replaces every hand-written backward task of the
reference (src/ops/ backward kernels), and gradient synchronization falls
out of params' shardings.
"""

from __future__ import annotations

import contextlib
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from flexflow_tpu.config import FFConfig
from flexflow_tpu.core.graph import Graph, Node
from flexflow_tpu.core.machine import MachineView
from flexflow_tpu.core.optype import OperatorType
from flexflow_tpu.core.ptensor import DataType
from flexflow_tpu.losses import LossType, compute_loss
from flexflow_tpu.metrics import MetricsType, compute_metrics
from flexflow_tpu.obs.annotate import FIRST_CALL_PHASE, phase_span
from flexflow_tpu.ops.base import LoweringContext, OpSharding, ShardAnnot
from flexflow_tpu.ops.inout import InputOp
from flexflow_tpu.optimizers import Optimizer
from flexflow_tpu.parallel.mesh import (
    annot_partition_spec,
    build_mesh,
    mesh_axis_sizes,
    view_slot_axes,
)


def weight_fold_key(base_key, op_name: str, w_name: str):
    """Per-weight init key derived from the weight's NAME, not its
    position in the topo enumeration: initialization is then invariant
    to how a strategy partitions the graph into programs (a placed
    2-segment lowering and the flat lowering draw identical weights for
    the same seed) and to graph rewrites that preserve op names."""
    import zlib

    return jax.random.fold_in(
        base_key, np.uint32(zlib.crc32(f"{op_name}/{w_name}".encode()))
    )


def data_parallel_strategy(graph: Graph, degree: int) -> Dict[int, MachineView]:
    """Batch-dim partitioning for every op — the reference's
    --only-data-parallel path (graph.cc:1572-1597)."""
    # candidate degrees: divisors of the device count, descending, so the
    # chosen degree always factors into the mesh's prime-factor axis pool
    divisors = sorted(
        (d for d in range(1, degree + 1) if degree % d == 0), reverse=True
    )
    strategy: Dict[int, MachineView] = {}
    for node in graph.topo_order():
        fixed = node.op.fixed_machine_view()
        if fixed is not None:
            strategy[node.guid] = fixed
            continue
        out = node.op.output_shapes[0]
        batch = out.sizes[0] if out.ndim else 1
        d = 1
        if out.ndim and 0 in node.op.splittable_output_dims():
            d = next(dd for dd in divisors if batch % dd == 0)
        strategy[node.guid] = (
            MachineView.data_parallel(out.ndim, d) if d > 1 else MachineView.trivial(out.ndim)
        )
    return strategy


class CompiledModel:
    """A PCG + strategy compiled to jitted train/eval steps over a mesh."""

    def __init__(
        self,
        graph: Graph,
        strategy: Dict[int, MachineView],
        config: FFConfig,
        loss_type: LossType,
        metric_types: Sequence[MetricsType],
        optimizer: Optional[Optimizer],
        mesh=None,
        label_dtype: str = "int32",
        sync_precision: Optional[Dict[str, str]] = None,
        sync_schedule=None,
        zero_groups: Optional[Sequence[str]] = None,
    ):
        self.graph = graph
        self.strategy = strategy
        self.config = config
        # op name -> bf16/int8: weight groups whose gradient sync runs
        # through the compressed collective (comm/quantized.py); the
        # search builds this map (search/sync_precision.py) and absent
        # /empty means the historical bit-exact fp32 psum
        self.sync_precision: Dict[str, str] = dict(sync_precision or {})
        # searched gradient-sync schedule (search/sync_schedule.py):
        # when present, _sync_grads executes its buckets in issue order
        # via comm/bucketed.py — fused per-bucket wire payloads with
        # optimization_barrier anchoring inside the backward; None (the
        # default) keeps the monolithic post-backward path
        self.sync_schedule = sync_schedule
        # per-group optimizer-state sharding (the co-searched ZeRO-1
        # dimension, search/comm_plan.py): op names whose optimizer
        # state (and update) shards over their replication axes — the
        # per-group generalization of config.zero_dp_shard, which
        # still arms ALL ops when set.  Linted (SHD140/141) before it
        # gets here.
        self.zero_groups: Tuple[str, ...] = tuple(zero_groups or ())
        self.loss_type = LossType.from_any(loss_type)
        self.metric_types = [MetricsType.from_any(m) for m in metric_types]
        self.optimizer = optimizer
        self.mesh = mesh if mesh is not None else build_mesh(
            jax.devices()[: config.num_devices]
        )
        self.label_dtype = label_dtype
        self.compute_dtype = DataType.from_any(config.compute_dtype).to_numpy()

        self._topo = graph.topo_order()
        self._input_nodes: List[Node] = [
            n for n in self._topo if isinstance(n.op, InputOp)
        ]
        # order inputs by frontend tensor guid for stable binding
        self._input_nodes.sort(key=lambda n: n.op.attrs.get("tensor_guid", n.guid))
        sinks = graph.sinks()
        assert sinks, "empty graph"
        self._sink = sinks[-1]

        # axis pool = the mesh's own axes (minus any pipeline axis, which
        # only the pipelined lowering may consume); for default meshes
        # this equals mesh_axis_sizes(num_devices).
        _pl = getattr(self, "pipeline", None)
        pp_axis = _pl.axis_name if _pl is not None else "pp"
        axis_pool = [(n, s) for n, s in self.mesh.shape.items() if n != pp_axis]
        self._shardings: Dict[int, OpSharding] = {}
        self._slot_axes: Dict[int, Dict[int, Tuple[str, ...]]] = {}
        for node in self._topo:
            mv = strategy.get(node.guid)
            if mv is None:
                mv = node.op.fixed_machine_view() or MachineView.trivial(
                    node.op.output_shapes[0].ndim
                )
            self._shardings[node.guid] = node.op.propagate(mv)
            self._slot_axes[node.guid] = view_slot_axes(mv, axis_pool)

        self._multi_device = int(np.prod(list(self.mesh.shape.values()))) > 1
        self._remat_blocks = self._find_remat_blocks() if config.remat else {}
        self._train_step_fn = None
        self._eval_step_fn = None

    # ------------------------------------------------------------------
    def _constrain(self, x, annot: ShardAnnot, slot_axes) -> jax.Array:
        if not self._multi_device or annot.partial:
            return x
        spec = annot_partition_spec(annot, slot_axes)
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(self.mesh, spec)
        )

    def input_sharding(self, i: int):
        """NamedSharding for the i-th frontend input (dataloader uses it)."""
        node = self._input_nodes[i]
        annot = self._shardings[node.guid].outputs[0]
        spec = annot_partition_spec(annot, self._slot_axes[node.guid])
        return jax.sharding.NamedSharding(self.mesh, spec)

    def batch_sharding(self):
        """Batch-dim sharding of the label tensor = sink's batch annot."""
        annot = self._shardings[self._sink.guid].outputs[0]
        axes = self._slot_axes[self._sink.guid].get(0, ())
        from jax.sharding import PartitionSpec

        spec = PartitionSpec(tuple(axes) if len(axes) > 1 else (axes[0] if axes else None))
        return jax.sharding.NamedSharding(self.mesh, spec)

    # ------------------------------------------------------------------
    def apply(
        self,
        params: Dict[str, Dict[str, jax.Array]],
        state: Dict[str, jax.Array],
        inputs: Sequence[jax.Array],
        rng: Optional[jax.Array],
        train: bool,
    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """Forward through the PCG (global view). Returns (logits, new_state)."""
        outs, new_state = self.apply_multi(
            params, state, inputs, rng, train,
            outputs=((self._sink.guid, 0),),
        )
        return outs[0], new_state

    def apply_multi(
        self,
        params: Dict[str, Dict[str, jax.Array]],
        state: Dict[str, jax.Array],
        inputs: Sequence[jax.Array],
        rng: Optional[jax.Array],
        train: bool,
        outputs: Sequence[Tuple[int, int]],
    ) -> Tuple[Tuple[jax.Array, ...], Dict[str, jax.Array]]:
        """Forward returning the requested ``(guid, output_idx)`` tensors
        instead of the sink's — the placed lowering pulls every tensor
        that crosses its segment boundary from one forward pass."""
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
        for node in self._topo:
            block = self._remat_blocks.get(node.guid)
            if block is None:
                self._run_node(node, ctx, values, params, inputs, input_pos)
            elif node is block[-1]:
                self._run_remat_block(block, ctx, values, params, outputs)
        new_state = dict(state)
        new_state.update(ctx.state_out)
        return tuple(values[key] for key in outputs), new_state

    def value_sharding(self, guid: int, idx: int = 0):
        """NamedSharding of op ``guid``'s ``idx``-th output under this
        program's mesh (boundary cotangents re-enter under it)."""
        annot = self._shardings[guid].outputs[idx]
        spec = annot_partition_spec(annot, self._slot_axes[guid])
        return jax.sharding.NamedSharding(self.mesh, spec)

    @staticmethod
    def _pure(op) -> bool:
        """``forward`` writes no state, so it may be recomputed."""
        return not op.writes_state and getattr(op, "state_specs", None) is None

    def _find_remat_blocks(self) -> Dict[int, List[Node]]:
        """guid -> the ops of its ``FFModel.remat_block`` in topo order,
        for every block that can run as ONE checkpoint where its last op
        stands: all of its ops pure, and nothing outside reads one of
        its values before then.  An op of a block that cannot falls back
        to the per-op rule of ``_lower_op``."""
        pos = {n.guid: i for i, n in enumerate(self._topo)}
        blocks: Dict[int, List[Node]] = {}
        for node in self._topo:
            block_id = node.op.remat_block
            if block_id is not None:
                blocks.setdefault(block_id, []).append(node)
        found = {}
        for nodes in blocks.values():
            inside = {n.guid for n in nodes}
            last = pos[nodes[-1].guid]
            early_reader = any(
                e.dst not in inside and pos[e.dst] < last
                for n in nodes for e in self.graph.out_edges[n.guid])
            if (len(nodes) > 1 and not early_reader
                    and all(self._pure(n.op) and not isinstance(n.op, InputOp)
                            for n in nodes)):
                found.update({g: nodes for g in inside})
        return found

    def _run_remat_block(self, nodes, ctx, values, params, wanted):
        """The block's ops under one ``jax.checkpoint``: what enters the
        block is saved, nothing inside it.  ``wanted``: the values the
        caller asked ``apply_multi`` for."""
        inside = {n.guid for n in nodes}
        entering = sorted({
            (e.src, e.src_idx) for n in nodes
            for e in self.graph.in_edges[n.guid] if e.src not in inside})
        leaving = sorted({
            (e.src, e.src_idx) for n in nodes
            for e in self.graph.out_edges[n.guid] if e.dst not in inside}
            | {k for k in wanted if k[0] in inside})
        keys = {n.op.weights_key for n in nodes}

        def block(entered, ws):
            local = dict(zip(entering, entered))
            for n in nodes:
                self._run_node(n, ctx, local, ws, (), {}, in_block=True)
            return [local[k] for k in leaving]

        left = jax.checkpoint(block)(
            [values[k] for k in entering],
            {k: params[k] for k in keys if k in params})
        values.update(zip(leaving, left))

    def _run_node(self, node, ctx, values, params, inputs, input_pos,
                  in_block: bool = False):
        """Lower one PCG node into ``values`` (shared by the pipelined
        subclass's apply).  ``in_block``: inside a block that is
        checkpointed as a whole, so not once more by itself."""
        osh = self._shardings[node.guid]
        axes = self._slot_axes[node.guid]
        if node.guid in input_pos:
            x = inputs[input_pos[node.guid]]
            values[(node.guid, 0)] = self._constrain(x, osh.outputs[0], axes)
            return
        in_edges = sorted(self.graph.in_edges[node.guid], key=lambda e: e.dst_idx)
        ins = []
        for e in in_edges:
            x = values[(e.src, e.src_idx)]
            if e.dst_idx < len(osh.inputs) and osh.inputs[e.dst_idx] is not None:
                x = self._constrain(x, osh.inputs[e.dst_idx], axes)
            ins.append(x)
        ctx.slot_axes = axes
        ws = params.get(node.op.weights_key, {})
        # device time of the op's instructions can be charged to
        # ``ff.mtp/ff.moe.experts`` ...; an op without a scope lowers to
        # the program it always did
        scope = "/".join(s for s in (node.op.block_scope, node.op.scope) if s)
        with jax.named_scope(scope) if scope else contextlib.nullcontext():
            self._lower_op(node, ctx, ins, ws, osh, axes, values,
                           remat=self.config.remat and not in_block)

    def _lower_op(self, node, ctx, ins, ws, osh, axes, values, remat):
        if self._multi_device:
            # ops with an explicit-SPMD lowering (shard_map +
            # collectives) take it when the sharding calls for it —
            # e.g. vocab-split embedding emits a masked local gather +
            # psum instead of whatever GSPMD would pick for the global
            # jnp.take (SURVEY.md §7 hard part (e))
            outs = node.op.forward_sharded(ctx, ins, ws, osh)
            if outs is not None:
                for i, y in enumerate(outs):
                    values[(node.guid, i)] = y
                return
        if remat and ws and self._pure(node.op):
            # rematerialize stateless ops that read weights — their own
            # or, ``weights_of``, another op's — in backward: their
            # activations are recomputed instead of saved (state-mutating
            # ops can't be checkpointed — forward must be pure)
            outs = jax.checkpoint(
                lambda i, w: node.op.forward(ctx, i, w)
            )(ins, ws)
        else:
            outs = node.op.forward(ctx, ins, ws)
        for i, y in enumerate(outs):
            if i < len(osh.outputs):
                y = self._constrain(y, osh.outputs[i], axes)
            values[(node.guid, i)] = y

    # ------------------------------------------------------------------
    def init_params(self, seed: int = 0):
        """Initialize sharded params + model state (reference: per-weight
        initializer tasks, initializer.cc; here one jitted program whose
        out_shardings place every weight shard directly)."""
        specs = []  # (op_name, weight_name, shape, dtype, init, sharding)
        for node in self._topo:
            osh = self._shardings[node.guid]
            axes = self._slot_axes[node.guid]
            for wi, ws in enumerate(node.op._weight_specs):
                annot = osh.weights[wi] if wi < len(osh.weights) else None
                spec = (
                    annot_partition_spec(annot, axes)
                    if annot is not None
                    else jax.sharding.PartitionSpec()
                )
                specs.append(
                    (
                        node.op.name,
                        ws.name,
                        ws.shape,
                        ws.dtype.to_numpy(),
                        ws.initializer,
                        jax.sharding.NamedSharding(self.mesh, spec),
                    )
                )

        def _init(key):
            out = {}
            for op_name, w_name, shape, dtype, init, _ in specs:
                k = weight_fold_key(key, op_name, w_name)
                out.setdefault(op_name, {})[w_name] = init.init(k, shape, dtype)
            return out

        shardings = {}
        for op_name, w_name, _, _, _, sh in specs:
            shardings.setdefault(op_name, {})[w_name] = sh
        key = jax.random.key(seed)
        params = jax.jit(_init, out_shardings=(shardings or None))(key)

        state: Dict[str, jax.Array] = {}
        # replicate state vars over the whole mesh so eager (un-jitted)
        # multi-device forward sees consistently-placed operands
        rep = jax.sharding.NamedSharding(self.mesh, jax.sharding.PartitionSpec())
        for node in self._topo:
            ss = getattr(node.op, "state_specs", None)
            if ss is None:
                continue
            # ops that declare per-state shardings (the decode op's
            # paged KV cache) get their state PLACED under the
            # strategy's view instead of replicated — the KV residency
            # the cost model credits to a sharded view is residency the
            # compiled program actually realizes
            st_annots = {}
            ssh = getattr(node.op, "state_shardings", None)
            if ssh is not None and self._multi_device:
                mv = self.strategy.get(node.guid)
                if mv is None:
                    mv = node.op.fixed_machine_view() or MachineView.trivial(
                        node.op.output_shapes[0].ndim)
                st_annots = ssh(mv) or {}
            for name, shape, dtype, fill in ss():
                v = jnp.full(shape, fill, dtype)
                if self._multi_device:
                    annot = st_annots.get(name)
                    sh = rep if annot is None else jax.sharding.NamedSharding(
                        self.mesh,
                        annot_partition_spec(
                            annot, self._slot_axes[node.guid]),
                    )
                    v = jax.device_put(v, sh)
                state[f"{node.op.name}/{name}"] = v
        self.param_shardings = shardings
        self._zero_shardings = None
        zero_all = getattr(self.config, "zero_dp_shard", False)
        zg = set(self.zero_groups)
        if (zero_all or zg) and self._multi_device:
            # global flag = every op; the co-searched per-group map
            # restricts the augmented shardings to its members — ops
            # outside it keep replicated optimizer state (and the
            # update credit the joint currency never claimed for them)
            zs: Dict[str, Dict[str, jax.sharding.NamedSharding]] = {}
            for op_name, w_name, shape, _, _, sh in specs:
                if not zero_all and op_name not in zg:
                    continue
                zs.setdefault(op_name, {})[w_name] = self._zero_augmented(
                    sh, shape
                )
            self._zero_shardings = zs or None
        # error-feedback residual state (comm.quantized_allreduce_ef):
        # one fp32 residual per int8_ef weight, sharded like the param
        # so the shard_map-local block aligns with the grad's — carried
        # in the model-state dict like any other training-loop state
        # (checkpoints round-trip it for free)
        self._ef_keys: Dict[str, Dict[str, str]] = {}
        ef_ops = {op for op, p in self.sync_precision.items()
                  if p == "int8_ef"}
        if ef_ops and self._multi_device:
            from flexflow_tpu.comm.quantized import (
                MIN_COMPRESS_ELEMS,
                replication_axes,
            )

            for op_name, w_name, shape, _, _, sh in specs:
                if op_name not in ef_ops:
                    continue
                nelems = 1
                for d in shape:
                    nelems *= d
                if nelems < MIN_COMPRESS_ELEMS:
                    continue  # sub-floor weights never compress
                rep, _n = replication_axes(sh, self.mesh)
                if not rep:
                    continue
                key = f"{op_name}/{w_name}/ef_residual"
                self._ef_keys.setdefault(op_name, {})[w_name] = key
                state[key] = jax.device_put(
                    jnp.zeros(shape, jnp.float32), sh)
        return params, state

    # ------------------------------------------------------------------
    def _zero_augmented(self, sh, shape):
        """ZeRO-1 / weight-update sharding (arXiv:2004.13336): extend a
        weight's PartitionSpec with the mesh axes the weight is
        replicated over, placed on the largest evenly-divisible dim.
        Optimizer state stored with this sharding makes GSPMD lower the
        grad psum to reduce-scatter and the updated-weight broadcast to
        all-gather — same ring bytes, 1/replication the memory and
        update compute."""
        from flexflow_tpu.parallel.mesh import place_zero_factors

        spec = list(sh.spec) + [None] * (len(shape) - len(sh.spec))
        used = set()
        for e in spec:
            if e is None:
                continue
            for a in (e if isinstance(e, tuple) else (e,)):
                used.add(a)
        free = [(n, s) for n, s in self.mesh.shape.items()
                if n not in used and s > 1]
        if not free:
            return sh
        extents = []
        for d in range(len(shape)):
            cur = spec[d]
            cur_axes = () if cur is None else (
                cur if isinstance(cur, tuple) else (cur,)
            )
            deg = 1
            for a in cur_axes:
                deg *= self.mesh.shape[a]
            extents.append(
                shape[d] // deg if deg and shape[d] % deg == 0 else 1
            )
        for d, fi in place_zero_factors(extents, [s for _, s in free]):
            cur = spec[d]
            cur_axes = () if cur is None else (
                cur if isinstance(cur, tuple) else (cur,)
            )
            spec[d] = tuple(cur_axes) + (free[fi][0],)
        while spec and spec[-1] is None:
            spec.pop()
        return jax.sharding.NamedSharding(
            self.mesh, jax.sharding.PartitionSpec(*spec)
        )

    @staticmethod
    def _map_param_slots(opt_state, leaf_fn):
        """Apply ``leaf_fn(op, w, x)`` to every leaf of the optimizer
        slots that mirror the params tree (Adam m/v, SGD momentum v);
        scalar slots (step) pass through."""
        out = {}
        for slot, sub in opt_state.items():
            if isinstance(sub, dict):
                out[slot] = {
                    op: {w: leaf_fn(op, w, x) for w, x in ws.items()}
                    for op, ws in sub.items()
                }
            else:
                out[slot] = sub
        return out

    def shard_opt_state(self, opt_state):
        """Re-place freshly initialized optimizer state under the
        ZeRO-1 shardings (no-op unless config.zero_dp_shard or a
        per-group ``zero_groups`` map armed some ops; non-member ops'
        slots pass through untouched)."""
        if getattr(self, "_zero_shardings", None) is None:
            return opt_state
        zs = self._zero_shardings

        def place(op, w, x):
            sh = zs.get(op, {}).get(w)
            return x if sh is None else jax.device_put(x, sh)

        return self._map_param_slots(opt_state, place)

    def _constrain_update(self, new_params, new_opt_state):
        """Pin the post-update shardings inside the jitted step: params
        back to their layer shardings (the all-gather side of ZeRO),
        optimizer slots to the augmented shardings (the reduce-scatter
        side).  With a per-group map only the member ops are pinned —
        the others' update stays wherever GSPMD placed it, exactly the
        pre-ZeRO behavior."""
        if getattr(self, "_zero_shardings", None) is None:
            return new_params, new_opt_state
        zs = self._zero_shardings
        new_params = {
            op: {
                w: (
                    jax.lax.with_sharding_constraint(
                        x, self.param_shardings[op][w]
                    )
                    if zs.get(op, {}).get(w) is not None else x
                )
                for w, x in ws.items()
            }
            for op, ws in new_params.items()
        }

        def pin(op, w, x):
            sh = zs.get(op, {}).get(w)
            return x if sh is None else jax.lax.with_sharding_constraint(
                x, sh)

        new_opt_state = self._map_param_slots(new_opt_state, pin)
        return new_params, new_opt_state

    # ------------------------------------------------------------------
    def _sync_grads(self, grads, ef_state=None):
        """Gradient sync inside the jitted step, before the optimizer
        update.

        ``ef_state`` — the model-state dict carrying the error-feedback
        residuals for ``int8_ef`` groups (``init_params`` created them
        under ``{op}/{w}/ef_residual`` keys): the call then returns
        ``(grads, updates)`` where ``updates`` maps those state keys to
        the new residuals — the training step merges them into its
        ``new_state`` so the feedback persists across steps.  With
        ``ef_state=None`` (direct callers, pre-EF tests) the legacy
        single-value return is kept and int8_ef runs the plain int8
        wire.

        With a searched ``sync_schedule`` the buckets execute in issue
        order (comm/bucketed.py): each compressed bucket's member grads
        flatten into ONE fused wire payload over their replication
        axes, and buckets chain through ``optimization_barrier`` so XLA
        issues the collectives in backward grad-readiness order — the
        overlap the simulator prices (exposed-comm semantics).  fp32
        buckets contribute only their value-identity ordering barrier,
        so an all-fp32 schedule stays bit-exact with the monolithic
        path.

        Without a schedule, the weight groups ``self.sync_precision``
        names run the quantized quantize → compressed all_to_all →
        requantize → all_gather round trip (EQuARX, comm/quantized.py).
        With neither (or a single device) this returns ``grads``
        untouched — bit-exact with the historical lowering.  Both paths
        compose with ZeRO-1: the round trip runs before the optimizer
        update, so _constrain_update's reduce-scatter/all-gather
        placement of the update is unchanged; with grad accumulation
        the AVERAGED grads sync once per optimizer step.
        """
        def ret(g, updates=None):
            return g if ef_state is None else (g, updates or {})

        if not self._multi_device:
            return ret(grads)
        shardings = getattr(self, "param_shardings", None)
        if shardings is None:  # init_params not run yet — nothing to map
            return ret(grads)
        residuals = None
        ef_keys = getattr(self, "_ef_keys", None)
        if ef_state is not None and ef_keys:
            residuals = {
                op: {w: ef_state[key] for w, key in ws.items()
                     if key in ef_state}
                for op, ws in ef_keys.items()
            }
        schedule = self.sync_schedule
        if schedule is not None and getattr(schedule, "buckets", None):
            from flexflow_tpu.comm import bucketed_grad_sync
            from flexflow_tpu.obs.annotate import lane_stamps_armed

            # the machine spec arms staged (hierarchical) execution of
            # buckets carrying a reduction plan — the nested axis split
            # follows the spec's slice structure, not the live backend.
            # lane_stamps (device_trace_dir captures only) brackets
            # each bucket with its stable lane id so the real trace
            # tag-matches the predicted comm lanes.
            got = bucketed_grad_sync(
                grads, self.mesh, shardings, schedule,
                machine=self.config.machine_spec, residuals=residuals,
                lane_stamps=lane_stamps_armed(self.config))
            if residuals is None:
                return ret(got)
            merged, new_res = got
            return ret(merged, self._ef_updates(new_res))
        if not self.sync_precision:
            return ret(grads)
        from flexflow_tpu.comm import quantized_grad_sync

        got = quantized_grad_sync(
            grads, self.mesh, shardings, self.sync_precision,
            residuals=residuals,
        )
        if residuals is None:
            return ret(got)
        merged, new_res = got
        return ret(merged, self._ef_updates(new_res))

    def _ef_updates(self, new_res):
        """Map the sync path's returned residual tree back onto its
        model-state keys."""
        updates = {}
        for op, ws in (new_res or {}).items():
            for w, r in ws.items():
                key = self._ef_keys.get(op, {}).get(w)
                if key is not None:
                    updates[key] = r
        return updates

    def _loss_from(self, logits, labels, new_state):
        """The loss of ``logits`` — or, where an op IS the objective and
        says so in ``{op}/loss`` (ops/exit_loss.py), that — plus every
        ``{op}/aux_loss``."""
        own = [v for k, v in new_state.items() if k.endswith("/loss")]
        loss = sum(own) if own else compute_loss(self.loss_type, logits,
                                                 labels)
        for k, v in new_state.items():
            if k.endswith("/aux_loss"):
                loss = loss + v
        return loss

    def _raw_step(self, params, opt_state, state, rng, inputs, labels):
        optimizer = self.optimizer
        ga = max(1, getattr(self.config, "grad_accum_steps", 1))
        if ga > 1:
            return self._raw_step_accum(
                params, opt_state, state, rng, inputs, labels, ga
            )

        def loss_fn(p):
            logits, new_state = self.apply(p, state, inputs, rng, train=True)
            loss = self._loss_from(logits, labels, new_state)
            return loss, (logits, new_state)

        (loss, (logits, new_state)), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(params)
        grads, ef_updates = self._sync_grads(grads, ef_state=state)
        new_state.update(ef_updates)
        new_params, new_opt_state = optimizer.apply(params, grads, opt_state)
        new_params, new_opt_state = self._constrain_update(
            new_params, new_opt_state
        )
        m = compute_metrics(self.metric_types, self.loss_type, logits, labels)
        return new_params, new_opt_state, new_state, loss, m

    def _raw_step_accum(self, params, opt_state, state, rng, inputs, labels, ga):
        """Gradient accumulation: the batch is processed as ``ga``
        microbatches inside a lax.scan, grads averaged, ONE optimizer
        update — activation memory scales with batch/ga while the
        effective batch stays the full batch: the loss is the mean of
        equal-sized microbatch means and metrics are per-batch SUMS
        (compute_metrics semantics), so they add across the disjoint
        microbatches.  The reference has no analogue — its
        per-iteration batch is bounded by what fits.  Together with
        config.remat this is the second memory lever."""
        B = labels.shape[0]
        assert B % ga == 0, (
            f"batch {B} must divide by grad_accum_steps {ga}"
        )

        def resh(x):
            return x.reshape((ga, B // ga) + x.shape[1:])

        keys = jax.random.split(rng, ga)

        def loss_fn(p, s, inp, lab, key):
            logits, new_state = self.apply(p, s, list(inp), key, train=True)
            loss = self._loss_from(logits, lab, new_state)
            return loss, (logits, new_state)

        gzero = jax.tree.map(jnp.zeros_like, params)

        def body(carry, xs):
            s, gacc = carry
            key, inp, lab = xs
            (loss, (logits, new_s)), g = jax.value_and_grad(
                loss_fn, has_aux=True
            )(params, s, inp, lab, key)
            gacc = jax.tree.map(jnp.add, gacc, g)
            m = compute_metrics(self.metric_types, self.loss_type, logits, lab)
            return (new_s, gacc), (loss, m)

        (new_state, gsum), (losses, ms) = jax.lax.scan(
            body, (state, gzero),
            (keys, tuple(resh(x) for x in inputs), resh(labels)),
        )
        grads = jax.tree.map(lambda g: g / ga, gsum)
        # the AVERAGED grads sync once per optimizer step, so the EF
        # residual advances once per step too (state, not per-microbatch)
        grads, ef_updates = self._sync_grads(grads, ef_state=state)
        new_state.update(ef_updates)
        new_params, new_opt_state = self.optimizer.apply(
            params, grads, opt_state
        )
        new_params, new_opt_state = self._constrain_update(
            new_params, new_opt_state
        )
        loss = jnp.mean(losses)
        m = jax.tree.map(lambda x: jnp.sum(x, axis=0), ms)
        return new_params, new_opt_state, new_state, loss, m

    def _build_train_step(self):
        return jax.jit(self._raw_step, donate_argnums=(0, 1, 2))

    def _build_train_steps(self):
        def multi(params, opt_state, state, rng, inputs_stacked, labels_stacked):
            n = labels_stacked.shape[0]
            keys = jax.random.split(rng, n)

            def body(carry, xs):
                p, o, s = carry
                key, inp, lab = xs
                p, o, s, loss, m = self._raw_step(p, o, s, key, list(inp), lab)
                return (p, o, s), (loss, m)

            (p, o, s), (losses, ms) = jax.lax.scan(
                body, (params, opt_state, state),
                (keys, tuple(inputs_stacked), labels_stacked),
            )
            return p, o, s, losses, ms

        return jax.jit(multi, donate_argnums=(0, 1, 2))

    def train_steps(self, params, opt_state, state, rng, inputs_stacked,
                    labels_stacked):
        """Run N training steps inside ONE compiled program
        (jax.lax.scan over stacked batches) — the XLA-native analogue
        of Legion iteration tracing (reference: begin_trace/end_trace,
        flexflow_cffi.py:1867-1874): per-call dispatch overhead is paid
        once per N steps instead of every step.

        ``inputs_stacked``: list of arrays [N, B, ...]; ``labels_stacked``
        [N, B, ...].  Returns (params, opt_state, state, losses [N],
        metrics stacked over N)."""
        if getattr(self, "_train_steps_fn", None) is None:
            self._train_steps_fn = self._build_train_steps()
            with phase_span(FIRST_CALL_PHASE + "train_steps"):
                return self._train_steps_fn(
                    params, opt_state, state, rng,
                    tuple(inputs_stacked), labels_stacked)
        return self._train_steps_fn(params, opt_state, state, rng,
                                    tuple(inputs_stacked), labels_stacked)

    def stacked_input_sharding(self, i: int):
        """Sharding for a [N, B, ...] stack of the i-th input (leading
        step axis unsharded)."""
        from jax.sharding import NamedSharding, PartitionSpec

        base = self.input_sharding(i).spec
        return NamedSharding(self.mesh, PartitionSpec(None, *base))

    def stacked_batch_sharding(self):
        from jax.sharding import NamedSharding, PartitionSpec

        base = self.batch_sharding().spec
        return NamedSharding(self.mesh, PartitionSpec(None, *base))

    def _build_eval_step(self):
        def step(params, state, inputs, labels):
            logits, new_state = self.apply(params, state, inputs, None, train=False)
            loss = self._loss_from(logits, labels, new_state)
            m = compute_metrics(self.metric_types, self.loss_type, logits, labels)
            return loss, m

        return jax.jit(step)

    def train_step(self, params, opt_state, state, rng, inputs, labels):
        if self._train_step_fn is None:
            self._train_step_fn = self._build_train_step()
            # the first call traces, lowers and compiles (or loads from
            # the persistent cache): on the timeline under its own tag
            with phase_span(FIRST_CALL_PHASE + "train_step"):
                return self._train_step_fn(params, opt_state, state, rng,
                                           inputs, labels)
        return self._train_step_fn(params, opt_state, state, rng, inputs, labels)

    def eval_step(self, params, state, inputs, labels):
        if self._eval_step_fn is None:
            self._eval_step_fn = self._build_eval_step()
        return self._eval_step_fn(params, state, inputs, labels)

    def forward_fn(self):
        """(params, state, inputs) -> logits — for export/inspection.
        Jitted once and cached (a fresh closure per call would recompile
        every time)."""
        if getattr(self, "_forward_fn", None) is None:

            @jax.jit
            def fwd(params, state, inputs):
                logits, _ = self.apply(params, state, inputs, None, train=False)
                return logits

            self._forward_fn = fwd
        return self._forward_fn
