"""StrategyPlan — the one record a compile decides and a lowering reads.

The search returns it (``driver.search_plan``), ``FFModel.compile``
carries it from its source (caller-supplied / imported / data-parallel
/ searched) through the post-search proposals, the strategy export and
the lowering, and ``FFModel.plan`` keeps it for ``recompile()`` and
``swap_strategy()``.  A new searched dimension is a field here, a row
in ``to_meta``/``from_meta``, and a ``_relint_*`` function.

The record owns three things and nothing else:

* the ``__meta__`` format of the eight strategy blocks — ``to_meta`` /
  ``from_meta`` are the only code that names their keys
  (``search/strategy_io.py`` keeps the file, the digests and the
  views);
* ``relint`` — one loop over the dimensions present, through the
  ``analysis`` lints, for plans that arrive from OUTSIDE (an imported
  file bypasses the search's always-on gates);
* ``drop_unexecutable`` — the one place that says "this lowering
  cannot execute sync precision / the schedule / the zero map".
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence

from flexflow_tpu.analysis.findings import (
    AnalysisError,
    Finding,
    raise_if_errors,
)
from flexflow_tpu.core.graph import Graph
from flexflow_tpu.core.machine import MachineView

# relint() dimensions, in gate order.  The comm plan (schedule + zero
# map) is linted against the sync-precision map, which compile()
# chooses for a strategy only AFTER the strategy passed its own lints
# — so an import gates the two groups at two points of the pipeline.
STRATEGY_DIMS = ("strategy", "placement", "serving", "kv",
                 "disaggregation", "fleet", "pipeline")
COMM_DIMS = ("sync_schedule", "zero_groups")


@dataclasses.dataclass
class StrategyPlan:
    graph: Graph
    strategy: Dict[int, MachineView]
    source: str  # "caller" | "imported" | "data_parallel" | "searched"
    pipeline: Optional[object] = None  # parallel.pipeline.PipelineConfig
    staged: Optional[object] = None  # the general staged-pipeline
    # candidate (pipeline_search.StagedPipelineProposal) for graphs the
    # stacked executor can't run
    placement: Optional[dict] = None  # device-block frame of a 2-block
    # placed strategy the placed executor runs (lint-clean when set)
    serving: Optional[dict] = None  # serve-objective provenance: SLO
    # budget + frame geometry + predicted p99 + per-device KV residency
    kv: Optional[dict] = None  # KV-lane provenance: pool dtype + scale
    # layout + prefix-sharing residency; None when the lane is unarmed
    disaggregation: Optional[object] = None  # the searched prefill/
    # decode DisaggregationProposal (adopted or honest zero), or the
    # persisted block of an imported one
    fleet: Optional[object] = None  # FleetProposal or imported block
    base_graph: Optional[Graph] = None  # the pre-rewrite graph narrow
    # block re-searches solve on (rewrites bake full-mesh views narrow
    # blocks can't host); None when the search rewrote nothing
    sync_precision: Dict[str, str] = dataclasses.field(default_factory=dict)
    sync_schedule: Optional[object] = None  # sync_schedule.SyncSchedule
    zero_groups: tuple = ()  # op names whose optimizer state shards
    stats: Dict[str, object] = dataclasses.field(default_factory=dict)

    # -- the __meta__ format -------------------------------------------
    def to_meta(self) -> dict:
        """The jsonable strategy blocks of ``__meta__`` (what fflint
        re-checks stdlib-only, STR206-213).  Only what is present and
        ADOPTED persists: honest-zero proposals write nothing."""
        meta: dict = {}
        if self.sync_schedule is not None:
            meta["sync_schedule"] = self.sync_schedule.to_jsonable()
        if self.zero_groups:
            meta["zero_groups"] = sorted(self.zero_groups)
        for key in ("serving", "kv", "placement"):
            if getattr(self, key) is not None:
                meta[key] = getattr(self, key)
        for key in ("disaggregation", "fleet"):
            block = _proposal_block(getattr(self, key))
            if block is not None:
                meta[key] = block
        if self.staged is not None:
            meta["pipeline"] = {
                "num_stages": self.staged.num_stages,
                "num_microbatches": self.staged.num_microbatches,
                "stages": [
                    [self.graph.nodes[g].op.name for g in stage]
                    for stage in self.staged.stage_guids
                ],
            }
        elif self.pipeline is not None:
            meta["pipeline"] = {
                "num_stages": self.pipeline.num_stages,
                "num_microbatches": self.pipeline.num_microbatches,
            }
        return meta

    @classmethod
    def from_meta(cls, meta: dict, graph: Graph,
                  strategy: Dict[int, MachineView], config
                  ) -> "StrategyPlan":
        """The plan an imported strategy file describes (its views are
        ``strategy``, already behind ``import_strategy``'s digest
        gate).  A hand-edited block may carry ANY JSON type: malformed
        shapes raise ``AnalysisError`` here, never a bare TypeError
        out of the gate itself; legality against THIS graph is
        ``relint``'s."""
        plan = cls(graph, strategy, "imported")
        for key in ("placement", "serving", "kv", "disaggregation",
                    "fleet"):
            if meta.get(key) is not None:
                setattr(plan, key, meta[key])
        if meta.get("pipeline") is not None:
            plan._adopt_pipeline_block(meta["pipeline"], config)
        sched_armed, zero_armed = plan.comm_plan_armed(config)
        if sched_armed and meta.get("sync_schedule") is not None:
            from flexflow_tpu.search.sync_schedule import SyncSchedule

            try:
                plan.sync_schedule = SyncSchedule.from_jsonable(
                    meta["sync_schedule"])
            except ValueError as e:
                raise AnalysisError(
                    f"imported strategy file carries a malformed "
                    f"sync_schedule: {e}", []) from e
        if zero_armed and meta.get("zero_groups") is not None:
            zero = meta["zero_groups"]
            if (not isinstance(zero, list)
                    or any(not isinstance(z, str) for z in zero)):
                raise AnalysisError(
                    "imported strategy file carries a malformed "
                    "zero_groups map (expected a list of op names)", [])
            plan.zero_groups = tuple(zero)
        return plan

    def _adopt_pipeline_block(self, block, config) -> None:
        """Form-check ``__meta__.pipeline`` (SHD150) and ADOPT it, not
        just check it: an export whose compile ran the staged executor
        must round-trip to the staged executor (an import that re-lints
        but silently lowers flat would defeat the proposal it
        validated — e.g. the HBM-infeasible regime staged pipelining
        exists for)."""
        def malformed(message):
            return AnalysisError(
                "imported pipeline proposal is illegal for this "
                "graph/strategy",
                [Finding(code="SHD150", pass_name="placement",
                         message=message)])

        if not isinstance(block, dict):
            raise malformed("imported __meta__.pipeline is not an object")
        ns = block.get("num_stages", 0)
        nm = block.get("num_microbatches", 0)
        stages = block.get("stages")
        if (not isinstance(ns, int) or not isinstance(nm, int)
                or isinstance(ns, bool) or isinstance(nm, bool)):
            raise malformed(
                f"imported __meta__.pipeline has non-integer "
                f"num_stages/num_microbatches ({ns!r}, {nm!r})")
        if stages is None:
            # S x M without explicit stages = the stacked-block shape;
            # adopted exactly as if the user had passed
            # compile(pipeline=...)
            from flexflow_tpu.parallel.pipeline import PipelineConfig

            if config.zero_dp_shard:
                # compile(pipeline=)'s contract, re-raised rather than
                # silently leaving optimizer state replicated
                raise NotImplementedError(
                    "zero_dp_shard is not supported with an imported "
                    "pipeline proposal")
            self.pipeline = PipelineConfig(
                num_stages=ns, num_microbatches=nm)
            return
        if not (isinstance(stages, list)
                and all(isinstance(s, list)
                        and all(isinstance(op, str) for op in s)
                        for s in stages)):
            raise malformed(
                "imported __meta__.pipeline stages is not a list of "
                "op-name lists")
        from flexflow_tpu.search.pipeline_search import (
            StagedPipelineProposal,
        )

        by_name = {n.op.name: n.guid for n in self.graph.topo_order()}
        self.staged = StagedPipelineProposal(
            num_stages=ns, num_microbatches=nm,
            stage_guids=[[by_name.get(op, -1) for op in stage]
                         for stage in stages],
            cost=float("nan"),  # not re-simulated here
            executable=False,
        )

    def comm_plan_armed(self, config):
        """(schedule armed, zero map armed): the comm plan exists only
        for a training step the flat lowering syncs itself — pipelined
        lowerings manage their own grad paths, and the global
        ``config.zero_dp_shard`` flag arms every op, so the per-group
        map is ignored under it."""
        base = (config.comp_mode == "training" and bool(self.strategy)
                and self.pipeline is None)
        return (
            base and getattr(config, "sync_schedule", "off") == "search",
            base and not config.zero_dp_shard,
        )

    # -- legality of a plan from outside -------------------------------
    def relint(self, config, dims: Sequence[str] = STRATEGY_DIMS + COMM_DIMS
               ) -> None:
        """Re-lint every dimension of ``dims`` this plan carries
        against THIS graph/strategy/mesh: a hand-edited or re-targeted
        artifact fails with findings at import, not inside the
        lowering, the executor or XLA."""
        present = self.to_meta()  # what persists is what is carried
        for dim in dims:
            if dim == "strategy" or dim in present:
                _RELINT[dim](self, config)

    # -- what the lowering could not take -------------------------------
    def tie_views(self) -> None:
        """One view for the ops of one ``weights_key``: an op that
        reads another's weights (``weights_of``) takes its owner's
        ``MachineView`` — same op type on the same shapes, so always
        legal — instead of having the weights re-sharded to its own in
        every step, unpriced (a sharer declares no weights, so the
        search saw none).  Only within one device block: a placed
        strategy's blocks stay as they were proposed.
        ``stats["tied_views_moved"]`` counts them."""
        if not self.strategy:
            return
        owners = {n.op.name: n for n in self.graph.nodes.values()}
        moved = 0
        for node in self.graph.nodes.values():
            owner = owners.get(node.op.weights_key)
            if (owner is None or owner is node
                    or type(owner.op) is not type(node.op)
                    or owner.op.input_shapes != node.op.input_shapes):
                continue
            view, own = (self.strategy.get(n.guid) for n in (owner, node))
            if (view is not None and own != view
                    and (own is None or own.start_part == view.start_part)):
                self.strategy[node.guid] = view
                moved += 1
        self.stats["tied_views_moved"] = moved

    def drop_unexecutable(self, compiled) -> None:
        """Placed/pipelined lowerings manage their own grad paths and
        placement and do not run ``_sync_grads``: say so rather than
        silently train at fp32 / leave optimizer state replicated /
        sync monolithically while the user expects the searched plan —
        and clear what did not execute, so the record stays what ran."""
        from flexflow_tpu.utils.logging import SEARCH_LOG

        name = type(compiled).__name__
        if self.sync_precision and not getattr(
                compiled, "sync_precision", None):
            SEARCH_LOG.log(
                f"sync_precision={compiled.config.sync_precision!r} chose "
                f"{len(self.sync_precision)} compressed groups but "
                f"this lowering ({name}) cannot "
                f"execute them; gradients sync at fp32"
            )
            self.sync_precision = {}
        if self.zero_groups and getattr(
                compiled, "zero_groups", None) is None:
            SEARCH_LOG.log(
                f"co-search chose {len(self.zero_groups)} "
                f"optimizer-sharded group(s) but this lowering "
                f"({name}) cannot execute the "
                f"per-group map; optimizer state stays replicated"
            )
            self.zero_groups = ()
        if self.sync_schedule is not None and getattr(
                compiled, "sync_schedule", None) is None:
            SEARCH_LOG.log(
                f"sync_schedule chose {len(self.sync_schedule.buckets)} "
                f"buckets but this lowering "
                f"({name}) cannot execute them; "
                f"gradients sync monolithically"
            )
            self.sync_schedule = None


def _proposal_block(proposal) -> Optional[dict]:
    """The persisted block of a disaggregation/fleet dimension: an
    imported block as it came, a searched proposal's ``to_meta()``
    when ADOPTED (already SHD164-167 gated at proposal time) — honest
    zeros persist nothing."""
    if hasattr(proposal, "adopted"):
        return proposal.to_meta() if proposal.adopted else None
    return proposal


def _lint_cost_model(config, **kw):
    from flexflow_tpu.search.machine_model import CostModel

    return CostModel(config.machine_spec,
                     num_devices=config.search_devices, **kw)


def _serving_spec(plan: StrategyPlan):
    """The ServingSpec an imported ``__meta__.serving`` block states —
    shared by the serving and the kv re-lint."""
    from flexflow_tpu.search.serving import ServingSpec

    sv = plan.serving
    if sv is None:
        return None
    try:
        return ServingSpec(
            max_seqs=int(sv["max_seqs"]),
            page_size=int(sv["page_size"]),
            pages_per_seq=int(sv["pages_per_seq"]),
            p99_budget_ms=float(sv.get("p99_budget_ms", 0.0)),
            quantile=float(sv.get("quantile", 0.99)),
            # residency was ranked under the kv block's prefix sharing
            # (when present): the SHD161 re-proof must price the same
            # pool
            shared_prefix_pages=int(
                (plan.kv or {}).get("shared_prefix_pages", 0) or 0),
        )
    except (KeyError, TypeError, ValueError) as e:
        raise AnalysisError(
            f"imported strategy file carries a malformed "
            f"__meta__.serving block: {e}", []) from e


def _relint_strategy(plan, config):
    from flexflow_tpu.analysis import lint_strategy

    raise_if_errors(
        lint_strategy(plan.graph, plan.strategy, config.num_devices),
        f"imported strategy {config.import_strategy_file!r} is illegal "
        f"for this graph/mesh")


def _relint_placement(plan, config):
    # pipeline/placement proposal provenance rides the strategy's
    # digest gate — re-lint against THIS graph/strategy so a
    # hand-edited proposal block fails with a finding at import, not
    # inside the placed/staged lowering (analysis/placement.py
    # SHD150-155)
    from flexflow_tpu.analysis import errors_only, lint_placement, placement_meta

    bad = errors_only(lint_placement(plan.graph, plan.strategy, config))
    if not bad and placement_meta(
            plan.graph, plan.strategy, config) != plan.placement:
        bad = [Finding(
            code="SHD153", pass_name="placement",
            message=(
                "imported __meta__.placement block frame disagrees "
                "with the device blocks the strategy's start_part "
                "views actually form"))]
    raise_if_errors(
        bad, "imported placement proposal is illegal for this "
        "graph/strategy")


def _relint_serving(plan, config):
    from flexflow_tpu.analysis import lint_serving

    spec = _serving_spec(plan)
    # inference=... must MATCH the producing gate's cost model (the
    # search ran under comp_mode=inference): a training-mode CostModel
    # counts activations 2x and would SHD161-reject legal
    # near-capacity artifacts the search-time gate passed; serving=
    # arms the same shared-residency discount
    raise_if_errors(
        lint_serving(
            plan.graph, plan.strategy, spec,
            _lint_cost_model(config,
                             inference=config.comp_mode == "inference",
                             serving=spec)),
        "imported serving provenance is illegal for this "
        "graph/strategy")


def _relint_kv(plan, config):
    # BEFORE the pool dtype is adopted onto the decode ops (SHD168/169)
    from flexflow_tpu.analysis import lint_kv

    raise_if_errors(
        lint_kv(plan.graph, plan.strategy, plan.kv,
                serving=_serving_spec(plan)),
        "imported __meta__.kv block is illegal for this "
        "graph/strategy")


def _relint_disaggregation(plan, config):
    # the persisted pool geometry must agree with the target's decode
    # ops and the shared-parameter-set bridge must still hold
    # (SHD164/165)
    from flexflow_tpu.analysis import lint_disaggregation

    raise_if_errors(
        lint_disaggregation(
            plan.graph, _proposal_block(plan.disaggregation), config),
        "imported disaggregation proposal is illegal for this graph")


def _relint_fleet(plan, config):
    # replica blocks must tile the mesh disjointly, routing must cover
    # every SLO class, and the persisted pool geometry must agree with
    # the target's decode ops (SHD166/167)
    from flexflow_tpu.analysis import lint_fleet

    raise_if_errors(
        lint_fleet(plan.graph, _proposal_block(plan.fleet), config),
        "imported fleet proposal is illegal for this graph")


def _relint_pipeline(plan, config):
    from flexflow_tpu.analysis import lint_pipeline_stages

    if plan.staged is not None:
        p, stage_guids = plan.staged, plan.staged.stage_guids
    else:
        p, stage_guids = plan.pipeline, None
    raise_if_errors(
        lint_pipeline_stages(plan.graph, stage_guids, p.num_stages,
                             p.num_microbatches, config),
        "imported pipeline proposal is illegal for this graph/strategy")


def _relint_sync_schedule(plan, config):
    from flexflow_tpu.analysis import lint_reduction_plan, lint_sync_schedule

    raise_if_errors(
        lint_sync_schedule(plan.graph, plan.strategy, plan.sync_schedule,
                           plan.sync_precision)
        + lint_reduction_plan(plan.graph, plan.strategy,
                              plan.sync_schedule,
                              _lint_cost_model(config)),
        "imported sync_schedule is illegal for this graph/strategy")


def _relint_zero_groups(plan, config):
    from flexflow_tpu.analysis import lint_zero_map

    raise_if_errors(
        lint_zero_map(plan.graph, plan.strategy, list(plan.zero_groups),
                      _lint_cost_model(config)),
        "imported zero_groups map is illegal for this graph/strategy")


_RELINT = {
    "strategy": _relint_strategy,
    "placement": _relint_placement,
    "serving": _relint_serving,
    "kv": _relint_kv,
    "disaggregation": _relint_disaggregation,
    "fleet": _relint_fleet,
    "pipeline": _relint_pipeline,
    "sync_schedule": _relint_sync_schedule,
    "zero_groups": _relint_zero_groups,
}
