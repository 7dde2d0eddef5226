"""The executor choice — one function for ``compile()``, ``recompile()``
and ``swap_strategy()``.

Four executors run one graph (ROADMAP queue 3 item 4): the flat SPMD
program (``lowering.CompiledModel``), two disjoint device blocks
(``placement_lowering``), the stacked-block scan pipeline
(``pipeline_lowering``) and the staged wavefront over arbitrary cuts
(``staged_pipeline_lowering``).  Which one a plan gets is decided here
and nowhere else, from the record alone (search/plan.py), so a model
re-lowers as what it was.
"""

from __future__ import annotations

from flexflow_tpu.analysis import (
    errors_only,
    lint_placement,
    placement_meta,
    raise_if_errors,
)
from flexflow_tpu.compiler.placement_lowering import placeable


def _placed(plan, config, mesh) -> bool:
    # mesh is None: a user-supplied mesh commits the whole graph to one
    # submesh program, which a 2-block placed strategy cannot honor —
    # the flat lowering (which respects mesh=) runs instead of silently
    # ignoring it.  Multi-block strategies OUTSIDE the placed lowering's
    # support (>2 blocks, multi-tensor cuts, grad accumulation) keep the
    # historical behavior: offsets are inert and the single SPMD
    # program replicates small-degree ops.
    return bool(plan.pipeline is None and mesh is None and plan.strategy
                and placeable(plan.graph, plan.strategy, config))


def placement_frame(plan, config, mesh):
    """The ``__meta__.placement`` frame of a cut the placed executor
    WILL run, or None.  ``lower`` requires pipeline/mesh unset AND
    placeable, and the frame must pass the same legality gate it
    enforces — a compile that will fail that gate (or run flat under
    mesh=) must not leave a placement artifact on disk.  Inert
    multi-block strategies persist no frame either."""
    if not _placed(plan, config, mesh) or errors_only(
            lint_placement(plan.graph, plan.strategy, config)):
        return None
    return placement_meta(plan.graph, plan.strategy, config)


def lower(plan, config, loss, metrics, optimizer, mesh=None, block_of=None):
    """Lower ``plan`` through the executor it calls for."""
    from flexflow_tpu.compiler import lowering

    graph, strategy = plan.graph, plan.strategy
    if _placed(plan, config, mesh):
        # disjoint start_part device blocks the placed lowering can
        # express: EXECUTED inter-op placement (reference:
        # mapper.cc:371-475 places ops on disjoint device sets and
        # Legion runs them)
        from flexflow_tpu.compiler.placement_lowering import (
            PlacedCompiledModel,
        )

        if plan.placement is None:
            # always-on legality gate on the cut about to execute
            # (search proposals were gated at proposal time; this also
            # covers caller-supplied placed strategies with findings
            # instead of opaque lowering errors).  A frame on the plan
            # passed this lint when it was put there (export, import).
            raise_if_errors(
                lint_placement(graph, strategy, config),
                "placed strategy is illegal for this graph/mesh")
        return PlacedCompiledModel(
            graph, strategy, config, loss, metrics, optimizer)
    if plan.pipeline is not None:
        from flexflow_tpu.compiler.pipeline_lowering import (
            PipelinedCompiledModel,
        )

        return PipelinedCompiledModel(
            graph, strategy, config, loss, metrics, optimizer,
            pipeline=plan.pipeline, block_of=block_of)
    if (plan.staged is not None and mesh is None
            and config.comp_mode == "training"):
        # flat is infeasible and the general staged proposal won: GPipe
        # over arbitrary graph cuts
        from flexflow_tpu.compiler.staged_pipeline_lowering import (
            StagedPipelinedModel,
        )

        try:
            return StagedPipelinedModel(
                graph, plan.staged.stage_guids,
                plan.staged.num_microbatches, config, loss, metrics,
                optimizer)
        except (NotImplementedError, ValueError):
            # stateful stages, several processes etc.: keep the flat
            # lowering (the proposal stays surfaced on the plan)
            pass
    return lowering.CompiledModel(
        graph, strategy, config, loss, metrics, optimizer, mesh=mesh,
        sync_precision=plan.sync_precision,
        sync_schedule=plan.sync_schedule, zero_groups=plan.zero_groups)
