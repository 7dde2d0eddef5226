"""Static analysis for the PCG pipeline — the correctness layer that
PROVES what the rest of the system assumes (reference inspiration:
GSPMD's decidable sharding propagation, arXiv:2105.04663; placement
legality as a constraint system, arXiv:2110.10548).

Three passes, one finding vocabulary (``findings.py``):

1. ``invariants``  — graph well-formedness after every rewrite
   (``PCG0xx``), armed by ``FLEXFLOW_TPU_VERIFY=1`` / ``--verify``.
2. ``equivalence`` — executable numeric proofs for the substitution
   registry (``EQV3xx``); ``proofgen`` generates the proof graphs
   from each rewrite's own matcher contract (EQV305 closed by
   construction for factory xfers, EQV306 reports unproven rules).
3. ``sharding``    — strategy/MachineView legality + search/lowering
   coherence (``SHD1xx``), the always-on gate in ``optimize_strategy``.
4. ``placement``   — pipeline stage cuts and ``start_part`` device
   blocks (``SHD150``-``SHD155``), the always-on gate on every
   pipeline/placement proposal the search returns, persists or
   imports.
5. ``swap``        — hot-swap legality (``SHD170``-``SHD172``): a live
   mid-run strategy swap must preserve every weight/op-state shape and
   cover the target graph, the always-on gate of
   ``FFModel.swap_strategy`` / the always-on training controller.

``tools/fflint.py`` exposes all of it as a CI-friendly CLI; findings
also flow through the obs event bus as ``analysis.finding`` events.

``equivalence`` and ``proofgen`` are intentionally NOT imported here:
they import the substitution machinery, which itself imports
``invariants`` — load them explicitly
(``from flexflow_tpu.analysis.equivalence import …``).
"""

from flexflow_tpu.analysis.findings import (
    AnalysisError,
    Finding,
    emit_findings,
    errors_only,
    raise_if_errors,
)
from flexflow_tpu.analysis.invariants import (
    CHECK_STATS,
    GraphInvariantError,
    assert_graph_ok,
    check_graph,
    scoped_verify,
    set_verify,
    verification_enabled,
)
from flexflow_tpu.analysis.placement import (
    lint_pipeline_stages,
    lint_placement,
    placement_meta,
)
from flexflow_tpu.analysis.sharding import (
    lint_disaggregation,
    lint_fleet,
    lint_kv,
    lint_reduction_plan,
    lint_serving,
    lint_strategy,
    lint_sync_schedule,
    lint_zero_map,
)
from flexflow_tpu.analysis.swap import lint_swap

__all__ = [
    "AnalysisError",
    "Finding",
    "emit_findings",
    "errors_only",
    "raise_if_errors",
    "CHECK_STATS",
    "GraphInvariantError",
    "assert_graph_ok",
    "check_graph",
    "scoped_verify",
    "set_verify",
    "verification_enabled",
    "lint_disaggregation",
    "lint_fleet",
    "lint_kv",
    "lint_pipeline_stages",
    "lint_placement",
    "lint_reduction_plan",
    "lint_serving",
    "lint_strategy",
    "lint_swap",
    "lint_sync_schedule",
    "lint_zero_map",
    "placement_meta",
]
