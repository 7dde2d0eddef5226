"""The strategy-plan record (flexflow_tpu/search/plan.py).

* format — every ``__meta__`` strategy block round-trips through
  ``to_meta``/``from_meta`` and re-lints clean; the same block, hand-
  corrupted, fails ``relint`` with the dimension's own finding code;
* lowering — ``compile()``, ``recompile()`` and ``swap_strategy()``
  choose the executor from the record, so a model re-lowers as what it
  was;
* no side channel — a search's record is its own: an unrelated search
  run between a search and the use of its record changes nothing in it.
"""

import copy
import dataclasses

import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu.analysis import AnalysisError
from flexflow_tpu.compiler.lowering import (
    CompiledModel,
    data_parallel_strategy,
)
from flexflow_tpu.core.machine import MachineView
from flexflow_tpu.search.plan import StrategyPlan

N_DEV = 8


def _mlp(**cfg_kw):
    cfg = ff.FFConfig(batch_size=16, num_devices=N_DEV,
                      compute_dtype="float32", cost_cache_file="",
                      **cfg_kw)
    m = ff.FFModel(cfg)
    t = m.create_tensor([16, 32], name="sp_x")
    for i in range(4):
        t = m.dense(t, 32, activation="relu", name=f"layer{i}_fc")
    m.dense(t, 4, name="sp_head")
    return m, cfg


def _placed():
    cfg = ff.FFConfig(batch_size=16, num_devices=N_DEV,
                      compute_dtype="float32", cost_cache_file="")
    m = ff.FFModel(cfg)
    ids = m.create_tensor([16, 4], dtype="int32", name="pm_ids")
    e = m.embedding(ids, 64, 8, name="pm_emb")
    h = m.flat(e, name="pm_flat")
    h = m.dense(h, 32, activation="relu", name="pm_mlp")
    m.dense(h, 4, name="pm_head")
    strat = {}
    for node in m.graph.topo_order():
        nd = node.op.output_shapes[0].ndim
        if node.op.name in ("pm_mlp", "pm_head"):
            strat[node.guid] = MachineView(
                dim_degrees=(4,) + (1,) * (nd - 1), start_part=4)
        else:
            strat[node.guid] = (
                node.op.fixed_machine_view()
                or MachineView(dim_degrees=(4,) + (1,) * (nd - 1)))
    return m, cfg, strat


DECODE_KW = dict(vocab=256, num_layers=2, hidden=64, num_heads=4,
                 ff_dim=64, page_size=4, pages_per_seq=8)


def _decode_cfg(**kw):
    return ff.FFConfig(batch_size=8, num_devices=N_DEV, search_budget=0,
                       search_timeout_s=30.0, objective="serve",
                       comp_mode="inference", cost_cache_file="", **kw)


@pytest.fixture(scope="module")
def serve_plan():
    """A searched serve-objective record with the KV lane armed."""
    from flexflow_tpu.models import build_gpt_decode
    from flexflow_tpu.search.driver import search_plan

    cfg = _decode_cfg(kv_precision="search")
    plan = search_plan(build_gpt_decode(cfg, **DECODE_KW).graph, cfg)
    assert plan.source == "searched" and plan.serving and plan.kv
    return cfg, plan


def _geometry(plan):
    sv = plan.serving
    return dict(max_seqs=sv["max_seqs"], page_size=sv["page_size"],
                pages_per_seq=sv["pages_per_seq"])


def _case_placement():
    from flexflow_tpu.analysis import placement_meta

    m, cfg, strat = _placed()
    plan = StrategyPlan(m.graph, strat, "caller",
                        placement=placement_meta(m.graph, strat, cfg))
    return cfg, plan, lambda b: dict(b, blocks=[[0, 2], [2, 6]]), "SHD153"


def _case_pipeline():
    from flexflow_tpu.search.pipeline_search import StagedPipelineProposal

    m, cfg = _mlp()
    topo = [n.guid for n in m.graph.topo_order()]
    plan = StrategyPlan(
        m.graph, data_parallel_strategy(m.graph, N_DEV), "caller",
        staged=StagedPipelineProposal(
            num_stages=2, num_microbatches=4,
            stage_guids=[topo[:3], topo[3:]], cost=1.0,
            executable=False))
    # stage 1 first: an edge crosses BACKWARD between the stages
    return (cfg, plan,
            lambda b: dict(b, stages=list(reversed(b["stages"]))),
            "SHD152")


def _case_serving(serve_plan):
    cfg, plan = serve_plan
    return (cfg, plan,
            lambda b: dict(b, page_size=b["page_size"] * 2), "SHD160")


def _case_kv(serve_plan):
    cfg, plan = serve_plan
    return cfg, plan, lambda b: dict(b, dtype="fp4"), "SHD169"


def _case_disaggregation(serve_plan):
    cfg, base = serve_plan
    block = dict(num_devices=N_DEV, prefill_devices=4, decode_devices=4,
                 chunk=8, prefill_seq_len=16, slo_classes=[],
                 **_geometry(base))
    plan = dataclasses.replace(base, disaggregation=block)
    return cfg, plan, lambda b: dict(b, pages_per_seq=999), "SHD165"


def _case_fleet(serve_plan):
    cfg, base = serve_plan
    block = dict(
        num_devices=N_DEV,
        replicas=[dict(devices=4, start=0, prefill_devices=0,
                       decode_devices=4),
                  dict(devices=4, start=4, prefill_devices=0,
                       decode_devices=4)],
        routing={"default": [0.5, 0.5]},
        slo_classes=[dict(name="default", priority=0, deadline_frames=0,
                          quantile=0.99, weight=1)],
        **_geometry(base))
    plan = dataclasses.replace(base, fleet=block)

    def overlap(b):
        b = copy.deepcopy(b)
        b["replicas"][1]["start"] = 2
        return b

    return cfg, plan, overlap, "SHD166"


def _case_sync_schedule():
    from flexflow_tpu.search.sync_schedule import SyncBucket, SyncSchedule

    m, cfg = _mlp(sync_schedule="search")
    cfg.comp_mode = "training"
    names = [n.op.name for n in m.graph.topo_order()
             if getattr(n.op, "_weight_specs", ())]
    plan = StrategyPlan(
        m.graph, data_parallel_strategy(m.graph, N_DEV), "caller",
        sync_schedule=SyncSchedule([
            SyncBucket("b0", tuple(reversed(names[2:])), "fp32"),
            SyncBucket("b1", tuple(reversed(names[:2])), "fp32")]))

    def unknown_op(b):
        b = copy.deepcopy(b)
        b["buckets"][0]["ops"].append("no_such_op")
        return b

    return cfg, plan, unknown_op, "SHD120"


def _case_zero_groups():
    m, cfg = _mlp()
    cfg.comp_mode = "training"
    plan = StrategyPlan(
        m.graph, data_parallel_strategy(m.graph, N_DEV), "caller",
        zero_groups=("layer0_fc", "layer1_fc"))
    return cfg, plan, lambda b: b + ["no_such_op"], "SHD140"


_CASES = {
    "placement": _case_placement, "pipeline": _case_pipeline,
    "serving": _case_serving, "kv": _case_kv,
    "disaggregation": _case_disaggregation, "fleet": _case_fleet,
    "sync_schedule": _case_sync_schedule, "zero_groups": _case_zero_groups,
}
_NEEDS_SERVE = ("serving", "kv", "disaggregation", "fleet")


@pytest.mark.parametrize("dim", list(_CASES))
def test_block_round_trips_and_its_corruption_is_a_finding(dim, request):
    """``from_meta(to_meta(plan))`` carries the dimension unchanged
    and re-lints clean against the graph it was exported for; the
    hand-corrupted block fails ``relint`` with the dimension's code."""
    args = ((request.getfixturevalue("serve_plan"),)
            if dim in _NEEDS_SERVE else ())
    cfg, plan, corrupt, code = _CASES[dim](*args)
    meta = plan.to_meta()
    assert dim in meta
    back = StrategyPlan.from_meta(meta, plan.graph, plan.strategy, cfg)
    assert back.source == "imported"
    assert back.to_meta() == meta
    # the dimension itself, not only its serialisation
    field = "staged" if dim == "pipeline" else dim
    want, got = getattr(plan, field), getattr(back, field)
    if dim == "pipeline":  # the imported cost is "not re-simulated"
        want, got = want.stage_guids, got.stage_guids
    assert got == want
    if dim != "pipeline":  # nan != nan
        assert StrategyPlan.from_meta(
            back.to_meta(), plan.graph, plan.strategy, cfg) == back
    back.relint(cfg)  # legal as exported

    bad = dict(meta, **{dim: corrupt(copy.deepcopy(meta[dim]))})
    with pytest.raises(AnalysisError) as ei:
        StrategyPlan.from_meta(
            bad, plan.graph, plan.strategy, cfg).relint(cfg)
    assert code in {f.code for f in ei.value.findings}


def test_unarmed_comm_plan_blocks_are_not_adopted():
    """A schedule/zero map in the file is adopted only where the
    config arms it (training, ``sync_schedule="search"``, no
    ``zero_dp_shard``) — elsewhere it is not even parsed."""
    _cfg, plan, _corrupt, _code = _case_sync_schedule()
    meta = dict(plan.to_meta(), zero_groups=["layer0_fc"])
    m, off = _mlp()  # sync_schedule="off"
    off.comp_mode = "training"
    back = StrategyPlan.from_meta(meta, plan.graph, plan.strategy, off)
    assert back.sync_schedule is None
    assert back.zero_groups == ("layer0_fc",)
    off.zero_dp_shard = True
    back = StrategyPlan.from_meta(
        dict(meta, sync_schedule="garbage"), plan.graph, plan.strategy,
        off)
    assert back.sync_schedule is None and back.zero_groups == ()


def _data():
    rng = np.random.default_rng(0)
    return (rng.normal(size=(32, 32)).astype(np.float32),
            rng.integers(0, 4, 32).astype(np.int32))


def test_flat_model_relowers_flat_through_recompile_and_swap(mesh8):
    m, cfg = _mlp(only_data_parallel=True)
    m.compile(loss_type="sparse_categorical_crossentropy", metrics=[])
    assert type(m.compiled) is CompiledModel
    assert m.plan.source == "data_parallel"
    first = m.compiled
    m.recompile()
    assert type(m.compiled) is CompiledModel and m.compiled is not first
    m.swap_strategy(data_parallel_strategy(m.graph, N_DEV))
    assert type(m.compiled) is CompiledModel
    assert m.plan.source == "caller" and m.strategy is m.plan.strategy
    x, y = _data()
    m.fit(x, y, batch_size=16, epochs=1, verbose=False)


def test_pipelined_model_relowers_pipelined(mesh8):
    from flexflow_tpu.compiler.pipeline_lowering import (
        PipelinedCompiledModel,
    )
    from flexflow_tpu.parallel.pipeline import PipelineConfig

    m, cfg = _mlp()
    m.compile(loss_type="sparse_categorical_crossentropy", metrics=[],
              pipeline=PipelineConfig(num_stages=2, num_microbatches=4))
    assert isinstance(m.compiled, PipelinedCompiledModel)
    assert m.plan.pipeline.num_stages == 2
    m.recompile()
    assert isinstance(m.compiled, PipelinedCompiledModel)
    with pytest.raises(NotImplementedError):
        m.swap_strategy(data_parallel_strategy(m.graph, N_DEV))
    assert isinstance(m.compiled, PipelinedCompiledModel)


def test_placed_model_relowers_placed(mesh8):
    from flexflow_tpu.compiler.placement_lowering import (
        PlacedCompiledModel,
    )

    m, cfg, strat = _placed()
    m.compile(loss_type="sparse_categorical_crossentropy", metrics=[],
              strategy=strat)
    assert isinstance(m.compiled, PlacedCompiledModel)
    m.recompile()
    assert isinstance(m.compiled, PlacedCompiledModel)
    with pytest.raises(NotImplementedError):
        m.swap_strategy(data_parallel_strategy(m.graph, N_DEV))
    assert isinstance(m.compiled, PlacedCompiledModel)


def test_swap_refuses_a_placeable_target(mesh8):
    """One chooser lowers a placeable strategy placed — which a live
    swap cannot re-shard into: refused like a placed source, with the
    model left as it was."""
    m, cfg, strat = _placed()
    m.compile(loss_type="sparse_categorical_crossentropy", metrics=[],
              strategy=data_parallel_strategy(m.graph, N_DEV))
    assert type(m.compiled) is CompiledModel
    before = (m.compiled, m.plan)
    with pytest.raises(NotImplementedError):
        m.swap_strategy(strat)
    assert (m.compiled, m.plan) == before


def test_a_search_record_has_no_side_channel(serve_plan):
    """The record is the search's whole answer and nothing reaches it
    afterwards: an unrelated (training, co-searched) search in between
    leaves every field of a serve record as it was, and each record
    keeps its own statistics."""
    from flexflow_tpu.search.driver import LAST_SEARCH_STATS, search_plan

    _cfg, plan = serve_plan
    before = copy.deepcopy(dataclasses.asdict(
        dataclasses.replace(plan, graph=None)))
    m, cfg = _mlp(sync_schedule="search", sync_precision="search",
                  co_search=True, search_budget=2)
    other = search_plan(m.graph, cfg)
    assert other.serving is None and other.kv is None
    assert dataclasses.asdict(
        dataclasses.replace(plan, graph=None)) == before
    assert other.stats is not plan.stats
    assert other.stats is not LAST_SEARCH_STATS
    assert other.stats == LAST_SEARCH_STATS  # the named debt: same numbers
