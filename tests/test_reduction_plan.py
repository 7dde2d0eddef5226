"""Hierarchical machine model + searched per-group reduction plans
(the multi-slice vertical slice): N-level link hierarchy, staged
pricing, plan search, legality lint, staged execution, persistence.

Contracts:

* flat regression — a single-level (flat) machine enumerates NO plans:
  pricing, schedule choice and search behavior are bit-identical to
  the plan-free tree (the PR's hard gate);
* hierarchy pricing — collective costs decompose over the level
  structure (level splits sum exactly to the scalar cost), and on a
  2-slice machine with a 10x ICI/DCN gap the searched staged plan
  beats the flat allreduce on the DP sync term by >= 2x (THE
  acceptance number);
* execution — fp32 staged plans are BIT-EXACT with the flat
  ``_sync_grads`` path (composing with bucketing and ZeRO-1), the
  compressed staged path runs real nested collectives and stays close
  to fp32;
* persistence — plans round-trip through the strategy file's
  ``__meta__`` behind the digest gate and fflint checks them
  stdlib-only (STR206).
"""

import dataclasses
import json
import math

import numpy as np
import pytest

import flexflow_tpu as ff
from zoo import SYNC_BOUND_BERT_KW, assert_int8_weights_close
from flexflow_tpu.compiler.lowering import data_parallel_strategy
from flexflow_tpu.core.machine import MachineSpec
from flexflow_tpu.search.machine_model import CostModel
from flexflow_tpu.search.reduction_plan import (
    ReductionPlan,
    assign_reduction_plans,
    canonical_stages,
    enumerate_reduction_plans,
    validate_stages,
)
from flexflow_tpu.search.simulator import Simulator
from flexflow_tpu.search.sync_schedule import (
    SyncSchedule,
    build_bucketed_schedule,
    choose_sync_schedule,
    synced_weight_groups,
)


def _two_slice(n=8, gap=10.0):
    base = MachineSpec.tpu_v5e(n)
    return dataclasses.replace(
        base, devices_per_host=n // 2,
        dcn_bandwidth=base.ici_bandwidth / gap)


def _bert_graph(n=8):
    from flexflow_tpu.models import build_transformer

    cfg = ff.FFConfig(batch_size=8, num_devices=n)
    return build_transformer(cfg, **SYNC_BOUND_BERT_KW).graph


# ---------------------------------------------------------------------------
# machine model: link hierarchy
def test_flat_machine_is_single_level():
    cm = CostModel(MachineSpec.tpu_v5e(8), num_devices=8)
    assert len(cm.levels()) == 1
    assert cm.levels()[0].name == "ici"
    assert enumerate_reduction_plans(1, "int8") == []


def test_two_slice_levels_and_axis_classification():
    cm = CostModel(_two_slice(), num_devices=8)
    levels = cm.levels()
    assert [lvl.name for lvl in levels] == ["ici", "dcn"]
    assert levels[1].bandwidth == pytest.approx(levels[0].bandwidth / 10)
    # aligned-span rule: spans 1,2,4 fit the 4-device slice; 8 crosses
    assert cm._axis_level(4) == 0 and cm._axis_level(8) == 1


def test_n_level_spec_roundtrip_and_levels(tmp_path):
    """3-level hierarchy (slice -> superpod -> machine) survives the
    machine-config file round trip and prices recursively."""
    spec = dataclasses.replace(
        MachineSpec.tpu_v5e(16), devices_per_host=2,
        slice_levels=((4, 5e9, 5e-6), (16, 1e9, 2e-5)))
    path = str(tmp_path / "machine.json")
    spec.to_file(path)
    back = MachineSpec.from_file(path)
    assert back == spec
    cm = CostModel(spec, num_devices=16)
    assert [lvl.name for lvl in cm.levels()] == ["ici", "dcn1", "dcn2"]
    # a 3-level staged plan prices every level once and beats flat
    factors = (2, 2, 4)
    flat = cm.allreduce(1 << 24, 16, spans_dcn=2)
    staged = cm.staged_sync_cost(
        float(1 << 24), factors, ("fp32", "fp32", "fp32"))
    assert 0 < staged < flat
    # the misconfigured (non-nesting) hierarchy is rejected loudly
    bad = dataclasses.replace(spec, slice_levels=((3, 5e9, 5e-6),))
    with pytest.raises(ValueError):
        bad.topology_levels()


def test_level_split_sums_to_scalar_cost():
    cm = CostModel(_two_slice(), num_devices=8)
    for prec in (None, "int8"):
        for spans in (0, 1):
            total = cm.allreduce(1 << 22, 8, spans, precision=prec)
            split = cm.allreduce_level_split(
                1 << 22, 8, spans, precision=prec)
            assert sum(split.values()) == pytest.approx(total, rel=1e-12)
            if spans:
                assert split["dcn"] > 0
            else:
                assert split.get("dcn", 0.0) == 0.0


def test_staged_sync_cost_beats_flat_on_two_slice():
    """The core hierarchical win: RS-within/AR-across/AG-within moves
    only the 1/f0 shard over DCN, so the staged cost beats the flat
    ring that drags the full payload across the slow links."""
    cm = CostModel(_two_slice(), num_devices=8)
    nbytes = float(1 << 24)
    flat = cm.allreduce(nbytes, 8, spans_dcn=1)
    staged = cm.staged_sync_cost(nbytes, (4, 2), ("fp32", "fp32"))
    assert staged < flat / 2, (flat, staged)
    # and on ONE slice the staged shape cannot beat flat (no slow link
    # to dodge: same ici currency + extra stages)
    flat_in = cm.allreduce(nbytes, 4, spans_dcn=0)
    staged_in = cm.staged_sync_cost(nbytes, (4, 1), ("fp32", "fp32"))
    assert staged_in >= flat_in * 0.99


def test_replica_level_split_matches_axis_assignment():
    cm = CostModel(_two_slice(), num_devices=8)
    # DP-8 weight sync rides all three mesh axes: x0 (stride 4, span 8)
    # crosses the slice, x1/x2 stay inside -> (4, 2)
    key = ((8, 1), (0,))
    assert cm.replica_level_split(key, 8) == (4, 2)
    # DP-2 rides only the outer axis -> (1, 2)
    assert cm.replica_level_split(((2, 1), (0,)), 2) == (1, 2)
    # an inner 4-way group stays within the slice -> (4, 1)
    assert cm.replica_level_split(((2, 4), (1,)), 4) == (4, 1)


# ---------------------------------------------------------------------------
# plan enumeration + schedule search
def test_plan_enumeration_and_validation():
    plans = enumerate_reduction_plans(2, "int8")
    names = {p.name for p in plans}
    assert names == {"staged_l1", "staged_l1_int8"}
    for p in plans:
        assert validate_stages(p.stages, 2) == []
        assert p.cross_level == 1
    # fp32 bucket: only the all-fp32 staged shape (per-level precision
    # must compose with the sync-precision map, never contradict it)
    assert {p.name for p in enumerate_reduction_plans(2, "fp32")} == \
        {"staged_l1"}
    # malformed shapes are caught
    assert validate_stages(canonical_stages(1, "int8")[:-1], 2)
    bad = ReductionPlan("x", canonical_stages(5, "fp32"))
    assert validate_stages(bad.stages, 2)


def test_plan_jsonable_roundtrip():
    plan = ReductionPlan("staged_l1_int8", canonical_stages(1, "int8"))
    sched = SyncSchedule([
        __import__("flexflow_tpu.search.sync_schedule",
                   fromlist=["SyncBucket"]).SyncBucket(
            "b0", ("fc1",), "int8", plan)])
    back = SyncSchedule.from_jsonable(sched.to_jsonable())
    assert back.buckets[0].plan == plan
    with pytest.raises(ValueError):
        ReductionPlan.from_jsonable({"name": "x", "stages": [
            {"kind": "teleport", "level": 0}]})


def test_flat_machine_choice_is_plan_free_and_unchanged():
    """The bit-identical flat gate at the choose level: on a flat
    machine the plan dimension must neither attach plans nor perturb
    the chosen schedule or its cost."""
    g = _bert_graph()
    dp = data_parallel_strategy(g, 8)
    sim = Simulator(MachineSpec.tpu_v5e(8), num_devices=8)
    sched, info = choose_sync_schedule(
        g, dp, sim, {}, ff.FFConfig(batch_size=8, num_devices=8))
    assert sched is not None
    assert info["staged_buckets"] == 0
    assert all(b.plan is None for b in sched.buckets)
    synced = synced_weight_groups(g, dp, sim.cost)
    assert assign_reduction_plans(sched, synced, sim.cost)[0] is None


def test_searched_plan_beats_flat_2x_on_two_slice():
    """THE acceptance number: on a simulated 2-slice topology with a
    10x ICI/DCN gap, the searched staged reduction plan beats the flat
    allreduce on the DP sync term by >= 2x for the sync-bound BERT."""
    g = _bert_graph()
    dp = data_parallel_strategy(g, 8)
    sim = Simulator(_two_slice(), num_devices=8)
    cfg = ff.FFConfig(batch_size=8, num_devices=8)
    synced = synced_weight_groups(g, dp, sim.cost)
    mono = build_bucketed_schedule(synced, {}, math.inf)
    bd_flat = {}
    c_flat = sim.simulate(g, dp, breakdown=bd_flat, sync_schedule=mono)
    sched, info = choose_sync_schedule(g, dp, sim, {}, cfg)
    assert sched is not None and info["staged_buckets"] >= 1
    assert any(b.plan is not None for b in sched.buckets)
    bd = {}
    c = sim.simulate(g, dp, breakdown=bd, sync_schedule=sched)
    assert bd_flat["sync_total_s"] >= 2.0 * bd["sync_total_s"], (
        bd_flat["sync_total_s"], bd["sync_total_s"])
    assert c < c_flat
    # per-level lanes: the DCN share shrank by the within-slice factor
    assert bd["sync_levels_s"]["dcn"] < \
        bd_flat["sync_levels_s"]["dcn"] / 2
    # bucket rows carry the plan + level split, summing to the cost
    for row in bd["sync_buckets"]:
        assert sum(row["levels"].values()) == pytest.approx(
            row["sync_s"], rel=1e-9)
        if row["plan"]:
            assert row["plan"].startswith("staged_l1")


def test_three_level_choice_reaches_deepest_level():
    """On a 3-level machine the searched plan must reach EXACTLY the
    deepest level the groups span (cross_level 2) — a shallower plan
    would price the coarse links wrong and the always-on lint gate
    (SHD131) would reject the search's own choice, aborting compile."""
    from flexflow_tpu.analysis import lint_reduction_plan

    g = _bert_graph()
    dp = data_parallel_strategy(g, 8)
    spec3 = dataclasses.replace(
        MachineSpec.tpu_v5e(8), devices_per_host=2,
        slice_levels=((4, 5e9, 5e-6), (8, 5e8, 2e-5)))
    sim = Simulator(spec3, num_devices=8)
    sched, info = choose_sync_schedule(
        g, dp, sim, {}, ff.FFConfig(batch_size=8, num_devices=8))
    assert sched is not None and info["staged_buckets"] >= 1
    planned = [b for b in sched.buckets if b.plan is not None]
    assert planned and all(b.plan.cross_level == 2 for b in planned)
    assert lint_reduction_plan(g, dp, sched, sim.cost) == []
    # pricing refuses to stage a group at a plan that does not reach
    # its deepest spanned level (falls back to flat — the executed
    # shape), so a too-shallow candidate can never undercut the legal
    # one
    from flexflow_tpu.search.sync_schedule import synced_weight_groups

    synced = synced_weight_groups(g, dp, sim.cost)
    parts = [p for _n, _mv, ps in synced for p in ps]
    shallow = ReductionPlan("staged_l1", canonical_stages(1, "fp32"))
    flat = sim.cost.bucket_sync_cost(parts, "fp32")
    assert sim.cost.bucket_sync_cost(parts, "fp32", plan=shallow) == \
        pytest.approx(flat)


def test_plan_composes_with_int8_precision_map():
    """Under sync_precision='search' on the 2-slice machine the cross
    stage may compress: int8 over DCN composes with the map."""
    g = _bert_graph()
    dp = data_parallel_strategy(g, 8)
    sim = Simulator(_two_slice(), num_devices=8, sync_precision="search")
    from flexflow_tpu.search.sync_precision import choose_sync_precision

    pmap = choose_sync_precision(g, dp, sim.cost)
    assert pmap, "sync-bound BERT must compress some groups"
    cfg = ff.FFConfig(batch_size=8, num_devices=8,
                      sync_precision="search")
    sched, info = choose_sync_schedule(g, dp, sim, pmap, cfg)
    assert sched is not None and info["staged_buckets"] >= 1
    planned = [b for b in sched.buckets if b.plan is not None]
    # compressed buckets pick the compressed cross stage (int8 over
    # DCN beats fp32 over DCN beats the flat ring)
    assert any(
        b.precision == "int8" and b.plan.name.endswith("int8")
        for b in planned), [(b.precision, b.plan.name) for b in planned]


def test_drift_report_carries_level_lanes():
    from flexflow_tpu.obs.drift import build_drift_report

    g = _bert_graph()
    dp = data_parallel_strategy(g, 8)
    sim = Simulator(_two_slice(), num_devices=8)
    sched, _ = choose_sync_schedule(
        g, dp, sim, {}, ff.FFConfig(batch_size=8, num_devices=8))
    bd = {}
    sim.simulate(g, dp, breakdown=bd, sync_schedule=sched)
    rep = build_drift_report(bd, measured_step_s=bd["total_s"] * 1.2)
    d = rep.to_dict()
    assert d["phases"]["sync_ici"]["predicted_s"] > 0
    assert d["phases"]["sync_dcn"]["predicted_s"] > 0
    assert d["phases"]["sync_dcn"]["measured_s"] is None  # honest
    for row in d["sync_buckets"]:
        assert "predicted_levels_s" in row
    assert any(row["plan"] for row in d["sync_buckets"])


# ---------------------------------------------------------------------------
# legality lint (SHD13x)
def _plan_lint(g, dp, sched, cm):
    from flexflow_tpu.analysis import lint_reduction_plan

    return [f.code for f in lint_reduction_plan(g, dp, sched, cm)]


def test_reduction_plan_lint_clean_and_codes():
    from flexflow_tpu.search.sync_schedule import SyncBucket

    g = _bert_graph()
    dp = data_parallel_strategy(g, 8)
    sim = Simulator(_two_slice(), num_devices=8)
    sched, _ = choose_sync_schedule(
        g, dp, sim, {}, ff.FFConfig(batch_size=8, num_devices=8))
    assert any(b.plan is not None for b in sched.buckets)
    assert _plan_lint(g, dp, sched, sim.cost) == []
    # a plan-free schedule is trivially legal
    assert _plan_lint(g, dp, SyncSchedule(
        [SyncBucket("b0", sched.buckets[0].ops, "fp32")]), sim.cost) == []
    planned = next(b for b in sched.buckets if b.plan is not None)
    # SHD130: non-canonical stage shape
    broken = ReductionPlan("x", planned.plan.stages[:-1])
    b130 = SyncSchedule([dataclasses.replace(planned, plan=broken)])
    assert "SHD130" in _plan_lint(g, dp, b130, sim.cost)
    # SHD131: plan reaching a level the groups do not span — lint on a
    # 3-level machine where the groups only cross level 1
    spec3 = dataclasses.replace(
        MachineSpec.tpu_v5e(8), devices_per_host=2,
        slice_levels=((4, 5e9, 5e-6), (8, 1e9, 2e-5)))
    cm3 = CostModel(spec3, num_devices=8)
    too_shallow = ReductionPlan("x", canonical_stages(1, "fp32"))
    b131 = SyncSchedule([dataclasses.replace(planned, plan=too_shallow)])
    assert "SHD131" in _plan_lint(g, dp, b131, cm3)
    # SHD132: a staged plan whose groups cannot be PROVEN to span a
    # slice boundary — here on a 12-device 2-slice model whose prime
    # pool (2,2,3) the strategy's power-of-two degrees do not factor
    # into, so no replication group resolves to cross-level axes
    spec12 = dataclasses.replace(
        MachineSpec.tpu_v5e(12), devices_per_host=4)
    cm12 = CostModel(spec12, num_devices=12)
    codes = _plan_lint(g, dp, SyncSchedule([planned]), cm12)
    assert "SHD132" in codes, codes
    # SHD133: cross precision contradicting the bucket precision
    comp = ReductionPlan("x", canonical_stages(1, "int8"))
    fp32_bucket = dataclasses.replace(planned, precision="fp32",
                                      plan=comp)
    assert "SHD133" in _plan_lint(
        g, dp, SyncSchedule([fp32_bucket]), sim.cost)


def test_choose_gates_plans_always_on():
    """The builder's always-on gate covers plans: choose_sync_schedule
    must never hand out a schedule whose plans its own lint rejects."""
    g = _bert_graph()
    dp = data_parallel_strategy(g, 8)
    sim = Simulator(_two_slice(), num_devices=8)
    sched, _ = choose_sync_schedule(
        g, dp, sim, {}, ff.FFConfig(batch_size=8, num_devices=8))
    from flexflow_tpu.analysis import (
        lint_reduction_plan,
        lint_sync_schedule,
    )

    assert not lint_sync_schedule(g, dp, sched, {})
    assert not lint_reduction_plan(g, dp, sched, sim.cost)


# ---------------------------------------------------------------------------
# execution: staged shard_map collectives
def _staged_machine_cfg(**kw):
    cfg = ff.FFConfig(batch_size=32, epochs=2, num_devices=8,
                      only_data_parallel=True, compute_dtype="float32",
                      **kw)
    cfg.machine_spec = _two_slice()
    return cfg


def _train_mlp(schedule=None, zero=False, seed=0):
    cfg = _staged_machine_cfg(zero_dp_shard=zero, seed=seed)
    m = ff.FFModel(cfg)
    x = m.create_tensor([32, 64])
    t = m.dense(x, 512, activation="relu", name="fc1")
    t = m.dense(t, 512, activation="relu", name="fc2")
    t = m.dense(t, 8, name="head")
    m.compile(optimizer=ff.AdamOptimizer(alpha=1e-3),
              loss_type="sparse_categorical_crossentropy", metrics=[])
    if schedule is not None:
        m.compiled.sync_schedule = schedule  # lazily jitted: early enough
    rng = np.random.default_rng(0)
    y = rng.integers(0, 8, 128).astype(np.int32)
    xd = rng.normal(size=(128, 64)).astype(np.float32)
    hist = m.fit(x=xd, y=y, verbose=False, shuffle=False)
    return m, hist[-1]["loss"]


def _sched(prec, plan):
    from flexflow_tpu.search.sync_schedule import SyncBucket

    return SyncSchedule([
        SyncBucket("b0", ("head", "fc2"), prec, plan),
        SyncBucket("b1", ("fc1",), prec, plan),
    ])


def test_staged_fp32_bitexact_with_monolithic(mesh8):
    """THE bit-exactness contract: an all-fp32 staged plan executes as
    value-identity anchors (GSPMD's own psum did the reduction), so
    training is bitwise identical to the monolithic ``_sync_grads``."""
    plan = ReductionPlan("staged_l1", canonical_stages(1, "fp32"))
    m_mono, _ = _train_mlp()
    m_plan, _ = _train_mlp(_sched("fp32", plan))
    for op, ws in m_mono.params.items():
        for w, a in ws.items():
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(m_plan.params[op][w]))


def test_staged_int8_close_and_composes_with_zero1(mesh8):
    """The compressed staged path runs the real nested collectives
    (exact RS/AG within the slice, int8 exchange across) and stays
    close to fp32 — composing with ZeRO-1 like the flat bucketed path."""
    plan = ReductionPlan("staged_l1_int8", canonical_stages(1, "int8"))
    m32, l32 = _train_mlp()
    m8, l8 = _train_mlp(_sched("int8", plan), zero=True)
    assert np.isfinite(l8) and np.isclose(l32, l8, rtol=5e-3)
    assert_int8_weights_close(m32.params, m8.params)
    v = m8.opt_state["v"]["fc1"]["kernel"]
    assert v.addressable_shards[0].data.size * 8 == v.size


def test_staged_allreduce_matches_psum(mesh8):
    """Direct collective contract: the staged shape sums like psum —
    exactly at fp32 cross precision, within the quantization error at
    int8 (never worse than the flat int8 collective's bound, since
    only the cross stage touches the value)."""
    from jax.sharding import PartitionSpec as P

    from flexflow_tpu.comm import (
        plan_axis_groups,
        shard_map,
        staged_allreduce,
    )

    rep = tuple(mesh8.axis_names)
    st_axes, st_sizes = plan_axis_groups(rep, mesh8, _two_slice(), 1)
    assert st_sizes == [4, 2]
    rng = np.random.default_rng(3)
    xs = np.asarray(rng.normal(size=(8, 777)).astype(np.float32))

    def run(prec):
        def local(x):
            return staged_allreduce(x[0], st_axes, st_sizes, prec)

        return np.asarray(shard_map(
            local, mesh=mesh8, in_specs=(P(rep),), out_specs=P(),
        )(xs))

    want = xs.sum(axis=0)
    got32 = run("fp32")
    np.testing.assert_allclose(got32, want, rtol=1e-6, atol=1e-5)
    from flexflow_tpu.comm import allreduce_error_bound

    got8 = run("int8")
    err = float(np.max(np.abs(got8 - want)))
    assert err <= allreduce_error_bound(list(xs), "int8"), err


# ---------------------------------------------------------------------------
# persistence + compile integration
def test_plan_roundtrip_through_strategy_file(tmp_path, mesh8):
    """compile() on the 2-slice machine persists the plan inside
    __meta__.sync_schedule; a fresh import adopts it; fflint validates
    it stdlib-only and flags corruption (STR206)."""
    import os
    import subprocess
    import sys

    from flexflow_tpu.models import build_transformer

    path = str(tmp_path / "strategy.json")
    cfg = ff.FFConfig(batch_size=8, num_devices=8,
                      only_data_parallel=True, compute_dtype="float32",
                      sync_schedule="search", export_strategy_file=path)
    cfg.machine_spec = _two_slice()
    m = build_transformer(cfg, **SYNC_BOUND_BERT_KW)
    m.compile(loss_type="mean_squared_error", metrics=[])
    assert m.sync_schedule is not None
    assert any(b.plan is not None for b in m.sync_schedule.buckets)
    data = json.load(open(path))
    persisted = data["__meta__"]["sync_schedule"]
    assert any(b.get("plan") for b in persisted["buckets"])
    back = SyncSchedule.from_jsonable(persisted)
    assert [b.plan.name if b.plan else None for b in back.buckets] == \
        [b.plan.name if b.plan else None for b in m.sync_schedule.buckets]
    # import adopts the plan-carrying schedule behind the digest gate
    cfg2 = ff.FFConfig(batch_size=8, num_devices=8,
                       compute_dtype="float32", sync_schedule="search",
                       import_strategy_file=path)
    cfg2.machine_spec = _two_slice()
    m2 = build_transformer(cfg2, **SYNC_BOUND_BERT_KW)
    m2.compile(loss_type="mean_squared_error", metrics=[])
    assert m2.sync_schedule is not None
    assert any(b.plan is not None for b in m2.sync_schedule.buckets)
    # fflint: clean file passes, corrupted plan fails with STR206
    fflint = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "fflint.py")
    proc = subprocess.run([sys.executable, fflint, "strategy", path],
                          capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    for bucket in data["__meta__"]["sync_schedule"]["buckets"]:
        if bucket.get("plan"):
            bucket["plan"]["stages"][0]["kind"] = "teleport"
            break
    json.dump(data, open(path, "w"))
    proc = subprocess.run([sys.executable, fflint, "strategy", path],
                          capture_output=True, text=True)
    assert proc.returncode == 1 and "STR206" in proc.stdout, proc.stdout
    # and compile refuses the corrupted artifact with a finding
    from flexflow_tpu.analysis import AnalysisError

    cfg3 = ff.FFConfig(batch_size=8, num_devices=8,
                       compute_dtype="float32", sync_schedule="search",
                       import_strategy_file=path)
    cfg3.machine_spec = _two_slice()
    m3 = build_transformer(cfg3, **SYNC_BOUND_BERT_KW)
    with pytest.raises((AnalysisError, ValueError)):
        m3.compile(loss_type="mean_squared_error", metrics=[])
