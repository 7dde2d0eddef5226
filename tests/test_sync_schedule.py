"""Overlap-aware bucketed gradient sync (the sync SCHEDULE vertical
slice): exposed-comm pricing, schedule search, legality lint, bucketed
execution, persistence.

Contracts:

* pricing — ``simulate(sync_schedule=...)``'s comm lanes are
  non-overlapping per device, sum to ``sync_total_s``, and the searched
  schedule's simulated step beats the monolithic schedule on the
  sync-bound BERT config (the acceptance number, simulated);
* execution — the bucketed fp32 path is BIT-EXACT with the monolithic
  ``_sync_grads`` on a multi-group model (CPU mesh), and compressed
  buckets stay numerically close to fp32;
* legality — SHD12x findings for coverage holes, double coverage,
  readiness-violating issue order, precision incoherence; the compile
  path gates imports;
* persistence — the schedule round-trips through the strategy file's
  ``__meta__`` and fflint validates it stdlib-only.
"""

import json
import math

import numpy as np
import pytest

import flexflow_tpu as ff
from zoo import SYNC_BOUND_BERT_KW, assert_int8_weights_close
from flexflow_tpu.compiler.lowering import data_parallel_strategy
from flexflow_tpu.search.simulator import Simulator
from flexflow_tpu.search.sync_schedule import (
    SyncBucket,
    SyncSchedule,
    build_bucketed_schedule,
    choose_sync_schedule,
    synced_weight_groups,
)


def _bert_graph(n=8, batch=8):
    from flexflow_tpu.models import build_transformer

    cfg = ff.FFConfig(batch_size=batch, num_devices=n)
    return build_transformer(cfg, **SYNC_BOUND_BERT_KW).graph


# ---------------------------------------------------------------------------
# cost model decomposition
def test_weight_sync_parts_sum_to_weight_sync_cost():
    """weight_sync_cost must equal the per-part allreduce sum — the
    decomposition the bucket pricing coalesces."""
    g = _bert_graph()
    dp = data_parallel_strategy(g, 8)
    cm = Simulator(ff.FFConfig(batch_size=8, num_devices=8).machine_spec,
                   num_devices=8).cost
    checked = 0
    for node in g.topo_order():
        if not node.op._weight_specs:
            continue
        parts = cm.weight_sync_parts(node.op, dp[node.guid])
        want = cm.weight_sync_cost(node.op, dp[node.guid])
        got = sum(cm.allreduce(b, r, s) for b, r, s, _e, _k in parts)
        assert got == want  # identical arithmetic, not approximately
        checked += 1
    assert checked >= 5


def test_bucket_fusion_amortizes_latency():
    """One fused bucket of k same-group parts must price below k
    separate allreduces (the coalescing reward) and above the single
    biggest part (no free lunch)."""
    cm = Simulator(ff.FFConfig(batch_size=8, num_devices=8).machine_spec,
                   num_devices=8).cost
    parts = [(1 << 20, 8, False, 1 << 18, ((8,), (0,)))] * 4
    fused = cm.bucket_sync_cost(parts)
    separate = sum(cm.allreduce(b, r, s) for b, r, s, _e, _k in parts)
    # parts on a DIFFERENT replication-axes signature do NOT fuse with
    # these (execution runs them as a separate collective)
    mixed = cm.bucket_sync_cost(parts + [(1 << 20, 8, False, 1 << 18,
                                          ((2, 8), (1,)))])
    assert mixed > fused + cm.allreduce(1 << 20, 8, False) * 0.99
    assert fused < separate
    assert fused > cm.allreduce(1 << 20, 8, False)


# ---------------------------------------------------------------------------
# simulator: exposed-comm pricing invariants
def _sim_with_schedule(schedule):
    g = _bert_graph()
    dp = data_parallel_strategy(g, 8)
    sim = Simulator(ff.FFConfig(batch_size=8, num_devices=8).machine_spec,
                    num_devices=8)
    bd, comm = {}, []
    total = sim.simulate(g, dp, breakdown=bd, comm_schedule=comm,
                         sync_schedule=schedule)
    return g, dp, sim, total, bd, comm


def test_comm_lanes_nonoverlapping_and_sum_to_sync_total():
    g = _bert_graph()
    dp = data_parallel_strategy(g, 8)
    sim = Simulator(ff.FFConfig(batch_size=8, num_devices=8).machine_spec,
                    num_devices=8)
    sched, _info = choose_sync_schedule(
        g, dp, sim, {}, ff.FFConfig(batch_size=8, num_devices=8))
    assert sched is not None
    for use in (None, sched):
        bd, comm = {}, []
        sim.simulate(g, dp, breakdown=bd, comm_schedule=comm,
                     sync_schedule=use)
        assert comm, "sync-bound config must emit sync lanes"
        # rows sum to sync_total_s (breakdown contract)
        total_rows = sum(f - s for _n, s, f, _d in comm)
        assert total_rows == pytest.approx(bd["sync_total_s"], rel=1e-12)
        # per-device lanes never overlap (shared ICI serializes)
        by_dev = {}
        for _n, s, f, devs in comm:
            for d in devs:
                by_dev.setdefault(d, []).append((s, f))
        for d, spans in by_dev.items():
            spans.sort()
            for (s0, f0), (s1, f1) in zip(spans, spans[1:]):
                assert s1 >= f0 - 1e-15, (d, spans)
        # exposed tail consistency
        assert bd["sync_exposed_s"] == pytest.approx(
            max(0.0, bd["comm_end_s"] - bd["compute_end_s"]), abs=1e-15)


def test_searched_schedule_beats_monolithic_on_sync_bound_bert():
    """THE acceptance number: the searched bucketed schedule's simulated
    step beats the monolithic schedule (one post-backward fused sync)
    on the sync-bound BERT config, by shrinking the exposed tail."""
    g = _bert_graph()
    dp = data_parallel_strategy(g, 8)
    sim = Simulator(ff.FFConfig(batch_size=8, num_devices=8).machine_spec,
                    num_devices=8)
    synced = synced_weight_groups(g, dp, sim.cost)
    mono = build_bucketed_schedule(synced, {}, math.inf)
    assert len(mono.buckets) == 1
    bd_m = {}
    c_mono = sim.simulate(g, dp, breakdown=bd_m, sync_schedule=mono)
    sched, info = choose_sync_schedule(
        g, dp, sim, {}, ff.FFConfig(batch_size=8, num_devices=8))
    assert sched is not None and len(sched.buckets) >= 2
    bd_s = {}
    c_sched = sim.simulate(g, dp, breakdown=bd_s, sync_schedule=sched)
    assert c_sched < c_mono
    assert bd_s["sync_exposed_s"] < bd_m["sync_exposed_s"]
    assert info["scheduled_s"] == pytest.approx(c_sched)
    # per-bucket rows are the drift report's predicted lanes
    rows = bd_s["sync_buckets"]
    assert len(rows) == len(sched.buckets)
    assert sum(r["sync_s"] for r in rows) == pytest.approx(
        bd_s["sync_total_s"], rel=1e-12)
    for r in rows:
        assert r["exposed_s"] >= 0.0 and r["finish_s"] >= r["start_s"]


def test_uncovered_groups_priced_as_exposed_monolithic_tail():
    """A schedule covering only part of the synced groups must not make
    the rest free: the leftovers issue after the full backward."""
    g = _bert_graph()
    dp = data_parallel_strategy(g, 8)
    sim = Simulator(ff.FFConfig(batch_size=8, num_devices=8).machine_spec,
                    num_devices=8)
    synced = synced_weight_groups(g, dp, sim.cost)
    full = build_bucketed_schedule(synced, {}, math.inf)
    partial = SyncSchedule([SyncBucket(
        "b0", tuple(n.op.name for n, _mv, _p in synced[-2:]), "fp32")])
    bd = {}
    sim.simulate(g, dp, breakdown=bd, sync_schedule=partial)
    bd_full = {}
    sim.simulate(g, dp, breakdown=bd_full, sync_schedule=full)
    # every group still priced somewhere: totals stay comparable
    assert bd["sync_total_s"] >= bd_full["sync_total_s"] * 0.9


# ---------------------------------------------------------------------------
# legality lint (SHD12x)
def _lint(g, dp, schedule, pmap=None):
    from flexflow_tpu.analysis import lint_sync_schedule

    return [f.code for f in lint_sync_schedule(g, dp, schedule, pmap)]


def test_schedule_lint_codes():
    g = _bert_graph()
    dp = data_parallel_strategy(g, 8)
    sim = Simulator(ff.FFConfig(batch_size=8, num_devices=8).machine_spec,
                    num_devices=8)
    sched, _ = choose_sync_schedule(
        g, dp, sim, {}, ff.FFConfig(batch_size=8, num_devices=8))
    assert sched is not None
    assert _lint(g, dp, sched) == []
    names = sched.covered_ops()
    # SHD120: unknown op / unknown precision
    bad = SyncSchedule([SyncBucket("b0", ("nonexistent_op",), "fp32")]
                       + sched.buckets[1:])
    codes = _lint(g, dp, bad)
    assert "SHD120" in codes and "SHD121" in codes  # plus coverage hole
    codes = _lint(g, dp, SyncSchedule(
        [SyncBucket("b0", tuple(names), "fp8")]))
    assert "SHD120" in codes
    # SHD121: double coverage
    dup = SyncSchedule(list(sched.buckets)
                       + [SyncBucket("dup", (names[0],), "fp32")])
    assert "SHD121" in _lint(g, dp, dup)
    # SHD121: coverage hole
    hole = SyncSchedule([SyncBucket("b0", tuple(names[:-1]), "fp32")])
    assert "SHD121" in _lint(g, dp, hole)
    # SHD122: issue order inverted vs grad readiness
    if len(sched.buckets) >= 2:
        inverted = SyncSchedule(list(reversed(sched.buckets)))
        assert "SHD122" in _lint(g, dp, inverted)
    # SHD123: compressed bucket contradicting the precision map
    comp = SyncSchedule([SyncBucket("b0", tuple(names), "int8")])
    assert "SHD123" in _lint(g, dp, comp, {})  # map says fp32


def test_choose_gates_its_own_product():
    """The builder's always-on gate: choose_sync_schedule must never
    hand out a schedule its own lint rejects (property over the BERT
    config + a weightless graph edge case)."""
    m = ff.FFModel(ff.FFConfig(batch_size=8, num_devices=8))
    x = m.create_tensor([8, 16])
    m.softmax(x, name="s")  # no weights at all
    sim = Simulator(ff.FFConfig(batch_size=8, num_devices=8).machine_spec,
                    num_devices=8)
    sched, info = choose_sync_schedule(
        m.graph, data_parallel_strategy(m.graph, 8), sim, {},
        ff.FFConfig(batch_size=8, num_devices=8))
    assert sched is None and info["buckets"] == 0


# ---------------------------------------------------------------------------
# execution: bit-exact fp32, close compressed, ZeRO-1/grad-accum compose
def _train_mlp(schedule=None, zero=False, grad_accum=1, seed=0):
    cfg = ff.FFConfig(batch_size=32, epochs=2, num_devices=8,
                      only_data_parallel=True, compute_dtype="float32",
                      zero_dp_shard=zero, grad_accum_steps=grad_accum,
                      seed=seed)
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


_FP32_SCHED = SyncSchedule([
    SyncBucket("b0", ("head", "fc2"), "fp32"),
    SyncBucket("b1", ("fc1",), "fp32"),
])


def test_bucketed_fp32_bitexact_with_monolithic(mesh8):
    """THE bit-exactness contract: an all-fp32 bucketed schedule (issue
    anchors only — the fp32 wire is GSPMD's own backward psum) trains
    bitwise identically to the monolithic ``_sync_grads``."""
    m_mono, _ = _train_mlp()
    m_sched, _ = _train_mlp(_FP32_SCHED)
    for op, ws in m_mono.params.items():
        for w, a in ws.items():
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(m_sched.params[op][w]))


def test_bucketed_int8_close_and_composes_with_zero1(mesh8):
    sched = SyncSchedule([
        SyncBucket("b0", ("head", "fc2"), "int8"),
        SyncBucket("b1", ("fc1",), "int8"),
    ])
    m32, l32 = _train_mlp()
    m8, l8 = _train_mlp(sched, zero=True)
    assert np.isfinite(l8) and np.isclose(l32, l8, rtol=5e-3)
    assert_int8_weights_close(m32.params, m8.params)
    # optimizer state stays ZeRO-sharded (round trip runs pre-update)
    v = m8.opt_state["v"]["fc1"]["kernel"]
    assert v.addressable_shards[0].data.size * 8 == v.size


def test_bucketed_sync_composes_with_grad_accum(mesh8):
    """With grad accumulation the AVERAGED grads sync once per
    optimizer step — the fp32 bucketed path stays bit-exact there too."""
    m_mono, _ = _train_mlp(grad_accum=4)
    m_sched, _ = _train_mlp(_FP32_SCHED, grad_accum=4)
    for op, ws in m_mono.params.items():
        for w, a in ws.items():
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(m_sched.params[op][w]))


# ---------------------------------------------------------------------------
# persistence + compile integration
def test_schedule_roundtrip_and_compile_gate(tmp_path, mesh8):
    """compile(sync_schedule='search') on the sync-bound BERT: chooses a
    schedule, executes it, persists it into the strategy file's
    __meta__; a fresh import adopts it; a corrupted file fails with a
    finding (STR/SHD), not inside XLA."""
    from flexflow_tpu.models import build_transformer

    path = str(tmp_path / "strategy.json")
    cfg = ff.FFConfig(batch_size=8, num_devices=8,
                      only_data_parallel=True, compute_dtype="float32",
                      sync_schedule="search", export_strategy_file=path)
    m = build_transformer(cfg, **SYNC_BOUND_BERT_KW)
    m.compile(loss_type="mean_squared_error", metrics=[])
    assert m.sync_schedule is not None
    assert m.compiled.sync_schedule is m.sync_schedule
    data = json.load(open(path))
    persisted = data["__meta__"]["sync_schedule"]
    assert SyncSchedule.from_jsonable(persisted).covered_ops() == \
        m.sync_schedule.covered_ops()
    # predicted breakdown priced WITH the schedule: bucket rows present
    # (compile records them for the DriftReport's per-bucket lanes)
    # round trip through import
    cfg2 = ff.FFConfig(batch_size=8, num_devices=8,
                       compute_dtype="float32", sync_schedule="search",
                       import_strategy_file=path)
    m2 = build_transformer(cfg2, **SYNC_BOUND_BERT_KW)
    m2.compile(loss_type="mean_squared_error", metrics=[])
    assert m2.sync_schedule is not None
    assert m2.sync_schedule.covered_ops() == m.sync_schedule.covered_ops()
    # corrupt the persisted schedule: compile must refuse with findings
    data["__meta__"]["sync_schedule"]["buckets"][0] = {
        "name": "b0", "ops": ["not_an_op"], "precision": "fp32"}
    bad_path = str(tmp_path / "bad.json")
    json.dump(data, open(bad_path, "w"))
    from flexflow_tpu.analysis import AnalysisError

    cfg3 = ff.FFConfig(batch_size=8, num_devices=8,
                       compute_dtype="float32", sync_schedule="search",
                       import_strategy_file=bad_path)
    m3 = build_transformer(cfg3, **SYNC_BOUND_BERT_KW)
    with pytest.raises(AnalysisError):
        m3.compile(loss_type="mean_squared_error", metrics=[])


def test_fflint_validates_persisted_schedule(tmp_path, mesh8):
    import subprocess
    import sys

    from flexflow_tpu.models import build_transformer

    path = str(tmp_path / "strategy.json")
    cfg = ff.FFConfig(batch_size=8, num_devices=8,
                      only_data_parallel=True, compute_dtype="float32",
                      sync_schedule="search", export_strategy_file=path)
    m = build_transformer(cfg, **SYNC_BOUND_BERT_KW)
    m.compile(loss_type="mean_squared_error", metrics=[])
    assert m.sync_schedule is not None
    import os

    fflint = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "fflint.py")
    proc = subprocess.run([sys.executable, fflint, "strategy", path],
                          capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    data = json.load(open(path))
    data["__meta__"]["sync_schedule"]["buckets"][0]["precision"] = "fp8"
    json.dump(data, open(path, "w"))
    proc = subprocess.run([sys.executable, fflint, "strategy", path],
                          capture_output=True, text=True)
    assert proc.returncode == 1 and "STR205" in proc.stdout


def test_drift_report_carries_bucket_rows(mesh8):
    from flexflow_tpu.obs.drift import build_drift_report

    g = _bert_graph()
    dp = data_parallel_strategy(g, 8)
    sim = Simulator(ff.FFConfig(batch_size=8, num_devices=8).machine_spec,
                    num_devices=8)
    sched, _ = choose_sync_schedule(
        g, dp, sim, {}, ff.FFConfig(batch_size=8, num_devices=8))
    bd = {}
    sim.simulate(g, dp, breakdown=bd, sync_schedule=sched)
    rep = build_drift_report(bd, measured_step_s=bd["total_s"] * 1.1)
    assert rep is not None and rep.sync_buckets
    d = rep.to_dict()
    assert len(d["sync_buckets"]) == len(sched.buckets)
    for row in d["sync_buckets"]:
        assert row["measured_s"] is None  # one fused program: honest
        assert row["predicted_sync_s"] > 0
    assert d["phases"]["sync_exposed"]["predicted_s"] == pytest.approx(
        bd["sync_exposed_s"])


def test_schedule_gate_runs_on_cache_served_search(tmp_path, mesh8):
    """Acceptance: the schedule choice + legality gate runs on BOTH
    optimize_strategy paths — a cache-served search result must hand
    compile the same schedule a fresh search does."""
    from flexflow_tpu.models import build_transformer
    from flexflow_tpu.search import driver

    cache = str(tmp_path / "cc.json")

    def run():
        cfg = ff.FFConfig(batch_size=8, num_devices=8,
                          sync_schedule="search", search_budget=2,
                          search_timeout_s=30, cost_cache_file=cache)
        g = build_transformer(cfg, **SYNC_BOUND_BERT_KW).graph
        plan = driver.search_plan(g, cfg)
        return plan.sync_schedule, plan.stats

    fresh_sched, fresh_stats = run()
    served_sched, served_stats = run()
    assert not fresh_stats.get("result_cache_hit")
    assert served_stats.get("result_cache_hit"), served_stats
    # the choice + gate RAN on both paths (its info row is recorded) and
    # agreed — for the searched TP champion the sync is mostly sharded
    # away, so "monolithic stands" (None) is itself a valid agreement
    assert "sync_schedule" in fresh_stats and "sync_schedule" in \
        served_stats
    if fresh_sched is None:
        assert served_sched is None
    else:
        assert [b.ops for b in fresh_sched.buckets] == \
            [b.ops for b in served_sched.buckets]
