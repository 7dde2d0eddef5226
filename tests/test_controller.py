"""The always-on loop: hot strategy swap (bit-exact fp32 re-shard),
drift-driven live re-search, elastic-mesh recovery, and the
deterministic fault-injection harness (runtime/controller.py,
runtime/faults.py, analysis/swap.py, FFModel.swap_strategy)."""

import json
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu.compiler.lowering import data_parallel_strategy
from flexflow_tpu.runtime import (
    FaultPlan,
    TrainingController,
    shrink_config,
)
from flexflow_tpu.search.calibration import CalibrationTable

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _make_model(num_devices=4, seed=0, with_cache=False, **cfg_kw):
    cfg = ff.FFConfig(batch_size=8, num_devices=num_devices,
                      only_data_parallel=True, seed=seed, **cfg_kw)
    m = ff.FFModel(cfg)
    x = m.create_tensor([8, 16])
    h = m.dense(x, 32, activation="relu", name="d0")
    if with_cache:
        h = m.cache(h, name="c0")
    m.dense(h, 4, name="d1")
    m.compile(optimizer=ff.SGDOptimizer(lr=1e-2),
              loss_type="sparse_categorical_crossentropy",
              metrics=["accuracy"])
    return m


def _data(n=32, seed=0):
    rng = np.random.RandomState(seed)
    return (rng.randn(n, 16).astype(np.float32),
            rng.randint(0, 4, size=(n,)).astype(np.int32))


def _fake_table(path, scale=1.0):
    t = CalibrationTable()
    t._t[("('probe', 16, 32)", (1, 1), 1)] = 1e-4 * scale
    t._t[("('probe', 16, 32)", (2, 1), 1)] = 6e-5 * scale
    t.backend = None  # coherent with any machine model
    t.save(path)
    return t


def _host_trees(m):
    import jax

    out = {}
    for name, tree in (("params", m.params), ("opt_state", m.opt_state),
                       ("state", m.state)):
        flat, _ = jax.tree_util.tree_flatten_with_path(tree)
        out[name] = {repr(p): np.array(leaf, copy=True)
                     for p, leaf in flat}
    return out


def _assert_trees_bit_exact(a, b):
    assert a.keys() == b.keys()
    for name in a:
        assert a[name].keys() == b[name].keys(), name
        for k in a[name]:
            np.testing.assert_array_equal(a[name][k], b[name][k],
                                          err_msg=f"{name}:{k}")


# ---------------------------------------------------------------------------
# hot swap mechanics


def test_swap_strategy_bit_exact_and_trainable():
    """The swap contract: params, optimizer slots and op state are
    value-IDENTICAL across the re-shard (fp32 re-shard is a value
    identity — the in-memory checkpoint is the oracle), and the model
    keeps training under the new strategy."""
    m = _make_model(with_cache=True)
    X, Y = _data()
    m.fit(X, Y, batch_size=8, epochs=2, verbose=False)
    before = _host_trees(m)
    rep = m.swap_strategy(data_parallel_strategy(m.graph, 2))
    assert rep["fallback"] is False and not rep["dropped"]
    _assert_trees_bit_exact(before, _host_trees(m))
    # the cache op's mutable state rode the swap
    assert any("c0/cached" in k for k in before["state"])
    m.fit(X, Y, batch_size=8, epochs=1, verbose=False)  # must not raise


def test_swap_matches_direct_device_put_oracle():
    """The swap-step state equals an UNINTERRUPTED fp32 re-shard
    oracle: device_put of the pre-swap host values onto the post-swap
    shardings, leaf by leaf."""
    import jax

    m = _make_model()
    X, Y = _data()
    m.fit(X, Y, batch_size=8, epochs=1, verbose=False)
    pre = {op: {w: np.array(a, copy=True) for w, a in ws.items()}
           for op, ws in m.params.items()}
    m.swap_strategy(data_parallel_strategy(m.graph, 2))
    for op, ws in pre.items():
        for w, host in ws.items():
            live = m.params[op][w]
            oracle = jax.device_put(host, live.sharding)
            np.testing.assert_array_equal(np.asarray(live),
                                          np.asarray(oracle))


def test_swap_gate_rejects_weight_and_state_loss():
    """SHD170/SHD171: a target graph that drops (or invents) a weight
    or op state is an illegal swap — the always-on gate refuses it."""
    from flexflow_tpu.analysis import AnalysisError, lint_swap

    m = _make_model(with_cache=True)
    other = ff.FFModel(ff.FFConfig(batch_size=8, num_devices=4,
                                   only_data_parallel=True))
    x = other.create_tensor([8, 16])
    h = other.dense(x, 32, activation="relu", name="d0")
    other.dense(h, 8, name="d1")  # shape change + cache state dropped
    strat = data_parallel_strategy(other.graph, 4)
    codes = {f.code for f in lint_swap(
        m.graph, other.graph, strat, 4)}
    assert "SHD170" in codes and "SHD171" in codes
    with pytest.raises(AnalysisError):
        m.swap_strategy(strat, graph=other.graph)


def test_swap_gate_rejects_uncovered_node():
    from flexflow_tpu.analysis import lint_swap

    m = _make_model()
    strat = data_parallel_strategy(m.graph, 4)
    victim = next(g for g, v in strat.items()
                  if len(m.graph.nodes[g].op._weight_specs))
    del strat[victim]
    codes = {f.code for f in lint_swap(m.graph, m.graph, strat, 4)}
    assert "SHD172" in codes


def test_swap_comm_plan_lint_failure_falls_back_to_fp32(monkeypatch):
    """A searched comm plan that fails its legality gate post-swap
    degrades to the monolithic fp32 sync path instead of failing."""
    from flexflow_tpu.analysis import AnalysisError
    from flexflow_tpu.search import driver as _driver

    m = _make_model(sync_schedule="search")
    X, Y = _data()
    m.fit(X, Y, batch_size=8, epochs=1, verbose=False)

    def boom(*a, **kw):
        raise AnalysisError("injected post-swap plan lint failure", [])

    monkeypatch.setattr(_driver, "_build_sync_schedule", boom)
    rep = m.swap_strategy(data_parallel_strategy(m.graph, 4))
    assert rep["fallback"] is True
    assert m.sync_schedule is None and not m.sync_precision_map
    m.fit(X, Y, batch_size=8, epochs=1, verbose=False)


def test_elastic_swap_with_zero_sharded_optimizer():
    """Mesh shrink re-homes per-group ZeRO optimizer shards: values
    bit-exact, training continues on the survivors."""
    m = _make_model(num_devices=4, zero_dp_shard=True)
    X, Y = _data()
    m.fit(X, Y, batch_size=8, epochs=2, verbose=False)
    before = _host_trees(m)
    cfg2 = shrink_config(m.config, 2)
    m.swap_strategy(data_parallel_strategy(m.graph, 2), config=cfg2)
    assert m.config.num_devices == 2
    _assert_trees_bit_exact(before, _host_trees(m))
    m.fit(X, Y, batch_size=8, epochs=1, verbose=False)


# ---------------------------------------------------------------------------
# fault plan


def test_fault_plan_parse_and_env(monkeypatch):
    plan = FaultPlan.parse("calibration_drift@3, device_loss@6:2", seed=5)
    assert [(f.kind, f.step, f.arg) for f in plan.faults] == [
        ("calibration_drift", 3, None), ("device_loss", 6, 2)]
    monkeypatch.setenv("FLEXFLOW_TPU_FAULTS", "collective_failure@1:4")
    monkeypatch.setenv("FLEXFLOW_TPU_FAULT_SEED", "9")
    env = FaultPlan.from_env()
    assert env.seed == 9 and env.faults[0].arg == 4
    with pytest.raises(ValueError):
        FaultPlan.parse("meteor_strike@1")
    # a zero failure budget / zero survivors is a plan that silently
    # tests nothing — rejected at parse, not discovered mid-run
    with pytest.raises(ValueError, match=">= 1"):
        FaultPlan.parse("collective_failure@3:0")
    with pytest.raises(ValueError, match=">= 1"):
        FaultPlan.parse("device_loss@3:0")
    monkeypatch.delenv("FLEXFLOW_TPU_FAULTS")
    assert FaultPlan.from_env() is None


def test_fault_plan_drift_factor_is_seed_deterministic(tmp_path):
    seen = []
    for _ in range(2):
        cal = str(tmp_path / "CAL.json")
        _fake_table(cal)
        plan = FaultPlan.parse("calibration_drift@0", seed=11)
        seen.append(plan.inject_calibration_drift(plan.faults[0], cal))
        with open(cal) as f:
            assert json.load(f)["stale"] is True
    assert seen[0] == seen[1]


# ---------------------------------------------------------------------------
# end-to-end recovery (the acceptance scenarios)


def test_drift_research_hot_swap_e2e_and_deterministic(tmp_path):
    """Injected calibration drift at step k: the controller re-searches
    warm, hot-swaps between steps, the pre-swap trajectory is
    bit-identical to an unfaulted run, the post-swap trajectory stays
    close (same math, possibly different reduction order), and the
    whole run is bit-reproducible under the fixed fault seed."""
    cal = str(tmp_path / "CALIBRATION.json")
    X, Y = _data()

    def run(faulted):
        _fake_table(cal)
        m = _make_model(calibration_file=cal)
        plan = (FaultPlan.parse("calibration_drift@3", seed=7)
                if faulted else None)
        ctl = TrainingController(m, faults=plan)
        out = ctl.run(X, Y, steps=6)
        return out, m

    out_a, _ = run(faulted=True)
    out_b, _ = run(faulted=True)
    clean, _ = run(faulted=False)
    la = [h["loss"] for h in out_a["history"]]
    lb = [h["loss"] for h in out_b["history"]]
    lc = [h["loss"] for h in clean["history"]]
    assert la == lb  # deterministic under the fixed fault seed
    assert out_a["stats"]["swaps"] == 1
    assert out_a["stats"]["research_seconds"]
    assert la[:3] == lc[:3]  # bit-identical up to the swap step
    np.testing.assert_allclose(la, lc, rtol=1e-4, atol=1e-6)


def test_drift_swap_step_state_bit_exact_vs_oracle(tmp_path):
    """The swap step's full state is bit-exact vs the uninterrupted
    run's state at that step (the swap itself moved no values)."""
    cal = str(tmp_path / "CALIBRATION.json")
    X, Y = _data()

    _fake_table(cal)
    m_clean = _make_model(calibration_file=cal)
    TrainingController(m_clean).run(X, Y, steps=3)
    oracle = _host_trees(m_clean)

    _fake_table(cal)
    m = _make_model(calibration_file=cal)
    ctl = TrainingController(m, faults=FaultPlan.parse(
        "calibration_drift@3", seed=7))
    ctl.run(X, Y, steps=4)
    # rewind the extra step by replaying: instead, compare via a second
    # controller stopped AT the swap step
    _fake_table(cal)
    m2 = _make_model(calibration_file=cal)
    ctl2 = TrainingController(m2, faults=FaultPlan.parse(
        "calibration_drift@3", seed=7))
    out2 = ctl2.run(X, Y, steps=3)
    assert out2["stats"]["swaps"] == 0  # fault fires at step 3 exactly
    _assert_trees_bit_exact(oracle, _host_trees(m2))
    assert ctl.stats["swaps"] == 1


def test_device_loss_recovery_matches_shrunken_mesh_trajectory(tmp_path):
    """Injected device loss: the run resumes on the surviving mesh and
    its loss trajectory matches a shrunken-mesh-from-scratch run within
    tolerance (reduction-order noise only)."""
    X, Y = _data()
    m = _make_model(num_devices=4)
    plan = FaultPlan.parse("device_loss@3:2", seed=7)
    out = TrainingController(m, faults=plan).run(X, Y, steps=8)
    assert m.config.num_devices == 2
    assert out["stats"]["recoveries"] == 1 and out["stats"]["swaps"] == 1

    oracle = _make_model(num_devices=2)
    out_o = TrainingController(oracle).run(X, Y, steps=8)
    la = [h["loss"] for h in out["history"]]
    lo = [h["loss"] for h in out_o["history"]]
    assert all(np.isfinite(la))
    np.testing.assert_allclose(la, lo, rtol=1e-4, atol=1e-6)

    # deterministic under the fixed fault seed
    m2 = _make_model(num_devices=4)
    out2 = TrainingController(m2, faults=FaultPlan.parse(
        "device_loss@3:2", seed=7)).run(X, Y, steps=8)
    assert la == [h["loss"] for h in out2["history"]]


def test_collective_failure_retry_then_monolithic_fallback():
    """Transient collective faults retry within the bounded budget; a
    persistent one degrades to the monolithic fp32 sync path and the
    run completes."""
    X, Y = _data()
    m = _make_model()
    plan = FaultPlan.parse(
        "collective_failure@2:1,collective_failure@4:99", seed=3)
    ctl = TrainingController(m, faults=plan, max_retries=2)
    out = ctl.run(X, Y, steps=6)
    assert len(out["history"]) == 6
    assert out["stats"]["retries"] >= 3
    assert out["stats"]["fallbacks"] == 1
    assert m.sync_schedule is None and not m.sync_precision_map
    assert m.config.sync_schedule == "off"


def test_corrupt_checkpoint_restore_drill(tmp_path):
    """A torn newest snapshot triggers the restore drill: fall back to
    the newest COMPLETE step, rewind, and replay deterministically."""
    X, Y = _data()
    d = str(tmp_path / "ck")
    m = _make_model()
    plan = FaultPlan.parse("corrupt_checkpoint@5", seed=1)
    ctl = TrainingController(m, faults=plan, checkpoint_dir=d,
                             checkpoint_every=2)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        out = ctl.run(X, Y, steps=8)
    assert out["stats"]["restores"] == 1
    assert [h["step"] for h in out["history"]] == list(range(8))

    clean = _make_model()
    out_c = TrainingController(clean, checkpoint_dir=str(tmp_path / "c2"),
                               checkpoint_every=2).run(X, Y, steps=8)
    # the replayed tail is bit-identical to the unfaulted run (the rng
    # counter rode the checkpoint)
    assert ([h["loss"] for h in out["history"]]
            == [h["loss"] for h in out_c["history"]])


# ---------------------------------------------------------------------------
# telemetry


def test_controller_events_validate_and_render(tmp_path):
    from flexflow_tpu.obs.events import BUS, validate_event

    log = str(tmp_path / "obs.jsonl")
    cal = str(tmp_path / "CALIBRATION.json")
    _fake_table(cal)
    BUS.configure(log)
    try:
        m = _make_model(calibration_file=cal)
        plan = FaultPlan.parse(
            "calibration_drift@2,collective_failure@4:99", seed=7)
        TrainingController(m, faults=plan, max_retries=1).run(
            *_data(), steps=6)
        BUS.flush()
    finally:
        BUS.close()
    kinds = set()
    with open(log) as f:
        for line in f:
            evt = json.loads(line)
            assert validate_event(evt) == [], (evt, validate_event(evt))
            kinds.add(evt["kind"])
    assert {"fault.injected", "controller.research", "controller.swap",
            "controller.retry", "controller.fallback",
            "controller.summary"} <= kinds
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "ffobs.py"),
         "report", log],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert "Always-on controller" in proc.stdout
    assert "Hot swap at step" in proc.stdout


def test_corrupt_checkpoint_before_first_save_degrades_gracefully(
        tmp_path):
    """Review fix: the fault firing before any snapshot exists (or
    after truncating the ONLY one) must not kill the run — the live
    in-memory state is intact, so the drill is skipped and training
    continues."""
    X, Y = _data()
    m = _make_model()
    plan = FaultPlan.parse("corrupt_checkpoint@1", seed=1)
    ctl = TrainingController(m, faults=plan,
                             checkpoint_dir=str(tmp_path / "ck"),
                             checkpoint_every=4)
    out = ctl.run(X, Y, steps=6)
    assert len(out["history"]) == 6
    assert out["stats"]["restores"] == 0


def test_monolithic_fallback_drops_zero_groups():
    """Review fix: the fp32 fallback drops the WHOLE searched comm
    plan — the per-group ZeRO map included, not just the schedule and
    wire precision."""
    X, Y = _data()
    m = _make_model()
    m.fit(X, Y, batch_size=8, epochs=1, verbose=False)
    # stand in for a co-searched map (any lint-passing content would
    # otherwise be carried forward by swap_strategy BY DESIGN)
    m.plan.zero_groups = ("d0",)
    plan = FaultPlan.parse("collective_failure@1:99", seed=3)
    ctl = TrainingController(m, faults=plan, max_retries=1)
    out = ctl.run(X, Y, steps=3)
    assert out["stats"]["fallbacks"] == 1
    assert m.zero_groups == () and m.compiled.zero_groups == ()


def test_snapshot_shape_mismatch_keeps_fresh_init():
    """Review fix: a saved state entry whose shape no longer matches
    the template keeps the template's fresh init — the stale buffer
    must not ride the grown-state carry back in."""
    from flexflow_tpu.runtime.checkpoint import (
        restore_in_memory,
        snapshot_in_memory,
    )

    m = _make_model(with_cache=True)
    X, Y = _data()
    m.fit(X, Y, batch_size=8, epochs=1, verbose=False)
    snap = snapshot_in_memory(m)
    good = np.asarray(m.state["c0/cached"])
    snap["trees"]["state"]["c0/cached"] = np.zeros((1, 1),
                                                   dtype=np.float32)
    report = restore_in_memory(m, snap)
    assert tuple(np.asarray(m.state["c0/cached"]).shape) == good.shape
    assert "state/c0/cached" in report["fresh"]


def test_shrink_config_preserves_machine_family():
    """Review fix: shrinking must not change WHAT machine the model
    describes — a host_cpu spec stays host_cpu (platform included: the
    calibration coherence rule keys on it), a custom spec keeps its
    constants, and only the default tpu_v5e family is re-derived."""
    import dataclasses

    from flexflow_tpu.core.machine import MachineSpec

    cpu_cfg = ff.FFConfig(batch_size=8, num_devices=8,
                          machine_spec=MachineSpec.host_cpu(8))
    small = shrink_config(cpu_cfg, 4)
    assert small.machine_spec == MachineSpec.host_cpu(4)
    assert small.machine_spec.platform == "cpu"

    default_cfg = ff.FFConfig(batch_size=8, num_devices=8)
    assert shrink_config(default_cfg, 4).machine_spec == \
        MachineSpec.tpu_v5e(4)

    custom = dataclasses.replace(MachineSpec.tpu_v5e(8),
                                 peak_flops=1.23e14, name="custom")
    custom_cfg = ff.FFConfig(batch_size=8, num_devices=8,
                             machine_spec=custom)
    shrunk = shrink_config(custom_cfg, 4).machine_spec
    assert shrunk.num_devices == 4
    assert shrunk.peak_flops == 1.23e14 and shrunk.name == "custom"


def test_failed_swap_rolls_back_to_old_program(monkeypatch):
    """Review fix: a swap that fails PAST the gate (a non-AnalysisError
    out of the re-lowering itself) leaves the model exactly as it was
    — old program, old config/strategy, old state — and training
    continues."""
    import flexflow_tpu.compiler.lowering as lowering

    m = _make_model(num_devices=4)
    X, Y = _data()
    m.fit(X, Y, batch_size=8, epochs=1, verbose=False)
    before = _host_trees(m)
    old = (m.compiled, m.strategy, m.config, m.graph)

    def boom(*a, **kw):
        raise RuntimeError("injected lowering failure")

    monkeypatch.setattr(lowering, "CompiledModel", boom)
    with pytest.raises(RuntimeError, match="injected"):
        m.swap_strategy(data_parallel_strategy(m.graph, 2),
                        config=shrink_config(m.config, 2))
    assert (m.compiled, m.strategy, m.config, m.graph) == old
    assert m.config.num_devices == 4
    _assert_trees_bit_exact(before, _host_trees(m))
    monkeypatch.undo()
    m.fit(X, Y, batch_size=8, epochs=1, verbose=False)  # still alive


def test_swap_refuses_placed_lowering():
    """Review fix: a live inter-op-placed model must be REFUSED by
    swap_strategy (its plan carries none of the pipeline/staged
    markers) — never silently re-lowered flat mid-run."""
    from flexflow_tpu.compiler.placement_lowering import (
        PlacedCompiledModel,
    )
    from flexflow_tpu.core.machine import MachineView

    cfg = ff.FFConfig(batch_size=8, num_devices=8,
                      compute_dtype="float32")
    m = ff.FFModel(cfg)
    ids = m.create_tensor([8, 4], dtype="int32", name="ids")
    e = m.embedding(ids, 16, 8, name="emb")
    h = m.flat(e, name="flatten")
    h = m.dense(h, 32, activation="relu", name="mlp1")
    m.dense(h, 4, name="head")
    strat = {}
    for node in m.graph.topo_order():
        nd = node.op.output_shapes[0].ndim
        if node.op.name in ("mlp1", "head"):
            strat[node.guid] = MachineView(
                dim_degrees=(4,) + (1,) * (nd - 1), start_part=4)
        else:
            strat[node.guid] = (
                node.op.fixed_machine_view()
                or MachineView(dim_degrees=(4,) + (1,) * (nd - 1)))
    m.compile(loss_type="sparse_categorical_crossentropy",
              metrics=["accuracy"], strategy=strat)
    assert isinstance(m.compiled, PlacedCompiledModel)
    with pytest.raises(NotImplementedError, match="placed"):
        m.swap_strategy(data_parallel_strategy(m.graph, 8))


def test_research_fallback_degrades_to_dp_past_chain_threshold(
        monkeypatch):
    """Review fix: when the swap gate refuses the rewritten winner on a
    graph past the chain threshold, the fallback must NOT run the flat
    whole-graph DP (documented not to terminate at production scale) —
    it degrades to plain data parallelism and the swap proceeds."""
    import flexflow_tpu.analysis as analysis
    from flexflow_tpu.analysis import Finding
    from flexflow_tpu.search import driver as _driver

    m = _make_model()
    X, Y = _data()

    def reject_all(*a, **kw):
        return [Finding(code="SHD170", pass_name="swap",
                        message="forced rejection")]

    monkeypatch.setattr(analysis, "lint_swap", reject_all)
    monkeypatch.setattr(_driver, "CHAIN_MIN_NODES", 1)
    ctl = TrainingController(m)
    g, s = ctl._research(m.config, "calibration_drift", step=0)
    monkeypatch.undo()  # the swap below must run the REAL gate
    assert g is m.graph
    detail = ctl.stats["research_detail"][-1]
    assert detail["dp_fallback"] is True and detail["searches"] == 1
    # the DP strategy is immediately swappable
    ctl._swap(0, s)
    ctl.run(X, Y, steps=2)


# ---------------------------------------------------------------------------
# measured-drift triggers (ISSUE 14): serving p99 + device-trace lanes
def test_p99_drift_fault_triggers_research(tmp_path):
    """A seeded measured-p99 drift past threshold (the p99_drift fault
    kind) must trigger a controller re-search with the "p99_drift"
    trigger — the serve currency joining the calibration-signature
    watch as a first-class re-search signal."""
    from flexflow_tpu.obs.events import BUS

    log = str(tmp_path / "obs.jsonl")
    BUS.configure(log)
    try:
        # profiling arms compile's predicted breakdown — the searched
        # prediction the measured p99 is judged against
        m = _make_model(profiling=True)
        assert m.predicted_breakdown is not None
        X, Y = _data()
        ctl = TrainingController(
            m, faults=FaultPlan.parse("p99_drift@2", seed=7))
        out = ctl.run(X, Y, steps=5)
        triggers = [d["trigger"] for d in ctl.stats["research_detail"]]
        assert "p99_drift" in triggers
        assert ctl.stats["swaps"] >= 1
        assert all(np.isfinite(h["loss"]) for h in out["history"])
    finally:
        BUS.close()
    events = [json.loads(line) for line in open(log)]
    p99 = [e for e in events if e["kind"] == "controller.p99_drift"]
    assert len(p99) == 1 and p99[0]["drifted"] is True
    assert p99[0]["ratio"] > 1.5  # the seeded draw is 1.5x-3.5x
    from flexflow_tpu.obs.events import validate_event

    for e in events:
        assert validate_event(e) == [], e
    # determinism: the same seed pre-draws the same ratio (the full
    # controller replay is covered by the calibration-drift e2e test —
    # no need to pay a second 5-step run here)
    plan_a = FaultPlan.parse("p99_drift@2", seed=7)
    plan_b = FaultPlan.parse("p99_drift@2", seed=7)
    assert plan_a._draws[id(plan_a.faults[0])] == \
        plan_b._draws[id(plan_b.faults[0])] == pytest.approx(
            p99[0]["ratio"])


def test_observe_p99_below_threshold_is_inert():
    m = _make_model(profiling=True)
    ctl = TrainingController(m)
    pred = m.predicted_breakdown["total_s"]
    ratio = ctl.observe_p99(pred * 1.1, step=0)
    assert ratio == pytest.approx(1.1)
    assert ctl._p99_trigger is None
    # missing either side declines instead of inventing a ratio
    assert ctl.observe_p99(0.0, step=0) is None


def test_lane_drift_report_triggers_research():
    """A matched LaneDriftReport with a stale lane (the device-trace
    measured side) arms a "lane_drift" re-search at the next step
    boundary; a clean report stays inert, and the SAME report object
    never fires twice."""
    from flexflow_tpu.obs.trace_ingest import LaneDriftReport

    m = _make_model(profiling=True)
    X, Y = _data()
    ctl = TrainingController(m)
    clean = LaneDriftReport(
        steps=2, predicted_total_s=1e-3, measured_step_s=1e-3,
        threshold=0.5,
        lanes=[{"lane": "bucket:b0:sync", "matched": True,
                "sync_frac_ratio": 1.0}])
    m.lane_drift_report = clean
    ctl.run(X, Y, steps=2)
    assert not any(d["trigger"] == "lane_drift"
                   for d in ctl.stats["research_detail"])
    drifted = LaneDriftReport(
        steps=2, predicted_total_s=1e-3, measured_step_s=1e-3,
        threshold=0.5,
        lanes=[{"lane": "bucket:b0:sync", "matched": True,
                "sync_frac_ratio": 9.0}])
    assert drifted.stale_lanes == ["bucket:b0:sync"]
    m.lane_drift_report = drifted
    ctl.run(X, Y, steps=3)
    lane_triggers = [d for d in ctl.stats["research_detail"]
                     if d["trigger"] == "lane_drift"]
    assert len(lane_triggers) == 1  # consumed once, not every step
