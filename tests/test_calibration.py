"""Cost-model calibration: measured per-(op, view) costs override the
roofline and change search decisions (reference: ProfilingRecord cache,
src/runtime/simulator.cc:515-554; on-device timing model.cu:38-74)."""

import math

import pytest

import flexflow_tpu as ff
from flexflow_tpu.core.machine import MachineView
from flexflow_tpu.search.calibration import (
    CalibrationTable,
    calibrate_graph,
    measure_op_view,
)
from flexflow_tpu.search.dp import SearchHelper
from flexflow_tpu.search.simulator import Simulator


def mlp_model(batch=64, in_dim=128, hidden=256, classes=16):
    cfg = ff.FFConfig(batch_size=batch, num_devices=8, only_data_parallel=True)
    m = ff.FFModel(cfg)
    x = m.create_tensor([batch, in_dim])
    t = m.dense(x, hidden, activation="relu", name="fc1")
    t = m.dense(t, classes, name="head")
    return m


def test_table_roundtrip(tmp_path):
    m = mlp_model()
    op = m.node_by_name("fc1").op
    table = CalibrationTable()
    table.put(op, MachineView.data_parallel(2, 8), 1.5e-4)
    table.put(op, MachineView.trivial(2), 9e-4)
    p = str(tmp_path / "calib.json")
    table.save(p)
    loaded = CalibrationTable.load(p)
    assert len(loaded) == 2
    assert loaded.get(op, MachineView.data_parallel(2, 8)) == pytest.approx(1.5e-4)
    assert loaded.get(op, MachineView.trivial(2)) == pytest.approx(9e-4)


def test_injected_measurements_flip_search_ranking():
    """The VERDICT r2 contract: a search decision must be reversible by
    measurements alone.  For this small dense layer the roofline keeps
    fc1 UNSHARDED (compute is tiny; any sharding pays sync/xfer).
    Inject measurements saying the unsharded kernel is pathologically
    slow on real hardware while every sharded variant is fast, and the
    search must start sharding that op."""
    m = mlp_model()
    g = m.graph
    n_dev = 8

    def searched_parts(calibration):
        sim = Simulator(m.config.machine_spec, num_devices=n_dev,
                        calibration=calibration)
        helper = SearchHelper(sim, n_dev)
        _, strategy = helper.graph_cost(g)
        fc1 = m.node_by_name("fc1")
        return strategy[fc1.guid].num_parts

    assert searched_parts(None) == 1  # roofline: trivial wins

    fc1_op = m.node_by_name("fc1").op
    table = CalibrationTable()
    from flexflow_tpu.search.views import boundary_views, candidate_views

    views = list(candidate_views(fc1_op, n_dev)) + list(
        boundary_views(fc1_op, n_dev)
    )
    for mv in views:
        table.put(fc1_op, mv, 5e-2 if mv.num_parts == 1 else 1e-6)
    assert searched_parts(table) > 1  # measurements flipped the ranking


def test_measure_and_calibrate_graph_smoke():
    """measure_op_view probes a sharded dense layer on the live backend
    (CPU mesh in tests; the real chip under bench) and calibrate_graph
    fills a table for a small graph within its budget."""
    # shapes large enough that one forward clears timer noise on a CPU
    # backend — sub-noise probes now decline (return None) by design
    m = mlp_model(batch=512, in_dim=512, hidden=1024, classes=64)
    op = m.node_by_name("fc1").op
    t_full = measure_op_view(op, MachineView.trivial(2), warmup=1, repeats=2)
    assert t_full is not None and math.isfinite(t_full) and t_full > 0
    t_shard = measure_op_view(op, MachineView.data_parallel(2, 8),
                              warmup=1, repeats=2)
    assert t_shard is not None and t_shard > 0

    table = calibrate_graph(m.graph, 8, time_budget_s=20.0, repeats=1)
    assert len(table) > 0
    # the search consumes the table through the simulator
    sim = Simulator(m.config.machine_spec, num_devices=8, calibration=table)
    helper = SearchHelper(sim, 8)
    cost, strategy = helper.graph_cost(m.graph)
    assert math.isfinite(cost) and strategy


def test_calibrate_graph_fills_caller_table_in_place():
    """Regression: an EMPTY CalibrationTable is falsy (__len__ == 0), so a
    `table or CalibrationTable()` default silently discarded the caller's
    table — a caller passed a fresh table, calibrate_graph filled a
    private one, and the artifact reported 'calibrated 0 records'."""
    m = mlp_model(batch=512, in_dim=512, hidden=1024, classes=64)
    mine = CalibrationTable()
    assert not mine  # the precondition that triggered the bug
    out = calibrate_graph(m.graph, 8, mine, time_budget_s=20.0, repeats=1)
    assert out is mine
    assert len(mine) > 0


def test_compile_time_calibration_probes_and_persists(tmp_path):
    """FFConfig(calibrate=True) makes the default compile path probe
    this graph's (op, view) costs on the live backend and rank with
    them — the reference's default behavior (simulator.cc:515-554,
    model.cu:38-74) — persisting to calibration_file for later runs."""
    import json
    import os

    from flexflow_tpu.core.machine import MachineSpec

    path = str(tmp_path / "cal.json")
    # machine model must describe the live backend for probes to be
    # coherent (the driver declines to probe otherwise)
    cfg = ff.FFConfig(batch_size=512, num_devices=8, search_budget=2,
                      calibrate=True, calibration_file=path,
                      calibration_budget_s=25.0,
                      machine_spec=MachineSpec.host_cpu(8))
    m = ff.FFModel(cfg)
    x = m.create_tensor([512, 512])
    t = m.dense(x, 1024, activation="relu", name="fc1")
    t = m.dense(t, 64, name="head")
    m.compile(loss_type="sparse_categorical_crossentropy", metrics=[])
    assert os.path.exists(path)
    with open(path) as f:
        data = json.load(f)
    assert len(data["records"]) > 0
    assert data["backend"] == "cpu"  # tests run on the CPU mesh

    # second compile resumes from the persisted table (no growth needed,
    # just correctness of the load path through FFConfig)
    cfg2 = ff.FFConfig(batch_size=512, num_devices=8, search_budget=2,
                       calibration_file=path,
                       machine_spec=MachineSpec.host_cpu(8))
    m2 = ff.FFModel(cfg2)
    x2 = m2.create_tensor([512, 512])
    t2 = m2.dense(x2, 1024, activation="relu", name="fc1")
    t2 = m2.dense(t2, 64, name="head")
    m2.compile(loss_type="sparse_categorical_crossentropy", metrics=[])


def test_mismatched_backend_calibration_ignored(tmp_path):
    """A table probed on a backend the machine model does not describe
    must not override the roofline (TPU-probed milliseconds are
    incoherent with a CPU-modeled simulator and vice versa): the driver
    discards it and ranks analytically.  A TPU table WITH a TPU machine
    model on a CPU host stays valid — the reference's
    search-on-small-machine pattern (graph.cc:1535-1540)."""
    from flexflow_tpu.core.machine import MachineSpec
    from flexflow_tpu.search.driver import optimize_strategy
    from flexflow_tpu.search.views import boundary_views, candidate_views

    m = mlp_model()
    fc1 = m.node_by_name("fc1")
    views = list(candidate_views(fc1.op, 8)) + list(
        boundary_views(fc1.op, 8))

    def tpu_table(path, punish_unsharded):
        t = CalibrationTable()
        for mv in views:
            slow = (mv.num_parts == 1) if punish_unsharded \
                else (mv.num_parts > 1)
            t.put(fc1.op, mv, 5e-2 if slow else 1e-6)
        t.backend = "tpu"
        t.save(path)
        return path

    # the CPU roofline SHARDS this layer (low peak flops -> compute
    # dominates); a consulted table punishing sharding would flip it to
    # unsharded.  With a cpu machine model the tpu-probed table must be
    # discarded, so the sharded roofline pick survives.
    path_ps = tpu_table(str(tmp_path / "punish_shard.json"),
                        punish_unsharded=False)
    cfg = ff.FFConfig(batch_size=64, num_devices=8, search_budget=0,
                      calibration_file=path_ps,
                      machine_spec=MachineSpec.host_cpu(8))
    strategy = optimize_strategy(m.graph, cfg)
    assert strategy[fc1.guid].num_parts > 1

    # the TPU roofline keeps this layer UNSHARDED; the same-backend
    # table punishing unsharded IS consulted and flips the ranking —
    # even though tests run on a CPU host (the reference's
    # search-on-small-machine pattern)
    path_pu = tpu_table(str(tmp_path / "punish_unsharded.json"),
                        punish_unsharded=True)
    cfg_tpu = ff.FFConfig(batch_size=64, num_devices=8, search_budget=0,
                          calibration_file=path_pu)
    assert cfg_tpu.machine_spec.platform == "tpu"  # the default model
    strategy2 = optimize_strategy(m.graph, cfg_tpu)
    assert strategy2[fc1.guid].num_parts > 1
    # and the punishing-sharded table, consulted on the tpu model,
    # keeps it unsharded — proving consultation, not coincidence
    cfg_tpu2 = ff.FFConfig(batch_size=64, num_devices=8, search_budget=0,
                           calibration_file=path_ps)
    strategy3 = optimize_strategy(m.graph, cfg_tpu2)
    assert strategy3[fc1.guid].num_parts == 1


# ---------------------------------------------------------------------------
# adaptive probes for sub-noise ops + fusion-cluster measurements (round-4)
# ---------------------------------------------------------------------------


def test_cheap_ops_are_measurable():
    """softmax/layernorm/pool-class ops used to fall below timer noise
    and stay unmeasured — the adaptive scan length must resolve them."""
    cfg = ff.FFConfig(batch_size=8, num_devices=8, only_data_parallel=True)
    m = ff.FFModel(cfg)
    x = m.create_tensor([8, 32, 64])
    t = m.layer_norm(x, name="ln")
    t = m.softmax(t, name="sm")
    table = calibrate_graph(m.graph, 8, time_budget_s=60.0, repeats=2)
    kinds = {eval(k[0])[0] for k in table._t}
    assert "layernorm" in kinds, kinds
    assert "softmax" in kinds, kinds


def test_cluster_probe_and_simulator_override(tmp_path):
    """A linear+gelu+softmax chain gets a fused measurement; the
    simulator must then price the chain at (or below) its lone-op sum,
    and the record must survive a save/load round trip."""
    from flexflow_tpu.compiler.lowering import data_parallel_strategy
    from flexflow_tpu.search.calibration import (
        calibrate_clusters,
        find_clusters,
    )

    cfg = ff.FFConfig(batch_size=32, num_devices=8, only_data_parallel=True)
    m = ff.FFModel(cfg)
    x = m.create_tensor([32, 128])
    t = m.dense(x, 256, name="fc")
    t = m.gelu(t, name="act")
    t = m.softmax(t, name="sm")

    chains = find_clusters(m.graph)
    assert len(chains) == 1
    producer, chain = chains[0]
    assert producer.op.name == "fc"
    assert [c.op.name for c in chain] == ["act", "sm"]

    table = CalibrationTable()
    calibrate_clusters(m.graph, 8, table, time_budget_s=60.0, repeats=2)
    assert table.num_clusters >= 1

    p = str(tmp_path / "calib.json")
    table.save(p)
    loaded = CalibrationTable.load(p)
    assert loaded.num_clusters == table.num_clusters

    strat = dict(data_parallel_strategy(m.graph, 8))
    base_sim = Simulator(cfg.machine_spec, num_devices=8)
    base = base_sim.simulate(m.graph, strat)
    fused = Simulator(cfg.machine_spec, num_devices=8,
                      calibration=loaded).simulate(m.graph, strat)
    assert math.isfinite(fused) and fused > 0
    # a fused measurement is a refinement with ratio clamped at 1.0, so
    # total simulated cost can never increase
    assert fused <= base * (1.0 + 1e-9)

    # deterministic check that the override actually engages: inject a
    # cluster record saying the fused chain costs 10% of the lone sum
    # and the simulated total must drop strictly below the baseline
    ops = [producer.op] + [c.op for c in chain]
    mv = strat[producer.guid]
    lone = sum(base_sim.cost.op_cost(op, mv, backward=False) for op in ops)
    injected = CalibrationTable()
    injected.put_cluster(ops, mv, lone * 0.1)
    cheap = Simulator(cfg.machine_spec, num_devices=8,
                      calibration=injected).simulate(m.graph, strat)
    assert cheap < base


def test_cluster_reservation_only_when_unmeasured(monkeypatch):
    """The 25% cluster-budget reservation must key on MISSING cluster
    probes, not on mere cluster presence: a resumed run whose clusters
    are fully measured would otherwise stop op probing at 75% of the
    budget and return the reserved time unused.  Deterministic via a
    fake clock + fake probes (each op probe 'costs' 10s), so the budget
    arithmetic — not host speed — decides what gets measured."""
    from flexflow_tpu.search import calibration as cal

    cfg = ff.FFConfig(batch_size=64, num_devices=8, only_data_parallel=True)
    m = ff.FFModel(cfg)
    x = m.create_tensor([64, 128])
    t = m.dense(x, 256, name="fc")
    t = m.gelu(t, name="act")
    g = m.graph

    clusters = cal.find_clusters(g)
    assert clusters
    clock = [0.0]
    monkeypatch.setattr(cal.time, "monotonic", lambda: clock[0])

    def fake_op_probe(op, mv, repeats=3, **kw):
        clock[0] += 10.0
        return 0.001

    def fake_cluster_probe(producer, chain, mv, repeats=3):
        clock[0] += 10.0
        return 0.002

    monkeypatch.setattr(cal, "measure_op_view", fake_op_probe)
    monkeypatch.setattr(cal, "measure_cluster", fake_cluster_probe)

    # learn the full queue size with an effectively unlimited budget
    probe_all = cal.calibrate_graph(g, 8, CalibrationTable(),
                                    time_budget_s=1e9)
    n_ops, n_cl = len(probe_all), probe_all.num_clusters
    # the budget arithmetic below only discriminates with >=6 queued op
    # probes (0.75*n + 1 < n); guard the regime, not just non-emptiness
    assert n_ops >= 6 and n_cl >= 1

    # Case 1: clusters fully pre-measured -> NO reservation; a budget of
    # exactly 10s/op must measure every queued op probe.  Under the
    # keyed-on-presence regression op probing would stop at 75% of the
    # budget and strand the rest (0.75*n + 1 < n for n > 4).
    pre = CalibrationTable()
    pre._clusters = dict(probe_all._clusters)
    assert not cal._any_cluster_unmeasured(pre, clusters, 8)
    clock[0] = 0.0
    cal.calibrate_graph(g, 8, pre, time_budget_s=10.0 * n_ops + 5.0)
    assert len(pre) == n_ops, (
        f"full budget must reach all {n_ops} op probes when no cluster "
        f"probe is missing; got {len(pre)}"
    )

    # Case 2: clusters unmeasured -> reservation applies; the same
    # budget stops op probing early and spends the tail on clusters.
    fresh = CalibrationTable()
    clock[0] = 0.0
    cal.calibrate_graph(g, 8, fresh, time_budget_s=10.0 * n_ops + 5.0)
    assert len(fresh) < n_ops, "reservation should starve some op probes"
    assert fresh.num_clusters >= 1, "reserved budget must reach clusters"


def test_cluster_probe_dedup_across_identical_chains(monkeypatch):
    """N identical chains share one cluster_key: the probe queue must
    hold each (cluster_key, view) ONCE, not N times — a tight budget
    would otherwise buy N copies of the same measurement."""
    from flexflow_tpu.search import calibration as cal

    cfg = ff.FFConfig(batch_size=64, num_devices=8, only_data_parallel=True)
    m = ff.FFModel(cfg)
    x = m.create_tensor([64, 128])
    for i in range(3):  # three IDENTICAL dense+gelu chains
        t = m.dense(x, 32, name=f"fc{i}")
        m.gelu(t, name=f"act{i}")

    calls = []
    monkeypatch.setattr(
        cal, "measure_cluster",
        lambda producer, chain, mv, repeats=3: calls.append(
            cal.CalibrationTable.cluster_key(
                [producer.op] + [c.op for c in chain], mv)) or 0.001)
    table = CalibrationTable()
    cal.calibrate_clusters(m.graph, 8, table, time_budget_s=1e9)
    assert len(calls) == len(set(calls)), (
        "identical chains must not be probed repeatedly")
    assert table.num_clusters == len(set(calls))


# ---------------------------------------------------------------------------
# satellite: drift-staleness -> automatic re-probe policy


def test_stale_table_reprobed_when_live_backend_matches(tmp_path):
    """A DriftReport-marked table must make the NEXT optimize_strategy
    re-probe (live backend == machine target) instead of only warning:
    fresh records, stale flag cleared on disk."""
    from flexflow_tpu.core.machine import MachineSpec
    from flexflow_tpu.search.driver import optimize_strategy

    path = str(tmp_path / "cal.json")
    cfg = ff.FFConfig(batch_size=16, num_devices=8,
                      machine_spec=MachineSpec.host_cpu(8),
                      calibration_file=path, search_budget=0,
                      calibration_budget_s=15.0, cost_cache_file="")
    m = ff.FFModel(cfg)
    x = m.create_tensor([16, 32])
    m.dense(m.dense(x, 64, name="fc1"), 8, name="head")
    table = CalibrationTable()
    calibrate_graph(m.graph, 8, table, time_budget_s=15.0)
    table.save(path)
    assert CalibrationTable.mark_stale_file(path, 2.5)
    loaded = CalibrationTable.load(path)
    assert loaded.stale and loaded.stale_ratio == 2.5
    optimize_strategy(m.graph, cfg, return_graph=False)
    after = CalibrationTable.load(path)
    assert not after.stale, "re-probe must clear the stale flag"
    assert len(after) > 0, "re-probe must produce fresh records"


def test_stale_table_discarded_when_backend_cannot_reprobe(tmp_path):
    """Stale table for a TPU machine model on a CPU host: the search
    must fall back to the roofline (table ignored) rather than rank
    with measurements execution falsified — and must NOT clear the
    on-disk stale flag (the re-probe still owes)."""
    from flexflow_tpu.search.driver import load_calibration, optimize_strategy

    path = str(tmp_path / "cal.json")
    cfg = ff.FFConfig(batch_size=16, num_devices=8,
                      calibration_file=path, search_budget=0,
                      cost_cache_file="")  # default machine: tpu_v5e
    m = ff.FFModel(cfg)
    x = m.create_tensor([16, 32])
    m.dense(m.dense(x, 64, name="fc1"), 8, name="head")
    table = CalibrationTable()
    table.backend = "tpu"
    for node in m.graph.topo_order():
        from flexflow_tpu.core.machine import MachineView

        table.put(node.op, MachineView.trivial(
            node.op.output_shapes[0].ndim), 1e-4)
    table.stale = True
    table.stale_ratio = 3.0
    table.save(path)
    optimize_strategy(m.graph, cfg, return_graph=False)
    after = CalibrationTable.load(path)
    assert after.stale, "deferred re-probe must keep the flag"
    assert len(after) == len(table), "records must survive untouched"
    assert load_calibration(cfg).stale  # and loading still sees it


def test_auto_reprobe_capped_on_persistent_drift(tmp_path):
    """Re-probing that keeps reproducing the drift is a cost-MODEL gap:
    past MAX_AUTO_REPROBES the driver must stop burning the calibration
    budget (records kept on disk, roofline used), and a healthy
    calibrated fit resets the allowance (mark_healthy_file)."""
    from flexflow_tpu.core.machine import MachineSpec
    from flexflow_tpu.search.driver import optimize_strategy

    path = str(tmp_path / "cal.json")
    cfg = ff.FFConfig(batch_size=16, num_devices=8,
                      machine_spec=MachineSpec.host_cpu(8),
                      calibration_file=path, search_budget=0,
                      calibration_budget_s=15.0, cost_cache_file="")
    m = ff.FFModel(cfg)
    x = m.create_tensor([16, 32])
    m.dense(m.dense(x, 64, name="fc1"), 8, name="head")
    table = CalibrationTable()
    calibrate_graph(m.graph, 8, table, time_budget_s=15.0)
    table.stale = True
    table.stale_ratio = 2.0
    table.reprobes = CalibrationTable.MAX_AUTO_REPROBES
    n_records = len(table)
    table.save(path)
    optimize_strategy(m.graph, cfg, return_graph=False)
    after = CalibrationTable.load(path)
    # capped: no re-probe ran — flag and records untouched on disk
    assert after.stale and len(after) == n_records
    assert after.reprobes == CalibrationTable.MAX_AUTO_REPROBES
    # a healthy calibrated fit resets the allowance
    assert CalibrationTable.mark_healthy_file(path)
    healthy = CalibrationTable.load(path)
    assert not healthy.stale and healthy.reprobes == 0
    # and the counter climbs through begin_reprobe on a fresh cycle
    healthy.stale = True
    healthy.begin_reprobe()
    assert healthy.reprobes == 1 and not healthy.stale


def test_healthy_calibrated_fit_resets_allowance_without_obs(tmp_path):
    """Regression (always-on loop satellite): the re-probe-allowance
    reset must NOT ride the drift-report path alone — a healthy
    calibrated fit with profiling OFF and the obs bus OFF still resets
    ``reprobes`` via mark_healthy_file (fit's own post-compile step
    timer is the evidence; staleness within the configured threshold
    counts as healthy)."""
    import json

    import numpy as np

    from flexflow_tpu.core.machine import MachineSpec
    from flexflow_tpu.obs.events import BUS

    path = str(tmp_path / "cal.json")
    cfg = ff.FFConfig(batch_size=8, num_devices=2,
                      machine_spec=MachineSpec.host_cpu(2),
                      only_data_parallel=True, calibration_file=path,
                      cost_cache_file="",
                      # a CPU-host step never lands within a real drift
                      # band; the threshold is config — what this test
                      # pins is the RESET PATH, not the band
                      drift_threshold=1e9)
    m = ff.FFModel(cfg)
    x = m.create_tensor([8, 16])
    m.dense(m.dense(x, 32, name="fc1"), 4, name="head")
    table = CalibrationTable()
    for node in m.graph.topo_order():
        table.put(node.op, MachineView.trivial(
            node.op.output_shapes[0].ndim), 1e-4)
    table.reprobes = CalibrationTable.MAX_AUTO_REPROBES  # spent allowance
    table.save(path)
    assert not BUS.enabled  # the whole point: no obs bus in play
    m.compile(loss_type="sparse_categorical_crossentropy",
              metrics=["accuracy"])
    # the calibrated compile recorded its prediction even with the bus
    # off (the gate the bugfix widened)
    assert m.predicted_breakdown and m.predicted_breakdown["calibrated"]
    rng = np.random.RandomState(0)
    X = rng.randn(16, 16).astype(np.float32)
    Y = rng.randint(0, 4, size=(16,)).astype(np.int32)
    m.fit(X, Y, batch_size=8, epochs=2, verbose=False)
    with open(path) as f:
        assert json.load(f)["reprobes"] == 0, (
            "healthy calibrated fit must reset the re-probe allowance "
            "even with profiling and the obs bus disabled")
