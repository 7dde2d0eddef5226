"""Joint strategy × comm-plan co-search (search/comm_plan.py, ROADMAP
item 2) — the PR 8 contracts:

* OFF-mode inertness — with ``FFConfig.co_search=False`` (the default)
  the sequential strategy→plan pipeline never touches the co-search
  machinery: a poisoned ``JointPricer`` across the 9-model zoo proves
  no code path constructs one, repeat searches stay deterministic, and
  the persisted search-result key is disjoint from joint-mode keys
  (the manual gate — zoo strategies + sim costs bit-identical to the
  pre-PR tree — was verified at PR time; these tests keep the OFF path
  structurally inert so it stays that way).
* never-worse property — on randomized machine specs the joint
  pipeline's result, scored in the joint currency (best comm plan via
  the exposed-comm simulation minus the ZeRO update credit), is never
  worse than the sequential pipeline's result scored the same way.
* comm-plan memo — repeated synced-group signatures are SERVED (memo
  then the persistent cost-cache layer), not re-searched.
* per-group optimizer sharding legality — SHD140/141 (analysis), the
  ``__meta__.zero_groups`` import gate, STR207 (fflint strategy) and
  CCH407/408 (fflint cache) seeded corruptions.
* EF residual state — ``int8_ef`` groups carry a persistent residual
  in the model-state dict: created at init, advanced by the step, and
  checkpoint round-tripped.
* match seed index — indexed ``find_matches`` is identical to the full
  scan (the FLEXFLOW_TPU_DELTA_CHECK oracle) and the skips land in
  ``search.perf``.
"""

import json
import math

import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu.compiler.lowering import data_parallel_strategy
from flexflow_tpu.search.cost_cache import stable_graph_digest
from flexflow_tpu.search.driver import LAST_SEARCH_STATS, optimize_strategy
from flexflow_tpu.search.simulator import Simulator


def _mlp_graph(cfg):
    m = ff.FFModel(cfg)
    x = m.create_tensor([cfg.batch_size, 128], name="cs_x")
    t = m.dense(x, 512, activation="relu", name="cs_fc1")
    t = m.dense(t, 512, activation="relu", name="cs_fc2")
    m.dense(t, 16, name="cs_head")
    return m.graph


def _bert_graph(cfg):
    from flexflow_tpu.models import build_transformer

    return build_transformer(cfg, num_layers=2, hidden=256, num_heads=4,
                             ff_dim=512, seq_len=16).graph


# ---------------------------------------------------------------------------
# OFF-mode inertness across the zoo


_ZOO = ["alexnet", "bert", "gpt", "dlrm", "candle_uno", "inception",
        "resnext50", "xdl", "mlp"]


@pytest.mark.parametrize("name", _ZOO)
def test_co_search_off_never_constructs_pricer(name, monkeypatch):
    """The bit-identical OFF gate, enforced structurally: a sequential
    (co_search=False) search across every zoo topology must never
    instantiate a JointPricer — the joint machinery is provably not on
    the path, so the pre-PR trajectory cannot be perturbed.  (The
    value-level half — zoo strategies + sim costs bit-identical to the
    pre-PR tree — was verified against the seed source at PR time.)"""
    from flexflow_tpu.search import comm_plan
    from zoo import model_specs

    def _poisoned(*a, **k):
        raise AssertionError(
            "JointPricer constructed on a co_search=False run")

    monkeypatch.setattr(comm_plan, "JointPricer", _poisoned)
    spec = model_specs()[name]
    cfg = ff.FFConfig(batch_size=spec["batch"], num_devices=8,
                      search_budget=4, cost_cache_file="")
    assert cfg.co_search is False
    g = spec["build"](cfg)
    bg, s = optimize_strategy(g.graph if hasattr(g, "graph") else g, cfg,
                              return_graph=True)
    assert s
    assert "comm_plan_serves" not in LAST_SEARCH_STATS


def test_co_search_off_is_deterministic():
    """Two fresh OFF-mode searches agree bit-for-bit (digest, view
    sequence, exact sim cost) — the regression surface the manual
    pre-PR comparison pinned."""

    def run():
        cfg = ff.FFConfig(batch_size=64, num_devices=8, search_budget=6,
                          cost_cache_file="")
        g = _bert_graph(cfg)
        bg, s = optimize_strategy(g, cfg, return_graph=True)
        views = [repr(s[n.guid]) for n in bg.topo_order()]
        cost = Simulator(cfg.machine_spec, num_devices=8).simulate(bg, s)
        return stable_graph_digest(bg), views, cost

    assert run() == run()


def test_search_result_keys_disjoint_between_modes(tmp_path):
    """A joint-mode persisted search result must never be served to a
    sequential run (and vice versa): the result key gains an
    extension-only co_search marker."""
    from flexflow_tpu.search.cost_cache import CostCache

    cfg_off = ff.FFConfig(batch_size=8, num_devices=8, search_budget=4)
    cfg_on = ff.FFConfig(batch_size=8, num_devices=8, search_budget=4,
                         co_search=True)
    g = _mlp_graph(cfg_off)
    assert (CostCache.search_key(g, cfg_off)
            != CostCache.search_key(g, cfg_on))


# ---------------------------------------------------------------------------
# the joint currency + never-worse property


def _joint_score(spec, n, g, s, cfg):
    from flexflow_tpu.search.comm_plan import JointPricer

    sim = Simulator(spec, num_devices=n)
    sim.cost.sync_precision = getattr(cfg, "sync_precision", "fp32")
    return JointPricer(cfg).price(sim, g, s)


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_joint_never_worse_than_sequential(seed):
    """Property: on a randomized machine spec, the joint pipeline's
    result — scored in the joint currency — is never worse than the
    sequential pipeline's result scored the same way.  The sequential
    result is always in the joint search space (same substitutions,
    same DP), so a worse joint pick would be a search bug, not a
    modeling disagreement."""
    import dataclasses

    rng = np.random.default_rng(seed)
    base = ff.FFConfig(batch_size=64, num_devices=8).machine_spec
    spec = dataclasses.replace(
        base,
        ici_bandwidth=base.ici_bandwidth * float(rng.uniform(0.05, 1.0)),
        hbm_bandwidth=base.hbm_bandwidth * float(rng.uniform(0.5, 1.5)),
        peak_flops=base.peak_flops * float(rng.uniform(0.5, 2.0)),
    )

    def run(co):
        cfg = ff.FFConfig(batch_size=64, num_devices=8, search_budget=8,
                          machine_spec=spec, cost_cache_file="",
                          sync_precision="search", sync_schedule="search",
                          co_search=co)
        g = _bert_graph(cfg) if seed % 2 else _mlp_graph(cfg)
        bg, s = optimize_strategy(g, cfg, return_graph=True)
        return bg, s, cfg

    g_seq, s_seq, _ = run(False)
    g_j, s_j, cfg_j = run(True)
    c_seq = _joint_score(spec, 8, g_seq, s_seq, cfg_j)
    c_j = _joint_score(spec, 8, g_j, s_j, cfg_j)
    assert math.isfinite(c_j)
    assert c_j <= c_seq * (1.0 + 1e-9)


# ---------------------------------------------------------------------------
# comm-plan memo: serve vs re-search, and the persistent layer


def test_comm_plan_memo_serves_repeated_signatures():
    from flexflow_tpu.search.comm_plan import JointPricer, synced_signature

    cfg = ff.FFConfig(batch_size=64, num_devices=8,
                      sync_precision="search", sync_schedule="search",
                      co_search=True)
    g = _mlp_graph(cfg)
    s = data_parallel_strategy(g, 8)
    sim = Simulator(cfg.machine_spec, num_devices=8)
    sim.cost.sync_precision = "search"
    jp = JointPricer(cfg)
    c1 = jp.price(sim, g, s)
    assert jp.searches == 1 and jp.serves == 0
    c2 = jp.price(sim, g, s)
    assert jp.searches == 1 and jp.serves == 1
    assert c1 == c2
    # a different strategy with the SAME synced-group signature serves
    # too — the memo key is the signature, not the strategy object
    assert synced_signature(g, s) == synced_signature(g, dict(s))
    jp.price(sim, g, dict(s))
    assert jp.searches == 1 and jp.serves == 2


def test_comm_plan_persists_across_processes_via_cost_cache(tmp_path):
    """The comm_plans cost-cache layer: a plan searched once is served
    from disk by a FRESH pricer over a FRESH cache object."""
    from flexflow_tpu.search.comm_plan import JointPricer
    from flexflow_tpu.search.cost_cache import CostCache, cost_signature

    cfg = ff.FFConfig(batch_size=64, num_devices=8,
                      sync_precision="search", sync_schedule="search",
                      co_search=True)
    g = _mlp_graph(cfg)
    s = data_parallel_strategy(g, 8)
    sim = Simulator(cfg.machine_spec, num_devices=8)
    sim.cost.sync_precision = "search"
    path = str(tmp_path / "cc.json")
    cc = CostCache(path, cost_signature(sim.cost))
    jp = JointPricer(cfg, cost_cache=cc)
    c1 = jp.price(sim, g, s)
    assert jp.searches == 1
    assert cc.comm_plans  # persisted payload staged
    cc.save()

    cc2 = CostCache(path, cost_signature(sim.cost))
    jp2 = JointPricer(cfg, cost_cache=cc2)
    sim2 = Simulator(cfg.machine_spec, num_devices=8)
    sim2.cost.sync_precision = "search"
    c2 = jp2.price(sim2, g, s)
    assert jp2.searches == 0 and jp2.serves == 1
    assert cc2.comm_plan_hits == 1
    assert c1 == c2


def test_unknown_comm_schema_drops_layer_loudly(tmp_path, capsys):
    from flexflow_tpu.search.cost_cache import CostCache, cost_signature

    cfg = ff.FFConfig(batch_size=8, num_devices=8)
    sim = Simulator(cfg.machine_spec, num_devices=8)
    path = str(tmp_path / "cc.json")
    cc = CostCache(path, cost_signature(sim.cost))
    cc.put_comm_plan("ab" * 12, {"schedule": {}, "adopted": False,
                                 "pmap": {}, "zero": [], "credit": 0.0})
    cc.save()
    with open(path) as f:
        data = json.load(f)
    data["comm_schema"] = 99
    with open(path, "w") as f:
        json.dump(data, f)
    cc2 = CostCache(path, cost_signature(sim.cost))
    assert not cc2.comm_plans
    assert "unknown comm_schema" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# per-group optimizer-state sharding: SHD140/141, import gate, STR207


def _dp_cost_model(n=8):
    from flexflow_tpu.search.machine_model import CostModel

    cfg = ff.FFConfig(batch_size=8, num_devices=n)
    return CostModel(cfg.machine_spec, num_devices=n)


def _codes(findings):
    return {f.code for f in findings}


def test_lint_zero_map_legal_and_codes():
    from flexflow_tpu.analysis import errors_only, lint_zero_map

    cfg = ff.FFConfig(batch_size=64, num_devices=8)
    g = _mlp_graph(cfg)
    s = data_parallel_strategy(g, 8)
    cm = _dp_cost_model()
    # legal: big dense layers replicate under DP and their optimizer
    # state shards evenly
    assert lint_zero_map(g, s, ["cs_fc1", "cs_fc2"], cm) == []
    # empty map is trivially legal
    assert lint_zero_map(g, s, [], cm) == []
    # SHD140: unknown op / weightless op / duplicate entry
    assert "SHD140" in _codes(lint_zero_map(g, s, ["nope"], cm))
    relu = next(n for n in g.topo_order()
                if not getattr(n.op, "_weight_specs", ()))
    assert "SHD140" in _codes(
        lint_zero_map(g, s, [relu.op.name], cm))
    assert "SHD140" in _codes(
        lint_zero_map(g, s, ["cs_fc1", "cs_fc1"], cm))
    # SHD140: an op with NO replicated weight under the strategy (full
    # tensor-parallel view) has nothing to shard optimizer state over
    from flexflow_tpu.search.views import candidate_views

    fc1 = next(n for n in g.topo_order() if n.op.name == "cs_fc1")
    tp = dict(s)
    for mv in candidate_views(fc1.op, 8):
        # feature-split: the kernel shards over the devices, nothing
        # replicates, nothing syncs
        if mv.replica_degree == 1 and mv.dim_degrees[-1] == 8:
            tp[fc1.guid] = mv
            break
    else:
        pytest.skip("no pure-TP view for cs_fc1")
    assert "SHD140" in _codes(lint_zero_map(g, tp, ["cs_fc1"], cm))


def test_lint_zero_map_shd141_unachievable_factor():
    """An op whose weight replicates but whose optimizer state cannot
    shard (no evenly-divisible factor for the free devices) is SHD141:
    the credited update win would never be realized."""
    from flexflow_tpu.analysis import lint_zero_map

    cfg = ff.FFConfig(batch_size=8, num_devices=8)
    m = ff.FFModel(cfg)
    x = m.create_tensor([8, 7], name="zl_x")
    m.dense(x, 5, name="zl_odd")  # 7x5 kernel: no factor of 8 divides
    g = m.graph
    s = data_parallel_strategy(g, 8)
    cm = _dp_cost_model()
    codes = _codes(lint_zero_map(g, s, ["zl_odd"], cm))
    assert codes == {"SHD141"}


def test_zero_groups_import_gate(tmp_path):
    """__meta__.zero_groups rides the strategy file: a legal map is
    adopted at compile, an illegal one raises at import."""
    from flexflow_tpu.search.strategy_io import attach_meta, export_strategy

    cfg = ff.FFConfig(batch_size=64, num_devices=8,
                      only_data_parallel=True)
    m = ff.FFModel(cfg)
    x = m.create_tensor([64, 128], name="zg_x")
    t = m.dense(x, 512, activation="relu", name="zg_fc1")
    m.dense(t, 16, name="zg_head")
    s = data_parallel_strategy(m.graph, 8)
    p = str(tmp_path / "s.json")
    export_strategy(p, m.graph, s)
    attach_meta(p, zero_groups=["zg_fc1"])

    def compile_with(path):
        cfg2 = ff.FFConfig(batch_size=64, num_devices=8,
                           import_strategy_file=path)
        m2 = ff.FFModel(cfg2)
        x2 = m2.create_tensor([64, 128], name="zg_x")
        t2 = m2.dense(x2, 512, activation="relu", name="zg_fc1")
        m2.dense(t2, 16, name="zg_head")
        m2.compile(optimizer=ff.SGDOptimizer(),
                   loss_type="sparse_categorical_crossentropy",
                   metrics=[])
        return m2

    m_ok = compile_with(p)
    assert m_ok.zero_groups == ("zg_fc1",)

    # illegal: a weightless op name fails SHD140 at import
    bad = str(tmp_path / "bad.json")
    export_strategy(bad, m.graph, s)
    attach_meta(bad, zero_groups=["zg_x"])
    from flexflow_tpu.analysis import AnalysisError

    with pytest.raises(AnalysisError):
        compile_with(bad)


def test_fflint_zero_groups_str207(tmp_path):
    """Stdlib corruptions of __meta__.zero_groups: each exits 1 with
    STR207; the clean file exits 0."""
    from tools.fflint import main

    from flexflow_tpu.search.strategy_io import attach_meta, export_strategy

    cfg = ff.FFConfig(batch_size=64, num_devices=8,
                      only_data_parallel=True)
    m = ff.FFModel(cfg)
    x = m.create_tensor([64, 128], name="sl_x")
    t = m.dense(x, 256, activation="relu", name="sl_fc1")
    m.dense(t, 16, name="sl_head")
    s = data_parallel_strategy(m.graph, 8)
    p = str(tmp_path / "s.json")
    export_strategy(p, m.graph, s)
    attach_meta(p, zero_groups=["sl_fc1"])
    assert main(["strategy", p]) == 0
    with open(p) as f:
        clean = json.load(f)

    def corrupted(mutate):
        data = json.loads(json.dumps(clean))
        mutate(data["__meta__"])
        bad = str(tmp_path / "bad.json")
        with open(bad, "w") as f:
            json.dump(data, f)
        return main(["strategy", bad])

    assert corrupted(lambda meta: meta.update(zero_groups="sl_fc1")) == 1
    assert corrupted(lambda meta: meta.update(zero_groups=[])) == 1
    assert corrupted(
        lambda meta: meta.update(zero_groups=["sl_fc1", "sl_fc1"])) == 1
    assert corrupted(
        lambda meta: meta.update(zero_groups=["not_in_file"])) == 1
    assert corrupted(lambda meta: meta.update(zero_groups=[7])) == 1


def test_fflint_cache_comm_plan_layer(tmp_path, capsys):
    """CCH407 (unknown comm_schema) and CCH408 (malformed rows) seeded
    corruptions of the persisted comm-plan memo layer."""
    from tools.fflint import main

    from flexflow_tpu.search.comm_plan import JointPricer
    from flexflow_tpu.search.cost_cache import CostCache, cost_signature

    cfg = ff.FFConfig(batch_size=64, num_devices=8,
                      sync_precision="search", sync_schedule="search",
                      co_search=True)
    g = _mlp_graph(cfg)
    s = data_parallel_strategy(g, 8)
    sim = Simulator(cfg.machine_spec, num_devices=8)
    sim.cost.sync_precision = "search"
    path = str(tmp_path / "cc.json")
    cc = CostCache(path, cost_signature(sim.cost))
    JointPricer(cfg, cost_cache=cc).price(sim, g, s)
    assert cc.comm_plans
    cc.save()
    assert main(["cache", path]) == 0
    with open(path) as f:
        clean = json.load(f)

    def corrupted(mutate):
        data = json.loads(json.dumps(clean))
        mutate(data)
        bad = str(tmp_path / "bad.json")
        with open(bad, "w") as f:
            json.dump(data, f)
        return main(["cache", bad])

    # CCH407: unknown comm_schema
    assert corrupted(lambda d: d.update(comm_schema=99)) == 1
    # CCH408 family
    key = next(iter(clean["comm_plans"]))
    assert corrupted(
        lambda d: d["comm_plans"].__setitem__(key, "nope")) == 1
    assert corrupted(
        lambda d: d["comm_plans"][key].pop("schedule")) == 1
    assert corrupted(
        lambda d: d["comm_plans"][key].update(adopted="yes")) == 1
    assert corrupted(
        lambda d: d["comm_plans"][key].update(pmap={"op": "fp8"})) == 1
    assert corrupted(
        lambda d: d["comm_plans"][key].update(zero=[3])) == 1
    assert corrupted(
        lambda d: d["comm_plans"][key].update(credit=-1.0)) == 1
    assert corrupted(
        lambda d: d["comm_plans"].__setitem__("zz", d["comm_plans"][key])
    ) == 1


# ---------------------------------------------------------------------------
# EF residual: persistent training-loop state


def _train_ef(sync_ef, steps=2, seed=0):
    cfg = ff.FFConfig(batch_size=32, num_devices=8,
                      only_data_parallel=True, compute_dtype="float32",
                      sync_precision="int8", sync_ef=sync_ef, seed=seed)
    m = ff.FFModel(cfg)
    x = m.create_tensor([32, 64])
    t = m.dense(x, 2048, activation="relu", name="fc1")
    t = m.dense(t, 8, name="head")
    m.compile(optimizer=ff.AdamOptimizer(alpha=1e-3),
              loss_type="sparse_categorical_crossentropy", metrics=[])
    rng = np.random.default_rng(0)
    y = rng.integers(0, 8, 64).astype(np.int32)
    xd = rng.normal(size=(64, 64)).astype(np.float32)
    hist = m.fit(x=xd, y=y, epochs=steps, verbose=False)
    return m, hist[-1]["loss"]


def test_ef_residual_state_round_trip(mesh8, tmp_path):
    """sync_ef='auto' upgrades the int8 group to int8_ef and threads
    the residual as model state: created at init, advanced by the
    step, checkpoint round-tripped."""
    from flexflow_tpu.runtime.checkpoint import CheckpointManager

    m, loss = _train_ef("auto")
    assert m.sync_precision_map == {"fc1": "int8_ef"}
    key = "fc1/kernel/ef_residual"
    assert key in m.state
    res = np.asarray(m.state[key])
    # after a step the residual carries the (nonzero) quantization
    # error of the last sync
    assert float(np.max(np.abs(res))) > 0.0
    assert np.isfinite(loss)

    # checkpoint round trip: the residual is ordinary model state
    mgr = CheckpointManager(str(tmp_path), use_orbax=False)
    mgr.save(1, m)
    res_before = np.asarray(m.state[key]).copy()
    m.state[key] = m.state[key] * 0.0
    mgr.restore(m)
    np.testing.assert_array_equal(np.asarray(m.state[key]), res_before)

    # off keeps the plain int8 wire — no residual state anywhere
    m_off, _ = _train_ef("off")
    assert m_off.sync_precision_map == {"fc1": "int8"}
    assert not [k for k in m_off.state if k.endswith("ef_residual")]


def test_ef_close_to_fp32(mesh8):
    m_ef, l_ef = _train_ef("auto")
    cfg = ff.FFConfig(batch_size=32, num_devices=8,
                      only_data_parallel=True, compute_dtype="float32",
                      sync_precision="fp32", seed=0)
    m = ff.FFModel(cfg)
    x = m.create_tensor([32, 64])
    t = m.dense(x, 2048, activation="relu", name="fc1")
    t = m.dense(t, 8, name="head")
    m.compile(optimizer=ff.AdamOptimizer(alpha=1e-3),
              loss_type="sparse_categorical_crossentropy", metrics=[])
    rng = np.random.default_rng(0)
    y = rng.integers(0, 8, 64).astype(np.int32)
    xd = rng.normal(size=(64, 64)).astype(np.float32)
    l32 = m.fit(x=xd, y=y, epochs=2, verbose=False)[-1]["loss"]
    assert np.isfinite(l_ef) and np.isclose(l32, l_ef, rtol=5e-3)


# ---------------------------------------------------------------------------
# per-op-type match seed index


def test_indexed_find_matches_identical_to_full_scan(monkeypatch):
    """For every anchor-typed xfer: the indexed scan returns the SAME
    match list as the unindexed scan, and skips land in the counter.
    The in-function oracle (FLEXFLOW_TPU_DELTA_CHECK) is armed so a
    bad anchor_types declaration asserts inside find_matches."""
    from flexflow_tpu.search import substitution as subst

    monkeypatch.setattr(subst, "DELTA_MATCH_CHECK", True)
    cfg = ff.FFConfig(batch_size=64, num_devices=8)
    g = _bert_graph(cfg)
    xfers = subst.generate_all_pcg_xfers(8)
    anchored = [x for x in xfers
                if getattr(x, "anchor_types", None) is not None]
    assert anchored, "factory xfers must declare anchor types"
    before = subst._INDEX_SKIPS.value
    for x in anchored:
        if not hasattr(x, "matcher"):
            # BatchEmbeddingsXfer declares anchor_types too (for the
            # index + proofgen) but is duck-typed without a per-node
            # matcher; its indexed scan is checked against the old
            # full scan below
            continue
        got = [n.guid for n in x.find_matches(g)]
        full = [n.guid for n in g.topo_order() if x.matcher(g, n)]
        assert got == full
    from flexflow_tpu.core.optype import OperatorType

    be = subst.BatchEmbeddingsXfer()
    groups = {}
    for n in g.topo_order():
        if n.op.op_type is OperatorType.EMBEDDING:
            groups.setdefault(n.op.signature(), []).append(n.guid)
    full_be = [{i: gu for i, gu in enumerate(gs)}
               for gs in groups.values() if len(gs) >= 2]
    assert be.find_matches(g) == full_be
    assert subst._INDEX_SKIPS.value > before


def test_search_perf_reports_index_skips():
    cfg = ff.FFConfig(batch_size=64, num_devices=8, search_budget=4,
                      cost_cache_file="")
    g = _mlp_graph(cfg)
    optimize_strategy(g, cfg, return_graph=True)
    assert LAST_SEARCH_STATS.get("match_index_skips", 0) > 0


# ---------------------------------------------------------------------------
# the co-searched result executes: search → compile wiring


def test_co_search_result_wires_zero_groups_into_compile():
    """An end-to-end co-searched strategy lands its per-group
    optimizer-sharding map on the compiled model (the search's record
    → model.zero_groups), linted on the way."""
    from flexflow_tpu.search.driver import search_plan

    # placement search off: a placed proposal would REPLACE the
    # co-searched strategy, and the map gated for it must not follow
    # (on the parent of PR 30 exactly that happened here, and the
    # assert compared () with a global the compile had just reset)
    cfg = ff.FFConfig(batch_size=64, num_devices=8, search_budget=6,
                      cost_cache_file="", sync_precision="search",
                      sync_schedule="search", co_search=True,
                      enable_placement_search=False)
    m = ff.FFModel(cfg)
    x = m.create_tensor([64, 128], name="ew_x")
    t = m.dense(x, 512, activation="relu", name="ew_fc1")
    t = m.dense(t, 512, activation="relu", name="ew_fc2")
    m.dense(t, 16, name="ew_head")
    searched = search_plan(m.graph, cfg)  # the same deterministic search
    m.compile(optimizer=ff.SGDOptimizer(),
              loss_type="sparse_categorical_crossentropy", metrics=[])
    assert m.plan.source == "searched"
    assert searched.zero_groups  # the search shards at least one group
    assert m.zero_groups == searched.zero_groups
    assert m.compiled.zero_groups == m.zero_groups
