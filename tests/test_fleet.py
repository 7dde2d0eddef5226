"""Searched serving fleet: N replica blocks x per-replica strategies
x SLO-aware routing (ISSUE 16 — the serving tier priced in one
per-class p99 currency, elastically re-sized by the controller).

Contract highlights:

* the fleet search (search/fleet.py) partitions the mesh into replica
  blocks with per-block searched strategies and per-SLO-class routing
  fractions, priced per class; on the host machine model it PICKS a
  heterogeneous fleet that beats the single-replica baseline, adopts
  only past the margin (honest zero under an extreme margin), and
  never fakes a fleet when the replica bound forbids one;
* offered load re-sizes N: the same searched graph proposes more
  replicas at higher load — the elastic lever the controller pulls;
* SHD166/167 lint the proposal/artifact frame (disjoint blocks,
  routing coherence, pool geometry) and fflint STR212 re-checks the
  persisted ``__meta__.fleet`` stdlib-only;
* the FleetExecutor's deficit router follows the searched fractions
  deterministically under a seed, rolls per-replica records up into
  fleet per-class p99, and emits ``fleet.route`` events;
* ``TrainingController.observe_fleet`` compares measured per-class p99
  to the proposal's predictions, and a drift episode re-searches and
  HOT-APPLIES a re-sized fleet (``fleet.scale``);
* bit-identity: fleet knobs stay out of serve_fleet=off search keys,
  and partial-occupancy pricing never perturbs a full-frame signature.
"""

import json

import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu.core.machine import MachineSpec
from flexflow_tpu.runtime.decode import (
    ContinuousBatchingExecutor,
    DecodeRequest,
    SLOClass,
)
from flexflow_tpu.runtime.fleet import FleetExecutor

N_DEV = 8

# name:priority:deadline_frames:quantile:weight — the mixed-SLO table
# the fleet tests search under
FLEET_SLO = ("interactive:2:64:0.99:1,standard:1:0:0.99:2,"
             "batch:0:0:0.9:5")

# the small decode config whose searched host fleet the bench measures
FLEET_KW = dict(vocab=256, num_layers=2, hidden=64, num_heads=4,
                ff_dim=128, page_size=8, pages_per_seq=8)


def _fleet_cfg(**overrides):
    """Serve-objective config on the CPU-host machine model —
    max_replicas=3 keeps unequal widths in the partition space, the
    regime where the searched fleet is genuinely heterogeneous."""
    kw = dict(batch_size=8, num_devices=N_DEV, search_budget=4,
              search_timeout_s=30.0, objective="serve",
              comp_mode="inference", cost_cache_file="",
              serve_slo_classes=FLEET_SLO, serve_fleet_max_replicas=3,
              machine_spec=MachineSpec.host_cpu(N_DEV))
    kw.update(overrides)
    return ff.FFConfig(**kw)


@pytest.fixture(scope="module")
def host_fleet_search():
    from flexflow_tpu.models import build_gpt_decode
    from flexflow_tpu.search.driver import optimize_strategy
    from flexflow_tpu.search.fleet import propose_fleet

    cfg = _fleet_cfg()
    m = build_gpt_decode(cfg, **FLEET_KW)
    g, s = optimize_strategy(m.graph, cfg, return_graph=True)
    base = m.graph if g is not m.graph else None
    prop = propose_fleet(g, s, cfg, base_graph=base)
    return cfg, m.graph, g, s, prop


# ---------------------------------------------------------------------------
# the fleet search: adoption, margin gate, elastic load response
# ---------------------------------------------------------------------------
def test_fleet_search_adopts_heterogeneous_blocks(host_fleet_search):
    """THE acceptance scenario (serving fleet, simulated): on the host machine model with the replica bound at 3,
    the search picks a HETEROGENEOUS replica partition whose priced
    per-class p99 beats the single-replica baseline past the margin."""
    cfg, base, g, s, prop = host_fleet_search
    assert prop is not None and prop.adopted
    widths = [r.devices for r in prop.replicas]
    assert len(widths) >= 2 and sum(widths) <= N_DEV
    assert widths == sorted(widths, reverse=True)
    assert len(set(widths)) > 1  # genuinely unequal blocks
    assert prop.fleet_cost_s < prop.single_cost_s
    # every replica carries its own searched strategy at its own width
    assert all(r.strategy for r in prop.replicas)
    # disjoint device spans inside the machine
    spans = sorted((r.start, r.start + r.devices) for r in prop.replicas)
    assert all(a1 >= b0 for (_, b0), (a1, _) in zip(spans, spans[1:]))
    # routing covers every class, each row a distribution over replicas
    names = {c["name"] for c in prop.slo_classes}
    assert set(prop.routing) == names == {"interactive", "standard",
                                          "batch"}
    for fr in prop.routing.values():
        assert len(fr) == len(widths)
        assert abs(sum(fr) - 1.0) < 1e-6
    assert set(prop.per_class_p99_s) == names


def test_fleet_margin_gate_honest_zero(host_fleet_search):
    """An extreme improvement margin keeps the single replica: the
    proposal is still returned with BOTH prices recorded — the search
    does not manufacture adoption — and the replica bound at 1 cannot
    fake a fleet at all."""
    from flexflow_tpu.search.fleet import propose_fleet

    cfg, base, g, s, _ = host_fleet_search
    hard = _fleet_cfg(serve_fleet_max_replicas=2,
                      search_improvement_margin=0.9)
    prop = propose_fleet(g, s, hard, base_graph=base)
    assert prop is not None and not prop.adopted
    assert len(prop.replicas) == 1  # the single block stands
    assert prop.fleet_cost_s < prop.single_cost_s  # honest prices

    solo = propose_fleet(g, s, _fleet_cfg(serve_fleet_max_replicas=1),
                         base_graph=base)
    assert solo is not None and not solo.adopted
    assert [r.devices for r in solo.replicas] == [N_DEV]


def test_fleet_search_resizes_with_load(host_fleet_search):
    """The elastic lever: at a light offered load the search keeps a
    small fleet; folding a drift episode into the load
    (``load_scale``, what the controller's re-search passes) shifts
    the optimum to MORE replicas — queueing dominates and narrower
    blocks buy per-class headroom."""
    from flexflow_tpu.search.fleet import propose_fleet

    cfg, base, g, s, _ = host_fleet_search
    light = _fleet_cfg(serve_fleet_offered_load=0.3)
    nominal = propose_fleet(g, s, light, base_graph=base)
    drifted = propose_fleet(g, s, light, base_graph=base,
                            load_scale=3.0)
    assert nominal is not None and nominal.adopted
    assert drifted is not None and drifted.adopted
    assert len(drifted.replicas) > len(nominal.replicas)
    assert drifted.load_scale == 3.0


# ---------------------------------------------------------------------------
# lint gates: SHD166/167 at proposal/import, STR212 on the file
# ---------------------------------------------------------------------------
def test_lint_fleet_codes(host_fleet_search):
    from flexflow_tpu.analysis import errors_only, lint_fleet

    cfg, base, g, s, prop = host_fleet_search
    meta = prop.to_meta()
    assert not errors_only(lint_fleet(base, meta, cfg))

    def corrupt(**kw):
        c = json.loads(json.dumps(meta))
        c.update(kw)
        return c

    def codes(bad):
        return [f.code for f in lint_fleet(base, bad, cfg)]

    # SHD166: frame structure
    assert "SHD166" in codes(corrupt(replicas=[]))
    bad = corrupt()
    bad["replicas"][1]["start"] = 0  # overlaps replica 0
    assert "SHD166" in codes(bad)
    bad = corrupt()
    bad["replicas"][0]["devices"] = 2 * N_DEV  # overflows the machine
    assert "SHD166" in codes(bad)
    bad = corrupt()
    bad["replicas"][0]["prefill_devices"] = \
        bad["replicas"][0]["devices"]  # split no longer fits the block
    assert "SHD166" in codes(bad)

    # SHD167: routing + pool coherence
    assert "SHD167" in codes(
        corrupt(page_size=meta["page_size"] * 2))
    bad = corrupt()
    bad["routing"]["interactive"] = \
        bad["routing"]["interactive"] + [0.0]  # row sized wrong
    assert "SHD167" in codes(bad)
    bad = corrupt()
    bad["routing"]["standard"] = \
        [f * 0.5 for f in bad["routing"]["standard"]]  # sums to 0.5
    assert "SHD167" in codes(bad)
    bad = corrupt()
    bad["routing"]["bulk"] = bad["routing"]["batch"]  # unknown class
    assert "SHD167" in codes(bad)
    bad = corrupt()
    del bad["routing"]["batch"]  # class routes nowhere
    assert "SHD167" in codes(bad)
    bad = corrupt(slo_classes=meta["slo_classes"]
                  + [meta["slo_classes"][0]])  # duplicate class
    assert "SHD167" in codes(bad)


def test_str212_fleet_meta_lint(tmp_path):
    import sys

    sys.path.insert(0, "tools")
    try:
        from fflint import lint_strategy_file
    finally:
        sys.path.pop(0)

    reps = [
        {"replica": 0, "devices": 4, "start": 0, "prefill_devices": 0,
         "decode_devices": 4, "share": 0.5, "occupancy_slots": 16,
         "step_ms": 0.4, "handoff_ms": 0.0, "spans_dcn": False,
         "strategy_ops": 12},
        {"replica": 1, "devices": 4, "start": 4, "prefill_devices": 0,
         "decode_devices": 4, "share": 0.5, "occupancy_slots": 16,
         "step_ms": 0.4, "handoff_ms": 0.0, "spans_dcn": False,
         "strategy_ops": 12},
    ]
    good = {
        "graph_digest": "d" * 32,
        "serving": {"objective": "serve", "max_seqs": 32,
                    "page_size": 16, "pages_per_seq": 32,
                    "quantile": 0.99, "p99_budget_ms": 0.0},
        "fleet": {
            "num_devices": 8, "replicas": reps,
            "routing": {"interactive": [0.5, 0.5],
                        "standard": [0.5, 0.5],
                        "batch": [1.0, 0.0]},
            "routing_policy": "uniform",
            "single_step_ms": 0.8, "fleet_step_ms": 0.4,
            "per_class_p99_ms": {"interactive": 0.5, "standard": 0.6,
                                 "batch": 0.9},
            "max_seqs": 32, "page_size": 16, "pages_per_seq": 32,
            "offered_load": 0.85, "load_scale": 1.0,
            "slo_classes": [
                {"name": "interactive", "priority": 2,
                 "deadline_frames": 64, "quantile": 0.99, "weight": 1},
                {"name": "standard", "priority": 1,
                 "deadline_frames": 0, "quantile": 0.99, "weight": 2},
                {"name": "batch", "priority": 0, "deadline_frames": 0,
                 "quantile": 0.9, "weight": 5},
            ],
        },
    }
    base = {"lm_head": {"dims": [8, 1, 1], "replica": 1, "start": 0}}

    def write(meta):
        p = tmp_path / "strategy.json"
        p.write_text(json.dumps({**base, "__meta__": meta}))
        return str(p)

    assert not [f for f in lint_strategy_file(write(good))
                if f[1] == "STR212"]

    fm = good["fleet"]

    def mut(**kw):
        return {**good, "fleet": {**json.loads(json.dumps(fm)), **kw}}

    def rep_mut(i, **kw):
        m = mut()
        m["fleet"]["replicas"][i].update(kw)
        return m

    corruptions = [
        ("not-an-object", {**good, "fleet": [1]}),
        ("zero-width replica", rep_mut(0, devices=0)),
        ("overlap", rep_mut(1, start=0)),
        ("machine overflow", rep_mut(1, devices=8)),
        ("phase split misfit", rep_mut(0, prefill_devices=2,
                                       decode_devices=4)),
        ("strategyless replica", rep_mut(0, strategy_ops=0)),
        ("share outside [0,1]", rep_mut(0, share=1.5)),
        ("nan price", mut(fleet_step_ms=float("nan"))),
        ("routing row sized wrong", mut(
            routing={**fm["routing"], "interactive": [1.0]})),
        ("routing sum != 1", mut(
            routing={**fm["routing"], "standard": [0.5, 0.2]})),
        ("unknown routed class", mut(
            routing={**fm["routing"], "bulk": [0.5, 0.5]})),
        ("uncovered class", mut(
            routing={"interactive": [0.5, 0.5],
                     "standard": [0.5, 0.5]})),
        ("geometry vs serving", mut(page_size=64)),
        ("dup slo class", mut(
            slo_classes=fm["slo_classes"] + [fm["slo_classes"][0]])),
        ("non-positive weight", mut(
            slo_classes=[{**fm["slo_classes"][0], "weight": 0}]
            + fm["slo_classes"][1:])),
    ]
    for label, meta in corruptions:
        found = [f for f in lint_strategy_file(write(meta))
                 if f[1] == "STR212" and f[0] == "error"]
        assert found, f"corruption {label!r} not caught by STR212"


# ---------------------------------------------------------------------------
# the FleetExecutor: deterministic routing, fraction tracking, roll-up
# ---------------------------------------------------------------------------
SLO_TABLE = (
    SLOClass("interactive", priority=2, deadline_frames=0),
    SLOClass("standard", priority=1, deadline_frames=0),
    SLOClass("batch", priority=0, deadline_frames=0, quantile=0.9),
)


def _synthetic_step(vocab=97, delay_s=0.0):
    import time as _time

    def step(ids, table, lens):
        if delay_s:
            _time.sleep(delay_s)
        ids = np.asarray(ids)
        lens = np.asarray(lens)
        nxt = (ids[:, 0] * 7 + lens * 13 + 5) % vocab
        logits = np.zeros((ids.shape[0], 1, vocab), np.float32)
        logits[np.arange(ids.shape[0]), 0, nxt] = 1.0
        return logits

    return step


def _mk_fleet(routing, k=2, seed=3, delay_s=0.0):
    reps = [ContinuousBatchingExecutor(
        _synthetic_step(delay_s=delay_s), max_seqs=4, page_size=4,
        pages_per_seq=4, slo_classes=SLO_TABLE)
        for _ in range(k)]
    return FleetExecutor(reps, routing, slo_classes=SLO_TABLE,
                         seed=seed)


def _trace(n=12, seed=5):
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        cls = ("interactive", "standard", "batch")[
            int(rng.integers(0, 3))]
        L = int(rng.integers(1, 6))
        reqs.append(DecodeRequest(
            rid=f"r{i:02d}",
            prompt=list(map(int, rng.integers(1, 96, size=L))),
            max_new_tokens=int(rng.integers(1, 4)), slo=cls))
    return reqs


def test_fleet_router_determinism():
    """The acceptance determinism gate: equal fractions force router
    ties on every dispatch; the seeded tie-break makes a replayed
    trace map every request to the same replica, and the generated
    tokens match request-for-request."""
    routing = {"interactive": [0.5, 0.5], "standard": [0.5, 0.5],
               "batch": [0.5, 0.5]}

    def run():
        fl = _mk_fleet(routing, seed=11)
        out = fl.run(_trace(), max_frames=200)
        return dict(fl.assignments), out

    a1, o1 = run()
    a2, o2 = run()
    assert a1 == a2 and o1 == o2
    assert set(a1.values()) == {0, 1}  # both replicas genuinely used


def test_fleet_router_tracks_fractions():
    """Deficit routing is weighted round-robin, not a sampler: the
    running per-replica shares converge to the searched fractions from
    the first requests."""
    fl = _mk_fleet({"standard": [0.7, 0.3]}, seed=0)
    reqs = [DecodeRequest(rid=f"s{i}", prompt=[1 + i],
                          max_new_tokens=1, slo="standard")
            for i in range(20)]
    picks = [fl.route(r) for r in reqs]
    counts = [picks.count(0), picks.count(1)]
    assert sum(counts) == 20
    assert abs(counts[0] - 14) <= 1  # 0.7 of 20, within rounding
    # an unknown class falls back to the standard row, never crashes
    assert fl.route(DecodeRequest(rid="x", prompt=[1],
                                  max_new_tokens=1,
                                  slo="mystery")) in (0, 1)


def test_fleet_routing_validation():
    step = _synthetic_step()
    reps = [ContinuousBatchingExecutor(step, max_seqs=2, page_size=4,
                                       pages_per_seq=4)
            for _ in range(2)]
    with pytest.raises(ValueError):
        FleetExecutor([], {"standard": [1.0]})
    with pytest.raises(ValueError):
        FleetExecutor(reps, {"standard": [1.0]})  # row sized wrong
    with pytest.raises(ValueError):
        FleetExecutor(reps, {"standard": [0.0, 0.0]})  # routes nowhere


def test_fleet_rollup_per_class(tmp_path):
    """Per-replica request records merge into fleet per-class p99 (the
    measured side the controller compares), each record tagged with
    its replica, and every dispatch emits ``fleet.route``."""
    from flexflow_tpu.obs.events import BUS

    log = str(tmp_path / "obs.jsonl")
    BUS.configure(log)
    try:
        fl = _mk_fleet({"interactive": [0.5, 0.5],
                        "standard": [0.5, 0.5],
                        "batch": [0.5, 0.5]}, seed=1)
        out = fl.run(_trace(n=10), max_frames=200)
        assert len(out) == 10
        s = fl.summary()
        assert s["replicas"] == 2 and s["completed"] == 10
        assert sum(v["completed"]
                   for v in s["slo_classes"].values()) == 10
        for name, row in s["slo_classes"].items():
            assert row["ttft_p99_s"] is not None
            assert fl.measured_request_p99(
                "ttft_s", slo=name) is not None
        recs = fl.request_records
        assert {r["replica"] for r in recs} <= {0, 1}
        assert all(r["replica"] == fl.assignments[r["rid"]]
                   for r in recs)
    finally:
        BUS.close()
    kinds = [json.loads(ln) for ln in open(log)]
    routes = [e for e in kinds if e.get("kind") == "fleet.route"]
    assert len(routes) == 10
    assert all(e["replica"] == fl.assignments[e["rid"]]
               for e in routes)


# ---------------------------------------------------------------------------
# the controller: measured drift -> re-search -> hot-applied re-size
# ---------------------------------------------------------------------------
def test_controller_elastic_refleet(tmp_path):
    """THE elastic acceptance path end to end: compile under
    serve_fleet=search (the light-load fleet adopts 2 replicas),
    measure a drifted fleet (a deliberately slow step makes every
    class's p99 blow past its prediction), and the armed re-search
    RE-SIZES the fleet live — more replicas hot-applied onto
    ``model.fleet``, ``fleet.scale`` on the bus."""
    from flexflow_tpu.models import build_gpt_decode
    from flexflow_tpu.obs.events import BUS
    from flexflow_tpu.runtime.controller import TrainingController

    path = str(tmp_path / "fleet_strategy.json")
    cfg = _fleet_cfg(serve_fleet="search",
                     serve_fleet_offered_load=0.3,
                     export_strategy_file=path)
    m = build_gpt_decode(cfg, **FLEET_KW)
    m.compile(loss_type="sparse_categorical_crossentropy", metrics=[],
              comp_mode="inference")
    # the file's serving provenance is the record of the search whose
    # strategy the file holds — the full-mesh one — not of the last
    # narrow replica block the fleet pass solved after it (which is
    # what the module globals of the parent of PR 30 exported:
    # 0.678207 ms / 524,288 B against 0.233693 ms / 65,536 B here)
    from flexflow_tpu.search.driver import search_plan
    from flexflow_tpu.search.strategy_io import read_meta

    full_mesh = search_plan(build_gpt_decode(cfg, **FLEET_KW).graph, cfg)
    meta = read_meta(path)
    assert meta["serving"] == full_mesh.serving == m.plan.serving
    assert meta.get("kv") == full_mesh.kv
    assert meta["fleet"] == m.fleet.to_meta()
    old = m.fleet
    assert old is not None and old.adopted
    assert len(old.replicas) == 2  # the light-load optimum

    ctl = TrainingController(m)
    log = str(tmp_path / "obs.jsonl")
    BUS.configure(log)
    try:
        # a measured fleet shaped like the proposal, but each frame
        # far slower than the priced step: every class drifts up
        fl = _mk_fleet({c: list(fr) for c, fr in old.routing.items()},
                       k=len(old.replicas), seed=2, delay_s=0.004)
        fl.run(_trace(n=12), max_frames=300)
        ratios = ctl.observe_fleet(fl)
        assert ratios and max(ratios.values()) > 1.5

        new = ctl.maybe_refleet()
        assert new is not None and new is m.fleet and new is not old
        assert len(new.replicas) > len(old.replicas)  # re-sized live
        assert ctl.stats["fleet_scales"] == 1
        assert ctl.maybe_refleet() is None  # trigger consumed
    finally:
        BUS.close()
    events = [json.loads(ln) for ln in open(log)]
    drifts = [e for e in events
              if e.get("kind") == "controller.p99_drift"
              and e.get("slo")]
    assert {e["slo"] for e in drifts} == set(ratios)
    scales = [e for e in events if e.get("kind") == "fleet.scale"]
    assert len(scales) == 1
    assert scales[0]["from_replicas"] == len(old.replicas)
    assert scales[0]["to_replicas"] == len(new.replicas)
    assert scales[0]["resized"] is True
    assert scales[0]["load_scale"] > 1.0


# ---------------------------------------------------------------------------
# bit-identity: off means off
# ---------------------------------------------------------------------------
def test_fleet_knobs_stay_out_of_off_search_keys(host_fleet_search):
    """serve_fleet=off keys must stay byte-identical to pre-fleet
    caches no matter how the fleet knobs are set; only arming the
    search changes the key (a different search function)."""
    from flexflow_tpu.search.cost_cache import CostCache

    _, base, *_ = host_fleet_search
    off_a = _fleet_cfg(serve_fleet="off", serve_fleet_max_replicas=2)
    off_b = _fleet_cfg(serve_fleet="off", serve_fleet_max_replicas=8,
                       serve_fleet_offered_load=0.25)
    armed = _fleet_cfg(serve_fleet="search")
    assert CostCache.search_key(base, off_a) \
        == CostCache.search_key(base, off_b) \
        == CostCache.search_key(base, _fleet_cfg())
    assert CostCache.search_key(base, armed) \
        != CostCache.search_key(base, off_a)
    assert ff.FFConfig().serve_fleet == "off"
    with pytest.raises(ValueError):
        ff.FFConfig(serve_fleet="bogus")


def test_occupancy_signature_guards():
    """Partial-occupancy pricing (a replica block simulated at its
    routed share's slots) must never collide with or perturb the
    full-frame serving signature."""
    from flexflow_tpu.search.serving import ServingSpec

    spec = ServingSpec(max_seqs=16, page_size=16, pages_per_seq=16)
    part = spec.with_occupancy(4)
    assert part.occupancy_slots == 4
    assert part.signature() != spec.signature()
    # occupancy at (or past) the full frame IS the full frame
    assert spec.with_occupancy(16).occupancy_slots == 0
    assert spec.with_occupancy(99).signature() == spec.signature()
    # the floor: a tiny share still prices at least one live slot
    assert spec.with_occupancy(0).occupancy_slots == 1
