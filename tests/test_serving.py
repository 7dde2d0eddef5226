"""Serving workload: ragged paged decode attention, KV-cache-aware
search, the serve (p99/SLO) objective, and the continuous-batching
executor (ISSUE 10 / ROADMAP item 4).

Contract highlights:

* the ragged paged kernel (Pallas-interpret AND the XLA fallback)
  matches the dense masked reference across ragged shapes, including
  the single-token and full-page boundaries;
* per-device KV residency enters the simulator's memory check: a
  strategy that cannot hold the page pool is rejected INSIDE the
  search, never at OOM;
* on the serving-regime decode config the serve objective selects a
  DIFFERENT strategy than the throughput objective and wins on
  simulated p99 (the acceptance scenario);
* with objective="train" (the default) the serving machinery is
  structurally inert — a poisoned spec builder proves the default
  path never touches it, and cache signatures only extend under serve;
* the executor's continuous batching is semantically invisible:
  serving requests batched with admission/eviction yields EXACTLY the
  tokens of serving each request alone.
"""

import json
import math

import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu.core.machine import MachineSpec, MachineView
from flexflow_tpu.core.optype import OperatorType

N_DEV = 8


def _trivial_strategy(graph):
    return {
        n.guid: (n.op.fixed_machine_view()
                 or MachineView.trivial(n.op.output_shapes[0].ndim))
        for n in graph.topo_order()
    }


def _decode_views(graph, strategy):
    return [
        (tuple(strategy[n.guid].dim_degrees),
         strategy[n.guid].replica_degree)
        for n in graph.topo_order()
        if n.op.op_type == OperatorType.DECODE_ATTENTION
    ]


# ---------------------------------------------------------------------------
# kernel parity vs the dense masked reference
# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "B,H,D,page_size,pages_per_seq,lens,pool",
    [
        (4, 2, 16, 8, 3, (1, 8, 17, 24), "fp32"),  # single-token + full-page
        (2, 4, 32, 16, 2, (16, 32), "fp32"),       # exact page boundaries
        (3, 1, 8, 8, 4, (1, 9, 31), "fp32"),       # ragged mid-page
        (2, 2, 8, 4, 2, (3, 7), "fp32"),           # sub-lane tiny pages
        (2, 4, 96, 8, 2, (5, 16), "fp32"),         # head_dim no power of two
        # the serving cell's geometry (16 heads x 64, page 32): a
        # one-token, a mid-page, a page-boundary and a full sequence —
        # the shapes the TPU kernel rule admits, in every pool dtype
        (4, 16, 64, 32, 4, (1, 37, 64, 128), "fp32"),
        (4, 16, 64, 32, 4, (1, 37, 64, 128), "bf16"),
        (4, 16, 64, 32, 4, (1, 37, 64, 128), "int8"),
        # the kernel walks ceil(len / page) pages a row, nothing else:
        # every row ONE page; a row that ends on a page boundary beside
        # a full row (len = pages_per_seq x page) and a one-page row;
        # the cell's 32 pages a sequence, 1 to 32 of them live
        (4, 2, 16, 8, 3, (1, 1, 1, 1), "fp32"),
        (3, 2, 16, 8, 4, (8, 32, 1), "fp32"),
        (3, 2, 16, 8, 4, (8, 32, 1), "int8"),
        (4, 16, 64, 32, 32, (1, 33, 236, 1024), "fp32"),
        (4, 16, 64, 32, 32, (1, 33, 236, 1024), "int8"),
    ],
)
def test_ragged_kernel_matches_dense_reference(B, H, D, page_size,
                                               pages_per_seq, lens, pool):
    import jax.numpy as jnp

    from flexflow_tpu.kernels.ragged_paged_attention import (
        _pallas_ragged_paged,
        _xla_ragged_paged,
        _xla_ragged_paged_quant,
        dense_decode_reference,
        gather_kv_pages,
        paged_kernel_applies,
        ragged_paged_attention,
        ragged_paged_attention_quant,
    )
    from flexflow_tpu.ops.decode_attention import _quantize_kv

    rng = np.random.default_rng(0)
    P = B * pages_per_seq + 2  # pool larger than the allotment
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    # the pool as the op stores it: heads x head_dim fused, [P, page, H*D]
    kp = jnp.asarray(rng.normal(size=(P, page_size, H * D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P, page_size, H * D)), jnp.float32)
    pt = jnp.asarray(
        rng.permutation(P)[:B * pages_per_seq].reshape(B, pages_per_seq),
        jnp.int32)
    sl = jnp.asarray(lens, jnp.int32)
    scale = 1.0 / math.sqrt(D)
    scales = ()
    if pool == "bf16":
        kp, vp = kp.astype(jnp.bfloat16), vp.astype(jnp.bfloat16)
    if pool == "int8":
        (kp, ks), (vp, vs) = _quantize_kv(kp), _quantize_kv(vp)
        scales = (ks, vs)
        # the oracle sees the values the pool holds: dequantized
        k_held, v_held = kp * ks[..., None], vp * vs[..., None]
    else:
        k_held, v_held = kp, vp
    ref = dense_decode_reference(
        q, gather_kv_pages(k_held, pt, H), gather_kv_pages(v_held, pt, H),
        sl)
    if pool == "int8":
        got = ragged_paged_attention_quant(q, kp, vp, *scales, pt, sl)
        fb = _xla_ragged_paged_quant(q, kp, vp, *scales, pt, sl, scale)
    else:
        got = ragged_paged_attention(q, kp, vp, pt, sl)
        fb = _xla_ragged_paged(q, kp, vp, pt, sl, scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(fb), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)
    # THE shape rule; what fails it took the gather path above
    assert paged_kernel_applies(D, page_size) == (page_size % 8 == 0)
    # the kernel's arithmetic (each head's sum as a matmul with the 0/1
    # head-membership matrix) holds at every size
    pk = _pallas_ragged_paged(q, kp, vp, pt, sl, scale, True, *scales)
    np.testing.assert_allclose(np.asarray(pk), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("pool", ["fp32", "int8"])
def test_dead_pages_are_never_read(pool):
    """The kernel requests a sequence's LIVE pages and no other: every
    pool page that no live sequence owns holds NaN, and every
    ``page_table`` entry past a row's last live page points at one — a
    dead page copied and weighed by 0 would still turn the output NaN.
    (An int8 payload cannot hold a NaN: its scale rows do.)"""
    import jax.numpy as jnp

    from flexflow_tpu.kernels.ragged_paged_attention import (
        _pallas_ragged_paged,
        dense_decode_reference,
        gather_kv_pages,
    )
    from flexflow_tpu.ops.decode_attention import _quantize_kv

    B, H, D, page, pps = 4, 2, 16, 8, 6
    lens = (1, 8, 19, 48)  # 1, 1, 3 and all 6 pages live
    rng = np.random.default_rng(1)
    P = B * pps + 3
    kp = rng.normal(size=(P, page, H * D)).astype(np.float32)
    vp = rng.normal(size=(P, page, H * D)).astype(np.float32)
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    pt = rng.permutation(P)[:B * pps].reshape(B, pps).astype(np.int32)
    owned = np.zeros(P, bool)
    for b, n in enumerate(lens):
        owned[pt[b, :-(-n // page)]] = True
    dead = np.flatnonzero(~owned)
    assert len(dead) >= 3
    for b, n in enumerate(lens):
        live = -(-n // page)
        pt[b, live:] = dead[(b + np.arange(pps - live)) % len(dead)]
    kp, vp = jnp.asarray(kp), jnp.asarray(vp)
    scales = ()
    if pool == "int8":
        (kp, ks), (vp, vs) = _quantize_kv(kp), _quantize_kv(vp)
        k_clean, v_clean = kp * ks[..., None], vp * vs[..., None]
        scales = (ks.at[dead].set(jnp.nan), vs.at[dead].set(jnp.nan))
    else:
        k_clean, v_clean = kp, vp
        kp, vp = kp.at[dead].set(jnp.nan), vp.at[dead].set(jnp.nan)
    pt, sl = jnp.asarray(pt), jnp.asarray(lens, jnp.int32)
    # the oracle multiplies a masked position by 0: it takes the pool
    # as it was before the dead pages were poisoned
    ref = dense_decode_reference(
        q, gather_kv_pages(k_clean, pt, H), gather_kv_pages(v_clean, pt, H),
        sl)
    got = np.asarray(_pallas_ragged_paged(
        q, kp, vp, pt, sl, 1.0 / math.sqrt(D), True, *scales))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_kernel_grid_does_not_grow_with_pages_per_seq():
    """One grid step a sequence: the ``pallas_call``'s grid is the same
    at 32 and at 64 pages a sequence and has no axis of that length —
    the pages are walked inside the kernel, live ones only."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.kernels.ragged_paged_attention import (
        _pallas_ragged_paged,
    )

    B, H, D, page = 4, 2, 16, 8

    def grid(pps):
        S = jax.ShapeDtypeStruct
        pool = S((B * pps, page, H * D), jnp.float32)
        jaxpr = jax.make_jaxpr(
            lambda *a: _pallas_ragged_paged(*a, scale=0.25, interpret=True))(
            S((B, H, D), jnp.float32), pool, pool,
            S((B, pps), jnp.int32), S((B,), jnp.int32))
        calls = []

        def walk(jp):
            for eqn in jp.eqns:
                if eqn.primitive.name == "pallas_call":
                    calls.append(eqn)
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)

        walk(jaxpr.jaxpr)
        assert len(calls) == 1
        return tuple(calls[0].params["grid_mapping"].grid)

    g32, g64 = grid(32), grid(64)
    assert g32 == g64 == (B,)
    assert 32 not in g32 and 64 not in g64


def test_decode_op_incremental_matches_dense():
    """Stepping DecodeAttentionOp token by token must equal dense
    attention over every token cached so far — the cache scatter, the
    page indirection, and the +1 fresh-token length all proven against
    plain softmax."""
    import jax.numpy as jnp

    from flexflow_tpu.core.ptensor import ParallelTensorShape
    from flexflow_tpu.kernels.ragged_paged_attention import (
        dense_decode_reference,
    )
    from flexflow_tpu.ops.base import LoweringContext
    from flexflow_tpu.ops.decode_attention import DecodeAttentionOp

    B, E, H, ps, pps = 2, 32, 4, 4, 3
    op = DecodeAttentionOp(
        "dec",
        [ParallelTensorShape.make((B, 1, E), "float32"),
         ParallelTensorShape.make((B, pps), "int32"),
         ParallelTensorShape.make((B,), "int32")],
        embed_dim=E, num_heads=H, page_size=ps, pages_per_seq=pps)
    rng = np.random.default_rng(1)
    weights = {
        ws.name: jnp.asarray(rng.normal(size=ws.shape) * 0.1, jnp.float32)
        for ws in op._weight_specs
    }
    state = {}
    for name, shape, dtype, fill in op.state_specs():
        state[f"dec/{name}"] = jnp.full(shape, fill, dtype)
    # non-trivial page assignment (pages deliberately interleaved)
    pt = jnp.asarray([[1, 3, 5], [0, 2, 4]], jnp.int32)
    steps = ps * pps - 1
    xs = rng.normal(size=(steps, B, 1, E)).astype(np.float32)
    hist = []  # per-step hidden inputs, to rebuild dense K/V
    for t in range(steps):
        ctx = LoweringContext(compute_dtype=jnp.float32, train=False)
        ctx.state_in = state
        hidden = jnp.asarray(xs[t])
        lens = jnp.full((B,), t, jnp.int32)
        (out,) = op.forward(ctx, [hidden, pt, lens], weights)
        state = dict(state)
        state.update(ctx.state_out)
        hist.append(xs[t])
        # dense reference over every token so far
        x_all = jnp.asarray(np.stack(hist, axis=1)[:, :, 0, :])  # [B,t+1,E]
        qh = jnp.einsum("be,ehd->bhd", jnp.asarray(xs[t][:, 0, :]),
                        weights["wq"])
        kh = jnp.einsum("bse,ehd->bshd", x_all, weights["wk"])
        vh = jnp.einsum("bse,ehd->bshd", x_all, weights["wv"])
        ref_attn = dense_decode_reference(
            qh, kh, vh, jnp.full((B,), t + 1, jnp.int32))
        ref = jnp.einsum("bhd,hde->be", ref_attn, weights["wo"])[:, None, :]
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# KV-cache-aware memory accounting
# ---------------------------------------------------------------------------
def _decode_model(batch=16, **overrides):
    from flexflow_tpu.models import GPT_DECODE_KW, build_gpt_decode

    kw = dict(GPT_DECODE_KW)
    kw.update(overrides)
    cfg = ff.FFConfig(batch_size=batch, num_devices=N_DEV,
                      comp_mode="inference", cost_cache_file="",
                      search_budget=8, search_timeout_s=30.0)
    return build_gpt_decode(cfg, **kw), cfg


def test_kv_residency_enters_memory_accounting():
    from flexflow_tpu.search.machine_model import CostModel

    m, cfg = _decode_model()
    cm = CostModel(cfg.machine_spec, num_devices=N_DEV, inference=True)
    node = next(n for n in m.graph.topo_order()
                if n.op.op_type == OperatorType.DECODE_ATTENTION)
    triv = MachineView.trivial(3)
    dp = MachineView(dim_degrees=(8, 1, 1))
    tp = MachineView(dim_degrees=(1, 1, 1), replica_degree=8)
    kv_triv = node.op.kv_cache_bytes(triv)
    assert kv_triv == pytest.approx(
        node.op.attrs["num_pages"] * node.op.attrs["page_size"]
        * node.op.kv_bytes_per_token())
    # both batch and head splits genuinely divide residency
    assert node.op.kv_cache_bytes(dp) == pytest.approx(kv_triv / 8)
    assert node.op.kv_cache_bytes(tp) == pytest.approx(kv_triv / 8)
    # and op_memory carries the pool (strictly more than the weight
    # + activation memory of the same op with the hook detached)
    with_kv = cm.op_memory(node.op, triv)
    assert with_kv > kv_triv


def test_capacity_edge_rejected_inside_search():
    """On a machine whose HBM fits the page pool only when sharded,
    the unsharded strategy simulates to inf (the memory check) and the
    SEARCH returns a sharded strategy that fits — rejection happens at
    strategy-selection time, not at runtime OOM."""
    import dataclasses

    from flexflow_tpu.search.driver import optimize_strategy
    from flexflow_tpu.search.serving import kv_residency_bytes
    from flexflow_tpu.search.simulator import Simulator

    m, cfg = _decode_model()
    triv = _trivial_strategy(m.graph)
    sim0 = Simulator(cfg.machine_spec, num_devices=N_DEV, inference=True)
    need = sim0.peak_memory(m.graph, triv)
    # capacity window: the replicated pool blows it, 1/8 residency fits
    tight = dataclasses.replace(cfg.machine_spec, hbm_capacity=need / 2)
    cfg_tight = ff.FFConfig(
        batch_size=16, num_devices=N_DEV, comp_mode="inference",
        machine_spec=tight, cost_cache_file="", search_budget=8,
        search_timeout_s=30.0)
    sim = Simulator(tight, num_devices=N_DEV, inference=True)
    assert sim.simulate(m.graph, triv) == math.inf
    g, s = optimize_strategy(m.graph, cfg_tight, return_graph=True)
    cost = Simulator(tight, num_devices=N_DEV, inference=True).simulate(g, s)
    assert math.isfinite(cost), "search returned an HBM-infeasible strategy"
    assert kv_residency_bytes(g, s, N_DEV) < need / 2


# ---------------------------------------------------------------------------
# serve objective: divergence + inertness
# ---------------------------------------------------------------------------
def _search(objective, batch, kw):
    """(config, the search's whole record)."""
    from flexflow_tpu.models import build_gpt_decode
    from flexflow_tpu.search.driver import search_plan

    cfg = ff.FFConfig(batch_size=batch, num_devices=N_DEV,
                      search_budget=8, search_timeout_s=45.0,
                      objective=objective, comp_mode="inference",
                      cost_cache_file="")
    m = build_gpt_decode(cfg, **kw)
    return cfg, search_plan(m.graph, cfg)


def test_serve_objective_diverges_and_wins_p99():
    """THE acceptance scenario (inference serving, simulated): on the serving-regime decode config the serve
    objective picks a different strategy than throughput and wins on
    simulated p99 under the same arrival-model currency."""
    from flexflow_tpu.models import GPT_DECODE_SERVE_KW, SERVE_FRAME_SLOTS
    from flexflow_tpu.search.serving import serve_latency_quantiles

    cfg_t, plan_t = _search("train", SERVE_FRAME_SLOTS,
                            GPT_DECODE_SERVE_KW)
    assert plan_t.serving is None  # train run leaves no meta
    cfg_s, plan_s = _search("serve", SERVE_FRAME_SLOTS,
                            GPT_DECODE_SERVE_KW)
    g_t, s_t, g_s, s_s = (plan_t.graph, plan_t.strategy,
                          plan_s.graph, plan_s.strategy)
    assert _decode_views(g_t, s_t) != _decode_views(g_s, s_s)
    p99_t = serve_latency_quantiles(g_t, s_t, cfg_s)["p99"]
    p99_s = serve_latency_quantiles(g_s, s_s, cfg_s)["p99"]
    assert p99_s < p99_t, (p99_s, p99_t)
    meta = plan_s.serving
    assert meta is not None and meta["objective"] == "serve"
    assert meta["predicted_p99_step_ms"] > 0
    assert meta["kv_bytes_per_device"] > 0


def test_load_factor_monotone_in_batch_degree():
    from flexflow_tpu.search.serving import ServingSpec

    spec = ServingSpec(max_seqs=32, page_size=32, pages_per_seq=128)
    f = [spec.load_factor(d) for d in (1, 2, 4, 8, 16, 32)]
    assert all(0 < x <= 1.0 for x in f)
    # fewer sequences per shard = less averaging = fatter relative p99
    assert all(a <= b + 1e-9 for a, b in zip(f, f[1:])), f
    assert f[0] < f[-1]  # the imbalance amplification is non-trivial


def test_train_objective_is_structurally_inert(monkeypatch):
    """The default objective must never touch the serving machinery
    (the poisoned-builder discipline of test_co_search): a zoo search
    with objective='train' completes with serving_spec_for booby-
    trapped, and the cost/search cache keys are byte-identical to keys
    that predate the serving dimension."""
    from flexflow_tpu.models import build_mlp_unify
    from flexflow_tpu.search import serving as serving_mod
    from flexflow_tpu.search.cost_cache import cost_signature, CostCache
    from flexflow_tpu.search.driver import optimize_strategy
    from flexflow_tpu.search.machine_model import CostModel

    def _boom(*a, **k):  # pragma: no cover - must never run
        raise AssertionError("serving machinery touched under train")

    monkeypatch.setattr(serving_mod, "serving_spec_for", _boom)
    monkeypatch.setattr(serving_mod.ServingSpec, "load_factor", _boom)
    cfg = ff.FFConfig(batch_size=16, num_devices=N_DEV, search_budget=4,
                      search_timeout_s=20.0, cost_cache_file="")
    m = build_mlp_unify(cfg, in_dim=64, hidden=(64, 64))
    g, s = optimize_strategy(m.graph, cfg, return_graph=True)
    assert s
    # signature inertness: serving=None adds no key material
    cm = CostModel(cfg.machine_spec, num_devices=N_DEV)
    sig = cost_signature(cm)
    cm_no_attr = CostModel(cfg.machine_spec, num_devices=N_DEV)
    del cm_no_attr.__dict__["serving"]  # a pre-PR cost model shape
    assert cost_signature(cm_no_attr) == sig
    k_train = CostCache.search_key(m.graph, cfg)
    cfg2 = ff.FFConfig(batch_size=16, num_devices=N_DEV, search_budget=4,
                       search_timeout_s=20.0, cost_cache_file="")
    assert CostCache.search_key(m.graph, cfg2) == k_train
    cfg_serve = ff.FFConfig(batch_size=16, num_devices=N_DEV,
                            search_budget=4, search_timeout_s=20.0,
                            cost_cache_file="", objective="serve")
    assert CostCache.search_key(m.graph, cfg_serve) != k_train


def test_serve_objective_without_decode_ops_degenerates():
    from flexflow_tpu.models import build_mlp_unify
    from flexflow_tpu.search.driver import search_plan

    cfg = ff.FFConfig(batch_size=16, num_devices=N_DEV, search_budget=4,
                      search_timeout_s=20.0, cost_cache_file="",
                      objective="serve", comp_mode="inference")
    m = build_mlp_unify(cfg, in_dim=64, hidden=(64, 64))
    plan = search_plan(m.graph, cfg)
    assert plan.strategy and plan.serving is None


def test_serve_objective_requires_inference_mode():
    """A decode step has no backward: pricing the p99 currency with
    training costs would mint an SLO for a step that never runs — the
    driver refuses loudly instead (review finding)."""
    from flexflow_tpu.models import GPT_DECODE_KW, build_gpt_decode
    from flexflow_tpu.search.driver import optimize_strategy

    cfg = ff.FFConfig(batch_size=16, num_devices=N_DEV, search_budget=4,
                      search_timeout_s=20.0, cost_cache_file="",
                      objective="serve")  # comp_mode left at "training"
    m = build_gpt_decode(cfg, **GPT_DECODE_KW)
    with pytest.raises(ValueError, match="comp_mode='inference'"):
        optimize_strategy(m.graph, cfg, return_graph=True)


def test_co_search_refuses_serve_objective():
    with pytest.raises(ValueError, match="does not compose"):
        ff.FFConfig(objective="serve", co_search=True)


# ---------------------------------------------------------------------------
# SHD16x serving lints + STR209
# ---------------------------------------------------------------------------
def test_lint_serving_codes():
    import dataclasses

    from flexflow_tpu.analysis import errors_only, lint_serving
    from flexflow_tpu.search.machine_model import CostModel
    from flexflow_tpu.search.serving import ServingSpec, serving_spec_for

    m, cfg = _decode_model()
    strategy = _trivial_strategy(m.graph)
    cm = CostModel(cfg.machine_spec, num_devices=N_DEV, inference=True)
    spec = serving_spec_for(m.graph, cfg)
    assert not errors_only(lint_serving(m.graph, strategy, spec, cm))
    # SHD160: geometry disagreement with the decode ops
    wrong = dataclasses.replace(spec, page_size=spec.page_size * 2,
                                _factors={})
    codes = [f.code for f in lint_serving(m.graph, strategy, wrong, cm)]
    assert "SHD160" in codes
    # SHD160: missing spec entirely
    assert [f.code for f in lint_serving(m.graph, strategy, None, cm)] \
        == ["SHD160"]
    # SHD161: pool larger than HBM
    tiny = CostModel(
        dataclasses.replace(cfg.machine_spec, hbm_capacity=1e6),
        num_devices=N_DEV, inference=True)
    codes = [f.code for f in lint_serving(m.graph, strategy, spec, tiny)]
    assert "SHD161" in codes
    # SHD162: head split that does not divide the heads
    bad = dict(strategy)
    for n in m.graph.topo_order():
        if n.op.op_type == OperatorType.DECODE_ATTENTION:
            bad[n.guid] = MachineView(dim_degrees=(1, 1, 1),
                                      replica_degree=3)
    codes = [f.code for f in lint_serving(m.graph, bad, spec, cm)]
    assert "SHD162" in codes
    # SHD163: predicted p99 over the declared budget → warn, not error
    budget = dataclasses.replace(spec, p99_budget_ms=1e-6, _factors={})
    findings = lint_serving(m.graph, strategy, budget, cm,
                            predicted_p99_s=1.0)
    assert any(f.code == "SHD163" and f.severity == "warn"
               for f in findings)
    assert not errors_only(findings)
    # driver behavior when NO strategy can hold the pool: the search's
    # memory check prices everything inf, the result is returned for
    # compile's fallback machinery (the train-objective contract), and
    # no serving meta is minted for the infeasible artifact
    import dataclasses as _dc

    from flexflow_tpu.search.driver import search_plan
    from flexflow_tpu.search.simulator import Simulator

    floor_bytes = sum(
        n.op.kv_cache_bytes(MachineView(dim_degrees=(8, 1, 1)))
        for n in m.graph.topo_order()
        if n.op.op_type == OperatorType.DECODE_ATTENTION)
    hopeless = _dc.replace(cfg.machine_spec, hbm_capacity=floor_bytes / 2)
    cfg_bad = ff.FFConfig(
        batch_size=16, num_devices=N_DEV, comp_mode="inference",
        machine_spec=hopeless, cost_cache_file="", search_budget=4,
        search_timeout_s=20.0, objective="serve")
    bad = search_plan(m.graph, cfg_bad)
    g_bad, s_bad = bad.graph, bad.strategy
    assert bad.serving is None
    assert Simulator(hopeless, num_devices=N_DEV,
                     inference=True).simulate(g_bad, s_bad) == math.inf


def test_str209_serving_meta_lint(tmp_path):
    import sys

    sys.path.insert(0, "tools")
    try:
        from fflint import lint_strategy_file
    finally:
        sys.path.pop(0)

    good_meta = {
        "graph_digest": "d" * 32,
        "serving": {"objective": "serve", "max_seqs": 16,
                    "page_size": 16, "pages_per_seq": 16,
                    "quantile": 0.99, "p99_budget_ms": 0.0,
                    "predicted_p99_step_ms": 0.05,
                    "kv_bytes_per_device": 2.1e6},
    }
    base = {"lm_head": {"dims": [8, 1, 1], "replica": 1, "start": 0}}

    def write(meta):
        p = tmp_path / "strategy.json"
        p.write_text(json.dumps({**base, "__meta__": meta}))
        return str(p)

    assert not [f for f in lint_strategy_file(write(good_meta))
                if f[1] == "STR209"]
    corruptions = [
        ("not-an-object", {**good_meta, "serving": [1, 2]}),
        ("wrong objective", {**good_meta, "serving": {
            **good_meta["serving"], "objective": "train"}}),
        ("zero max_seqs", {**good_meta, "serving": {
            **good_meta["serving"], "max_seqs": 0}}),
        ("bool page_size", {**good_meta, "serving": {
            **good_meta["serving"], "page_size": True}}),
        ("quantile 1.5", {**good_meta, "serving": {
            **good_meta["serving"], "quantile": 1.5}}),
        ("negative budget", {**good_meta, "serving": {
            **good_meta["serving"], "p99_budget_ms": -1}}),
        ("nan p99", {**good_meta, "serving": {
            **good_meta["serving"], "predicted_p99_step_ms": float("nan")}}),
        ("negative kv", {**good_meta, "serving": {
            **good_meta["serving"], "kv_bytes_per_device": -5}}),
    ]
    for label, meta in corruptions:
        found = [f for f in lint_strategy_file(write(meta))
                 if f[1] == "STR209" and f[0] == "error"]
        assert found, f"corruption {label!r} not caught by STR209"


def test_serving_meta_round_trip(tmp_path):
    """compile(objective=serve) persists __meta__.serving behind the
    digest gate; import re-lints it (SHD16x) against the target graph."""
    from flexflow_tpu.models import GPT_DECODE_KW, build_gpt_decode
    from flexflow_tpu.search.strategy_io import read_meta

    path = str(tmp_path / "serve_strategy.json")
    kw = dict(GPT_DECODE_KW)
    cfg = ff.FFConfig(batch_size=8, num_devices=N_DEV, search_budget=0,
                      objective="serve", cost_cache_file="",
                      export_strategy_file=path)
    m = build_gpt_decode(cfg, **kw)
    m.compile(loss_type="sparse_categorical_crossentropy", metrics=[],
              comp_mode="inference")
    meta = read_meta(path)
    assert meta.get("serving", {}).get("objective") == "serve"
    assert meta["serving"]["max_seqs"] == 8
    # re-import: the serving block re-lints against THIS graph
    cfg2 = ff.FFConfig(batch_size=8, num_devices=N_DEV,
                       import_strategy_file=path, cost_cache_file="")
    m2 = build_gpt_decode(cfg2, **kw)
    m2.compile(loss_type="sparse_categorical_crossentropy", metrics=[],
               comp_mode="inference")
    assert m2.strategy
    # a corrupted geometry must fail the import gate with findings
    from flexflow_tpu.analysis import AnalysisError

    data = json.load(open(path))
    data["__meta__"]["serving"]["page_size"] = 64
    bad_path = str(tmp_path / "bad.json")
    json.dump(data, open(bad_path, "w"))
    cfg3 = ff.FFConfig(batch_size=8, num_devices=N_DEV,
                       import_strategy_file=bad_path, cost_cache_file="")
    m3 = build_gpt_decode(cfg3, **kw)
    with pytest.raises(AnalysisError):
        m3.compile(loss_type="sparse_categorical_crossentropy",
                   metrics=[], comp_mode="inference")


# ---------------------------------------------------------------------------
# continuous-batching executor
# ---------------------------------------------------------------------------
def _synthetic_step(vocab=97):
    """Deterministic model stand-in: the next token is a pure function
    of (current token, position) — enough structure that scheduling
    bugs (wrong slot, wrong position, corrupted cache) change the
    output stream."""

    def step(ids, table, lens):
        ids = np.asarray(ids)
        lens = np.asarray(lens)
        nxt = (ids[:, 0] * 7 + lens * 13 + 5) % vocab
        logits = np.zeros((ids.shape[0], 1, vocab), np.float32)
        logits[np.arange(ids.shape[0]), 0, nxt] = 1.0
        return logits

    return step


def test_executor_batched_equals_solo():
    """Continuous batching must be semantically invisible: each
    request's generated tokens equal serving it ALONE."""
    from flexflow_tpu.runtime.decode import (
        ContinuousBatchingExecutor,
        DecodeRequest,
    )

    reqs = [
        DecodeRequest(rid=f"r{i}", prompt=[3 + i, 11, 2 * i + 1],
                      max_new_tokens=3 + (i % 3))
        for i in range(7)
    ]
    solo = {}
    for r in reqs:
        ex = ContinuousBatchingExecutor(
            _synthetic_step(), max_seqs=1, page_size=4, pages_per_seq=4)
        solo.update(ex.run([DecodeRequest(rid=r.rid, prompt=list(r.prompt),
                                          max_new_tokens=r.max_new_tokens)]))
    # 3 slots, pages for only 2 concurrent sequences: admission waits
    ex = ContinuousBatchingExecutor(
        _synthetic_step(), max_seqs=3, page_size=4, pages_per_seq=4,
        num_pages=8)
    batched = ex.run(reqs, max_frames=400)
    assert batched == solo
    s = ex.summary()
    assert s["completed"] == len(reqs)
    assert s["admitted"] == len(reqs) and s["evicted"] == len(reqs)
    # every sequence page returned; only the oversubscribed pool's
    # permanently reserved scratch page stays out
    assert ex.allocator.pages_in_use == 1 and not ex.slot_aligned


def test_executor_exhausted_pool_never_corrupts_live_cache():
    """Review-finding regression: an OVERSUBSCRIBED pool fully
    exhausted by one live sequence while other slots sit idle — the
    idle rows' unavoidable scatter must land on the reserved scratch
    page, never on the live sequence's page 0 (whose slot 0 holds its
    FIRST cached token).  Proven end-to-end on the compiled decode
    graph: batched tokens equal serving the request alone."""
    from flexflow_tpu.models import build_gpt_decode
    from flexflow_tpu.runtime.decode import (
        ContinuousBatchingExecutor,
        DecodeRequest,
        compiled_decode_step,
    )

    kw = dict(vocab=128, num_layers=1, hidden=32, num_heads=2,
              ff_dim=32, page_size=2, pages_per_seq=2, num_pages=3)
    req = DecodeRequest(rid="a", prompt=[7, 11], max_new_tokens=2)

    def run(num_pages):
        cfg = ff.FFConfig(batch_size=2, num_devices=1, cost_cache_file="")
        m = build_gpt_decode(cfg, **kw)
        m.compile(loss_type="sparse_categorical_crossentropy",
                  metrics=[], comp_mode="inference",
                  strategy=_trivial_strategy(m.graph))
        ex = ContinuousBatchingExecutor(
            compiled_decode_step(m), max_seqs=2, page_size=2,
            pages_per_seq=2, num_pages=num_pages)
        return ex.run([DecodeRequest(rid="a", prompt=list(req.prompt),
                                     max_new_tokens=2)], max_frames=40)

    # pool 3: scratch reserved -> 2 usable -> the live sequence holds
    # EVERY allocatable page while slot 1 idles (the corruption regime)
    assert run(3) == run(4)  # 4 = slot-aligned, trivially safe


def test_executor_counts_live_pages_and_page_slots():
    """``decode.live_pages`` adds, a frame, the pages the ragged kernel
    walks — ceil((cached + 1) / page) of EVERY row, an idle row one —
    and ``decode.page_slots`` the slots x pages a sequence that a grid
    over page slots would step: both worked out by hand here."""
    from flexflow_tpu.obs.metrics import METRICS
    from flexflow_tpu.runtime.decode import (
        ContinuousBatchingExecutor,
        DecodeRequest,
    )

    seen = []
    inner = _synthetic_step()

    def step(ids, table, lens):
        seen.append(np.asarray(lens).copy())
        return inner(ids, table, lens)

    live, slots = (METRICS.counter(f"decode.{n}")
                   for n in ("live_pages", "page_slots"))
    live0, slots0 = live.value, slots.value
    ex = ContinuousBatchingExecutor(step, max_seqs=2, page_size=4,
                                    pages_per_seq=4)
    ex.run([DecodeRequest(rid="a", prompt=[5, 6, 7], max_new_tokens=4)],
           max_frames=40)
    # one request in slot 0, slot 1 idle: the frames cache 0..5 tokens
    # (three prompt tokens, then a fresh token a frame)
    assert [tuple(x) for x in seen] == [(c, 0) for c in range(6)]
    # slot 0 walks 1, 1, 1, 1, 2, 2 pages (it attends its fresh token
    # too: cached 3 fills page 0, cached 4 opens page 1); the idle row
    # one page a frame
    assert live.value - live0 == (1 + 1 + 1 + 1 + 2 + 2) + 6
    assert slots.value - slots0 == 6 * 2 * 4


def test_executor_page_accounting_and_caps():
    from flexflow_tpu.runtime.decode import (
        ContinuousBatchingExecutor,
        DecodeRequest,
        PageAllocator,
    )

    pa = PageAllocator(4)
    got = pa.alloc(3)
    assert pa.free_pages == 1 and pa.pages_in_use == 3
    assert pa.alloc(2) is None  # refuse partial allotments
    pa.free(got)
    assert pa.free_pages == 4
    ex = ContinuousBatchingExecutor(
        _synthetic_step(), max_seqs=2, page_size=4, pages_per_seq=2)
    with pytest.raises(AssertionError):  # request longer than a sequence
        ex.submit([DecodeRequest(rid="x", prompt=[1] * 7,
                                 max_new_tokens=9)])


def test_executor_on_compiled_decode_model():
    """End-to-end: the executor drives the COMPILED decode graph (KV
    caches threaded as model state) and emits schema-valid obs
    events + a decode DriftReport."""
    from flexflow_tpu.models import build_gpt_decode
    from flexflow_tpu.obs.events import BUS, validate_event
    from flexflow_tpu.runtime.decode import (
        ContinuousBatchingExecutor,
        DecodeRequest,
        compiled_decode_step,
    )

    kw = dict(vocab=256, num_layers=1, hidden=64, num_heads=4,
              ff_dim=64, page_size=4, pages_per_seq=4)
    cfg = ff.FFConfig(batch_size=4, num_devices=1, cost_cache_file="")
    m = build_gpt_decode(cfg, **kw)
    m.compile(loss_type="sparse_categorical_crossentropy", metrics=[],
              comp_mode="inference",
              strategy=_trivial_strategy(m.graph))
    import tempfile

    log = tempfile.mktemp(suffix=".jsonl")
    BUS.configure(log)
    try:
        ex = ContinuousBatchingExecutor(
            compiled_decode_step(m), max_seqs=4, page_size=4,
            pages_per_seq=4, num_pages=8, predicted_step_s=1e-4)
        out = ex.run([DecodeRequest(rid=f"r{i}", prompt=[1 + i, 2],
                                    max_new_tokens=3) for i in range(5)],
                     max_frames=120)
        assert len(out) == 5
        assert all(len(v) == 3 for v in out.values())
        rep = ex.decode_drift_report()
        assert rep is not None and "decode" in rep.phases
        BUS.flush()
        with open(log) as f:
            for line in f:
                assert validate_event(json.loads(line)) == []
    finally:
        BUS.close()
        import os

        os.remove(log)


def test_donated_state_has_one_live_owner():
    """The frame, the prefill chunk and the page copy DONATE the KV
    state (a call updates the pool in place), so the arrays a call
    consumed are dead: the live pool is ``model.state``, whichever
    step ran last.  Two steps over one model, ``model.state`` and
    ``step.state`` read between and after calls, and a page copy all
    keep working and never touch a deleted buffer."""
    import jax

    from flexflow_tpu.models import build_gpt_decode
    from flexflow_tpu.runtime.decode import (
        ContinuousBatchingExecutor,
        DecodeRequest,
        compiled_decode_step,
    )

    kw = dict(vocab=256, num_layers=2, hidden=64, num_heads=4,
              ff_dim=64, page_size=4, pages_per_seq=4)
    cfg = ff.FFConfig(batch_size=4, num_devices=1, cost_cache_file="")
    m = build_gpt_decode(cfg, **kw)
    m.compile(loss_type="sparse_categorical_crossentropy", metrics=[],
              comp_mode="inference", strategy=_trivial_strategy(m.graph))
    chunked = compiled_decode_step(m, prefill_chunk=4)
    plain = compiled_decode_step(m)

    # the state, and only the state, is donated into both programs
    ints = lambda *shape: np.zeros(shape, np.int32)  # noqa: E731
    for lowered in (
            chunked.frame_fn.lower(m.params, m.state,
                                   [ints(4, 1), ints(4, 4), ints(4)]),
            chunked.chunk_fn.lower(m.params, m.state, ints(1, 16),
                                   ints(1, 16), ints(1, 4), np.int32(1))):
        p_info, s_info = lowered.args_info[0][:2]
        assert all(a.donated for a in jax.tree.leaves(s_info))
        assert not any(a.donated for a in jax.tree.leaves(p_info))

    def serve(step):
        ex = ContinuousBatchingExecutor(
            step, max_seqs=4, page_size=4, pages_per_seq=4,
            prefill_fn=getattr(step, "prefill", None),
            prefill_chunk=4 if hasattr(step, "prefill") else 0)
        return ex.run([DecodeRequest(rid=f"r{i}", prompt=[1 + i, 2, 3, 4, 5, 6],
                                     max_new_tokens=3) for i in range(3)],
                      max_frames=120)

    def live():
        # what a step's frame takes: the model's live state and the
        # step's own last tokens
        for step in (chunked, plain):
            state = dict(step.state["state"])
            assert state.pop("last_tokens").shape == (4,)
            assert state.keys() == m.state.keys()
            assert all(state[k] is m.state[k] for k in state)
        assert not any(v.is_deleted() for v in m.state.values())
        return {k: np.asarray(v) for k, v in m.state.items()}

    first = m.state
    assert all(np.all(v == 0) for v in live().values())
    want = serve(chunked)
    # on a backend that honours donation the consumed arrays are gone;
    # the live ones are wherever model.state points
    assert first is not m.state
    assert any(np.any(v != 0) for v in live().values())
    assert serve(plain) == want      # the other step, same model
    live()
    assert serve(chunked) == want    # and back, over the pool it left
    before = live()
    chunked.copy_page(1, 9)
    after = live()
    for key, val in after.items():
        np.testing.assert_array_equal(val[9], before[key][1])
        np.testing.assert_array_equal(np.delete(val, 9, axis=0),
                                      np.delete(before[key], 9, axis=0))


def test_paged_kernel_rule_and_pool_shape():
    """THE shape rule and the pool's spec: head sizes and pages of
    whole 8-row tiles take the kernel; every pool
    is [pages, page, heads * head_dim] — heads fused on the minor axis
    — in the searched pool dtype, an int8 pool with [pages, page] fp32
    scales beside it."""
    import jax.numpy as jnp

    from flexflow_tpu.core.ptensor import ParallelTensorShape
    from flexflow_tpu.kernels.ragged_paged_attention import (
        paged_kernel_applies,
    )
    from flexflow_tpu.ops.decode_attention import DecodeAttentionOp

    assert paged_kernel_applies(64, 32) and paged_kernel_applies(8, 8)
    assert paged_kernel_applies(96, 32)
    assert not paged_kernel_applies(4, 8)
    assert not paged_kernel_applies(64, 4)
    for kvd, dtype in (("fp32", jnp.float32), ("bf16", jnp.bfloat16),
                       ("int8", jnp.int8)):
        op = DecodeAttentionOp(
            "dec",
            [ParallelTensorShape.make((2, 1, 128), "float32"),
             ParallelTensorShape.make((2, 3), "int32"),
             ParallelTensorShape.make((2,), "int32")],
            embed_dim=128, num_heads=2, page_size=8, pages_per_seq=3,
            kv_dtype=kvd)
        specs = {name: (shape, dt) for name, shape, dt, _ in op.state_specs()}
        assert specs["k_cache"] == specs["v_cache"] == ((6, 8, 128), dtype)
        assert set(specs) - {"k_cache", "v_cache"} == (
            {"k_scale", "v_scale"} if kvd == "int8" else set())
        assert op.attention_path(multi_device=False) == "pallas"
        assert op.attention_path(multi_device=True) == "xla"


def test_paged_kernel_rule_on_the_chip_wants_whole_lane_rows(monkeypatch):
    """On the TPU the kernel DMAs ``[page, H·D]`` pages out of the pool
    and Mosaic slices only whole 128-lane rows: the fused width decides
    there; the interpreter takes any width (how the tests above run the
    kernel at tiny shapes)."""
    import jax

    from flexflow_tpu.kernels.ragged_paged_attention import (
        paged_kernel_applies,
    )

    assert paged_kernel_applies(32, 8, 2) and paged_kernel_applies(96, 32, 3)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert paged_kernel_applies(64, 32, 16) and paged_kernel_applies(64, 32, 8)
    assert paged_kernel_applies(96, 32, 4)  # 384 lanes
    assert not paged_kernel_applies(32, 8, 2)  # 64 lanes
    assert not paged_kernel_applies(96, 32, 3)  # 288 lanes
    assert not paged_kernel_applies(64, 4, 16)  # the block rule still holds


def test_decode_graph_searched_strategy_executes():
    """A SEARCHED multi-device decode strategy lowers and steps on the
    host mesh — the state-sharded KV cache path is executable, not
    just priced."""
    from flexflow_tpu.models import build_gpt_decode
    from flexflow_tpu.runtime.decode import (
        ContinuousBatchingExecutor,
        DecodeRequest,
        compiled_decode_step,
    )

    kw = dict(vocab=256, num_layers=1, hidden=64, num_heads=4,
              ff_dim=64, page_size=4, pages_per_seq=4)
    cfg = ff.FFConfig(batch_size=8, num_devices=N_DEV,
                      search_budget=4, search_timeout_s=20.0,
                      cost_cache_file="",
                      machine_spec=MachineSpec.host_cpu(N_DEV))
    m = build_gpt_decode(cfg, **kw)
    m.compile(loss_type="sparse_categorical_crossentropy", metrics=[],
              comp_mode="inference")
    ex = ContinuousBatchingExecutor(
        compiled_decode_step(m), max_seqs=8, page_size=4,
        pages_per_seq=4)
    out = ex.run([DecodeRequest(rid="a", prompt=[5, 6, 7],
                                max_new_tokens=4)], max_frames=60)
    assert len(out["a"]) == 4


# ---------------------------------------------------------------------------
# the served weight tree: what the frame and the prefill chunk are handed
# ---------------------------------------------------------------------------
SERVED_KW = dict(vocab=97, num_layers=2, hidden=32, num_heads=4, ff_dim=64,
                 page_size=8, pages_per_seq=4)  # heads of 8: the kernel runs


def _served_model(compute_dtype="bfloat16", seed=3, kw=SERVED_KW,
                  **ffconfig):
    from flexflow_tpu.models import build_gpt_decode

    cfg = ff.FFConfig(batch_size=4, num_devices=1, cost_cache_file="",
                      compute_dtype=compute_dtype, seed=seed, **ffconfig)
    m = build_gpt_decode(cfg, **kw)
    m.compile(loss_type="sparse_categorical_crossentropy", metrics=[],
              comp_mode="inference")
    return m


def _over_master(model, step):
    """``step``'s two programs handed ``model.params`` itself — the
    fp32 [E, H, D] tree, converted inside every call — behind the
    interface of ``step``."""
    def frame(ids, page_table, seq_lens):
        (logits, _), model.state = step.frame_fn(
            model.params, model.state, [ids, page_table, seq_lens])
        return logits

    def prefill(ids, positions, page_table):
        # the run padded to the context in whole chunks of 4, as
        # ``step.prefill`` pads it: one program for every run
        width = SERVED_KW["page_size"] * SERVED_KW["pages_per_seq"]
        run = [np.zeros((1, width), np.int32) for _ in range(2)]
        run[0][:, :ids.shape[1]], run[1][:, :ids.shape[1]] = ids, positions
        model.state = step.chunk_fn(model.params, model.state, *run,
                                    page_table, np.int32(ids.shape[1] // 4))

    frame.prefill, frame.copy_page = prefill, step.copy_page
    frame.attention_path = step.attention_path
    return frame


def _serve_recording(model, step, prefix_sharing):
    """Six requests behind one shared prefix through chunked prefill and
    the executor, over a zeroed pool: (every frame's logits, the tokens
    handed back, the pool as it was left)."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.runtime.decode import (
        ContinuousBatchingExecutor,
        DecodeRequest,
    )

    model.state = jax.tree.map(jnp.zeros_like, model.state)
    logits = []

    def recording(ids, page_table, seq_lens):
        out = step(ids, page_table, seq_lens)
        logits.append(np.asarray(out, np.float32))
        return out

    extra = (dict(prefix_sharing=True, copy_page_fn=step.copy_page)
             if prefix_sharing else {})
    ex = ContinuousBatchingExecutor(
        recording, max_seqs=4, page_size=SERVED_KW["page_size"],
        pages_per_seq=SERVED_KW["pages_per_seq"], prefill_fn=step.prefill,
        prefill_chunk=4, **extra)
    rng = np.random.default_rng(0)
    shared = rng.integers(1, 97, size=11).tolist()
    tokens = ex.run([
        DecodeRequest(rid=f"r{i}", max_new_tokens=5,
                      prompt=shared + rng.integers(1, 97, size=2 + i).tolist())
        for i in range(6)], max_frames=200)
    return (np.stack(logits), tokens,
            {k: np.asarray(v) for k, v in model.state.items()})


@pytest.mark.parametrize("variant", ["fp32_pool", "int8_pool",
                                     "prefix_sharing"])
@pytest.mark.parametrize("compute_dtype", ["bfloat16", "float32"])
def test_served_tree_and_master_tree_serve_bit_identically(compute_dtype,
                                                           variant):
    """THE numerics contract of the served tree: rounding a matmul's
    weights to the compute dtype once, at build, and storing the
    projections fused is the SAME arithmetic as doing both inside every
    call — every frame's logits, the pool the chunks and frames left
    and every token are bit-identical, also under an int8 pool and
    under prefix sharing."""
    from flexflow_tpu.runtime.decode import compiled_decode_step

    lane = (dict(objective="serve", kv_precision="int8")
            if variant == "int8_pool" else {})
    m = _served_model(compute_dtype, **lane)
    step = compiled_decode_step(m, prefill_chunk=4)
    assert step.attention_path == "pallas"
    sharing = variant == "prefix_sharing"
    logits, tokens, pool = _serve_recording(m, step, sharing)
    logits0, tokens0, pool0 = _serve_recording(
        m, _over_master(m, step), sharing)
    assert tokens == tokens0 and all(len(t) == 5 for t in tokens.values())
    np.testing.assert_array_equal(logits, logits0)
    assert pool.keys() == pool0.keys()
    if variant == "int8_pool":
        assert {v.dtype for v in pool.values()} == {np.dtype(np.int8),
                                                    np.dtype(np.float32)}
    for key, val in pool.items():
        np.testing.assert_array_equal(val, pool0[key], err_msg=key)
    assert any(np.any(v != 0) for v in pool.values())


@pytest.mark.parametrize("compute_dtype", ["bfloat16", "float32"])
def test_served_tree_holds_matmul_leaves_as_the_matmuls_read_them(
        compute_dtype):
    """``step.weights``: every matmul's operand in the compute dtype,
    2-D, lane-dense (the attention projections fused to [E, H·D] /
    [H·D, E]); embedding tables, norms and biases THE master's arrays;
    ``model.params`` untouched; the registry says what was built."""
    import jax.numpy as jnp

    from flexflow_tpu.obs.exposition import render_prometheus
    from flexflow_tpu.obs.metrics import METRICS
    from flexflow_tpu.runtime.decode import compiled_decode_step

    m = _served_model(compute_dtype)
    master = m.params
    prepares = METRICS.counter("decode.weight_prepares").value
    step = compiled_decode_step(m, prefill_chunk=4)
    assert METRICS.counter("decode.weight_prepares").value == prepares + 1
    assert m.params is master
    cd, hd = jnp.dtype(compute_dtype), SERVED_KW["hidden"]
    matmul = {"wq", "wk", "wv", "wo", "kernel"}
    assert step.weights.keys() == master.keys()
    for name, ws in master.items():
        assert step.weights[name].keys() == ws.keys()
        for w, leaf in ws.items():
            served = step.weights[name][w]
            if w in matmul:
                assert served.dtype == cd and served.ndim == 2, (name, w)
                assert served.shape[-1] >= hd, (name, w, served.shape)
                assert int(np.prod(served.shape)) == int(np.prod(leaf.shape))
                # a kernel already in the compute dtype is served as it is
                assert (served is leaf) == (
                    leaf.dtype == cd and leaf.ndim == 2), (name, w)
            else:
                assert served is leaf, (name, w)
            assert leaf.dtype == jnp.float32
    attn = master["layer0_mha"]
    assert attn["wq"].shape == (hd, 4, 8) and attn["wo"].shape == (4, 8, hd)
    assert step.weights["layer0_mha"]["wo"].shape == (hd, hd)

    def nbytes(tree):
        return sum(x.nbytes for ws in tree.values() for x in ws.values())

    gauges = METRICS.snapshot()["gauges"]
    assert gauges["decode.weight_bytes"] == nbytes(step.weights)
    assert gauges["decode.weight_bytes_master"] == nbytes(master)
    halved = sum(x.nbytes for ws in master.values()
                 for w, x in ws.items() if w in matmul) // 2
    assert nbytes(master) - nbytes(step.weights) == (
        halved if compute_dtype == "bfloat16" else 0)
    text = render_prometheus(METRICS.snapshot())
    for name in ("decode_weight_prepares", "decode_weight_bytes",
                 "decode_weight_bytes_master"):
        assert name in text, name


def _weight_converts(lowered_text: str):
    """(shape, from, to) of every ``convert`` in ``main`` applied
    straight to an argument of rank 2 or more — of ``main``, or of the
    prefill program's loop over chunks, which hands the weights into
    its body as loop values."""
    import re

    main = lowered_text[lowered_text.index("func.func public @main"):]
    main = main.split("func.func private")[0]
    return [m.groups() for m in re.finditer(
        r"stablehlo\.convert %(?:arg|iterArg)[_\d]+ : "
        r"\(tensor<((?:\d+x){2,})(\w+)>\) -> tensor<(?:\d+x)+(\w+)>", main)]


def test_frame_over_the_served_tree_converts_no_weight(monkeypatch):
    """The program the server runs reads its matmul weights as they
    are: lowered over ``step.weights`` the frame and the chunk hold NO
    convert of a weight argument, where over ``model.params`` they hold
    one for every matmul leaf it reads (six a layer and the head; the
    prefill program ends at the last layer's cache write, so that
    layer's FFN and the head are never traced — its wo is, inside the
    loop over chunks, for an output nothing reads, and the compiler
    drops it).  Both still lower, with the same Mosaic calls."""
    import jax

    from flexflow_tpu.runtime.decode import compiled_decode_step

    # two heads of 64: on the chip the kernel wants whole 128-lane rows
    m = _served_model("bfloat16", kw=dict(SERVED_KW, hidden=128,
                                          num_heads=2))
    step = compiled_decode_step(m, prefill_chunk=4)
    layers = SERVED_KW["num_layers"]
    ints = lambda *shape: np.zeros(shape, np.int32)  # noqa: E731
    frame_ins = [ints(4, 1), ints(4, 4), ints(4)]
    chunk_ins = (ints(1, 32), ints(1, 32), ints(1, 4), np.int32(1))
    for tree, per_layer in ((step.weights, 0), (m.params, 6)):
        frame = step.frame_fn.lower(tree, m.state, frame_ins).as_text()
        chunk = step.chunk_fn.lower(tree, m.state, *chunk_ins).as_text()
        got = _weight_converts(frame)
        assert len(got) == (per_layer * layers + 1 if per_layer else 0), got
        assert all(src == "f32" and dst == "bf16" for _, src, dst in got)
        assert len(_weight_converts(chunk)) == (
            per_layer * layers - 2 if per_layer else 0)
    # lowered FOR the chip (the kernel picks the interpreter off-TPU by
    # the default backend, when it is traced: a step of its own), either
    # tree holds the kernel
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    chip = compiled_decode_step(m)
    calls = {chip.frame_fn.trace(tree, m.state, frame_ins).lower(
                 lowering_platforms=("tpu",)).as_text().count(
                     "tpu_custom_call")
             for tree in (chip.weights, m.params)}
    # (StableHLO keeps one function for the layers' identical kernels)
    assert calls == {1}, calls


def test_restored_params_are_served_anew(tmp_path):
    """``model.params`` replaced (a checkpoint of other values restored
    into the serving model): the next call derives the served tree
    again — ``decode.weight_prepares`` 1 → 2 — and the logits are the
    new weights', bit for bit; a call on the same tree derives
    nothing."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.obs.metrics import METRICS
    from flexflow_tpu.runtime.checkpoint import CheckpointManager
    from flexflow_tpu.runtime.decode import compiled_decode_step

    m = _served_model("bfloat16", seed=3)
    prepares = METRICS.counter("decode.weight_prepares")
    n0 = prepares.value
    step = compiled_decode_step(m)
    ids = np.arange(1, 5, dtype=np.int32)[:, None]
    table = np.arange(16, dtype=np.int32).reshape(4, 4)
    lens = np.zeros((4,), np.int32)

    def frame_over(params):
        return np.asarray(step.frame_fn(  # the state is donated: a copy
            params, jax.tree.map(jnp.copy, m.state),
            [ids, table, lens])[0][0])

    want_old = frame_over(m.params)
    old = np.asarray(step(ids, table, lens))
    np.asarray(step(ids, table, lens))
    assert prepares.value == n0 + 1
    served_old = step.weights

    donor = _served_model("bfloat16", seed=11)
    with CheckpointManager(str(tmp_path)) as mgr:
        mgr.save(0, donor)
        mgr.wait()
        mgr.restore(m)
    new = np.asarray(step(ids, table, lens))
    assert prepares.value == n0 + 2
    assert step.weights is not served_old
    np.asarray(step(ids, table, lens))
    assert prepares.value == n0 + 2
    np.testing.assert_array_equal(old, want_old)
    np.testing.assert_array_equal(new, frame_over(m.params))
    np.testing.assert_array_equal(new, frame_over(donor.params))
    assert np.abs(new - old).max() > 1e-2


def test_fit_builds_no_served_tree():
    """Training never serves: a ``fit`` of the tiny training model
    leaves ``decode.weight_prepares`` where it was."""
    from flexflow_tpu.models import build_gpt
    from flexflow_tpu.obs.metrics import METRICS

    prepares = METRICS.counter("decode.weight_prepares")
    n0 = prepares.value
    cfg = ff.FFConfig(batch_size=2, num_devices=1, cost_cache_file="",
                      compute_dtype="bfloat16", epochs=1)
    m = build_gpt(cfg, vocab=64, seq_len=16, num_layers=1, hidden=32,
                  num_heads=2, ff_dim=32)
    m.compile(loss_type="sparse_categorical_crossentropy", metrics=[])
    rng = np.random.default_rng(0)
    x = rng.integers(0, 64, size=(4, 16)).astype(np.int32)
    m.fit(x, np.roll(x, -1, axis=1), epochs=1)
    assert prepares.value == n0


def test_served_tree_keeps_the_masters_shardings():
    """Sharded weights (heads over four of the CPU's virtual devices,
    the first FFN's columns too): each served leaf is laid out as its
    master is — a head split of [E, H, D] is the same split of the
    fused [E, H·D] — and the frame over it equals the frame over the
    sharded fp32 tree bit for bit."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec

    from flexflow_tpu.models import build_gpt_decode
    from flexflow_tpu.runtime.decode import compiled_decode_step

    kw = dict(vocab=256, num_layers=1, hidden=64, num_heads=4, ff_dim=64,
              page_size=4, pages_per_seq=4)
    cfg = ff.FFConfig(batch_size=8, num_devices=N_DEV, cost_cache_file="",
                      compute_dtype="bfloat16",
                      machine_spec=MachineSpec.host_cpu(N_DEV))
    m = build_gpt_decode(cfg, **kw)
    strategy = _trivial_strategy(m.graph)
    for n in m.graph.topo_order():
        if n.op.op_type == OperatorType.DECODE_ATTENTION:
            strategy[n.guid] = MachineView(dim_degrees=(1, 1, 1),
                                           replica_degree=4)
        elif n.op.name.endswith("ff1"):
            strategy[n.guid] = MachineView(dim_degrees=(1, 1, 4))
    m.compile(loss_type="sparse_categorical_crossentropy", metrics=[],
              comp_mode="inference", strategy=strategy)
    step = compiled_decode_step(m)
    assert step.attention_path == "xla"
    split = 0
    for name, ws in m.params.items():
        for w, leaf in ws.items():
            served = step.weights[name][w]
            spec = tuple(leaf.sharding.spec)
            if spec and any(spec):
                split += 1
                axes = next(a for a in spec if a)
                # wq/wk/wv split dim 1 of 3, wo dim 0 of 3, the FFN
                # kernel dim 1 of 2: the same dim of the fused leaf
                want = PartitionSpec(*spec[:2]) if leaf.ndim == 3 else \
                    PartitionSpec(*spec)
                assert served.sharding.is_equivalent_to(
                    jax.sharding.NamedSharding(leaf.sharding.mesh, want),
                    served.ndim), (name, w, served.sharding)
                assert len(served.sharding.device_set) == N_DEV
                shard = served.addressable_shards[0].data
                assert shard.size * 4 == served.size, (name, w, axes)
            else:
                assert served.sharding.is_fully_replicated, (name, w)
    assert split == 6  # wq, wk, wv, wo, ff1's kernel and bias
    ids = np.ones((8, 1), np.int32)
    table = np.arange(32, dtype=np.int32).reshape(8, 4)
    lens = np.zeros((8,), np.int32)
    got = np.asarray(step(ids, table, lens))
    m.state = jax.tree.map(jnp.zeros_like, m.state)
    (want, _), _ = step.frame_fn(m.params, m.state, [ids, table, lens])
    np.testing.assert_array_equal(got, np.asarray(want))
