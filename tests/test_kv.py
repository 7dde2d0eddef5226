"""KV memory as a first-class searched resource (ISSUE 18): radix
prefix sharing (copy-on-write page refcounts + a prefix trie in the
PageAllocator) and the searched KV-cache pool precision lane
(FFConfig.kv_precision, __meta__.kv, SHD168/SHD169, STR213).

Contract highlights:

* sharing is semantically invisible: requests over a shared system
  prompt, batched through a FIXED undersized pool, produce EXACTLY the
  tokens of serving each request alone — while fitting >= 2x the
  concurrent sequences the unshared pool could hold;
* preemption and deadline expiry compose with shared pages: evicting
  one owner only drops refcounts (the sibling's cache survives), and a
  preempted sequence's continued stream is token-identical;
* the fp32 pool IS the pre-PR decode path: no attr, no extra state,
  adoption is a no-op — and the default/train-objective artifacts
  (op signature, ServingSpec signature, cost-cache search keys) stay
  byte-identical with the lane off;
* the int8 pool honors the accuracy contract (bounded drift vs fp32,
  kernel and XLA fallback agreeing), and an illegal __meta__.kv fails
  both the import gate (SHD168/169) and fflint (STR213).
"""

import json
import sys

import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu.core.machine import MachineView

N_DEV = 8


def _trivial_strategy(graph):
    return {
        n.guid: (n.op.fixed_machine_view()
                 or MachineView.trivial(n.op.output_shapes[0].ndim))
        for n in graph.topo_order()
    }


SYS_PROMPT = list(range(10, 26))  # 16 tokens = 4 full pages of 4


def _sharing_model(page_size=4, pages_per_seq=8, batch=4):
    from flexflow_tpu.models import build_gpt_decode

    kw = dict(vocab=128, num_layers=1, hidden=32, num_heads=2,
              ff_dim=32, page_size=page_size,
              pages_per_seq=pages_per_seq)
    cfg = ff.FFConfig(batch_size=batch, num_devices=1,
                      cost_cache_file="")
    m = build_gpt_decode(cfg, **kw)
    m.compile(loss_type="sparse_categorical_crossentropy", metrics=[],
              comp_mode="inference",
              strategy=_trivial_strategy(m.graph))
    return m


def _run(step, reqs, *, sharing, num_pages, max_seqs=4, page_size=4,
         pages_per_seq=8, submit_later=()):
    """Drive the executor to completion, tracking peak concurrency.
    ``submit_later`` entries are (frame, [requests]) injections."""
    from flexflow_tpu.runtime.decode import ContinuousBatchingExecutor

    # the chunked prefill lane is part of the sharing design: a
    # registrar's pages are published at admission (cached = len-1),
    # so siblings admitted in the SAME frame already claim them
    ex = ContinuousBatchingExecutor(
        step, max_seqs=max_seqs, page_size=page_size,
        pages_per_seq=pages_per_seq, num_pages=num_pages,
        prefill_fn=getattr(step, "prefill", None),
        prefill_chunk=page_size,
        prefix_sharing=sharing,
        copy_page_fn=step.copy_page if sharing else None)
    ex.submit(reqs)
    later = sorted(submit_later)
    peak = 0
    while ex.queue or any(s is not None for s in ex.slots) or later:
        assert ex.frame < 500, "kv sharing test run stuck"
        while later and later[0][0] <= ex.frame:
            ex.submit(later.pop(0)[1])
        ex.step()
        peak = max(peak, sum(s is not None for s in ex.slots))
    return ex, dict(ex.finished), peak


# ---------------------------------------------------------------------------
# PageAllocator: refcounts, the trie, reserve-on-divergence
# ---------------------------------------------------------------------------
def test_page_allocator_refcount_cow_trie():
    from flexflow_tpu.runtime.decode import PageAllocator

    pa = PageAllocator(8)
    pages = pa.alloc(3)
    tokens = list(range(100, 110))  # 2.5 pages of 4
    pa.register_prefix(tokens, 4, pages, cached=9)  # 2 full pages
    # full-page + mid-page lookup against a sibling prompt
    got, matched, partial = pa.lookup_prefix(tokens[:8], 4)
    assert got == pages[:2] and matched == 8 and partial is None
    sibling = tokens[:9] + [999, 998]
    got, matched, partial = pa.lookup_prefix(sibling, 4)
    assert got == pages[:2] and matched == 8
    assert partial is None  # page 2 (tokens 8..) was never registered
    pa.register_prefix(tokens + [55, 66], 4, pages, cached=12)
    got, matched, partial = pa.lookup_prefix(sibling, 4)
    assert partial == (pages[2], 1)  # agrees on one token mid-page
    # share raises refcounts; free only releases at zero
    pa.share(pages[:2])
    assert pa.refcount(pages[0]) == 2
    # reserve-on-divergence: a SHARED page (refcount 2) at/after the
    # write point must fail the admission assert
    with pytest.raises(AssertionError):
        pa.assert_divergence_reserved(pages[:2], 0)
    pa.assert_divergence_reserved(pages[:2], 2)
    pa.free(pages)
    assert pa.refcount(pages[0]) == 1 and pa.refcount(pages[2]) == 0
    # the freed page's trie entry is gone (its bytes will be reused)
    assert pa.lookup_prefix(sibling, 4)[2] is None
    # stale-hit guard: share() of a dead page is a loud failure
    pa.free([pages[0], pages[1]])
    with pytest.raises(AssertionError):
        pa.share([pages[0]])


# ---------------------------------------------------------------------------
# measured sharing: concurrency win + token identity (the tentpole)
# ---------------------------------------------------------------------------
def test_prefix_sharing_concurrency_and_token_identity():
    """At a FIXED 21-page pool the unshared executor fits 2 concurrent
    sequences; with radix sharing the same pool holds 4 (>= 2x), the
    mid-page divergent request exercises copy-on-write, and every
    request's tokens are EXACTLY those of serving it alone."""
    from flexflow_tpu.runtime.decode import (
        DecodeRequest,
        compiled_decode_step,
    )

    m = _sharing_model()
    step = compiled_decode_step(m, prefill_chunk=4)

    def reqs():
        return [
            # r0 registers sys + its page-4 chunk [100,101,102,103]
            DecodeRequest(rid="r0", prompt=SYS_PROMPT + [100, 101, 102,
                                                         103, 104, 105],
                          max_new_tokens=8),
            DecodeRequest(rid="r1", prompt=SYS_PROMPT + [30, 31],
                          max_new_tokens=2),
            DecodeRequest(rid="r2", prompt=SYS_PROMPT + [40, 41],
                          max_new_tokens=2),
            DecodeRequest(rid="r3", prompt=SYS_PROMPT + [50, 52],
                          max_new_tokens=2),
            # rc diverges MID-page: agrees with r0's page-4 chunk on 2
            # tokens -> claimed via copy-on-write at admission
            DecodeRequest(rid="rc", prompt=SYS_PROMPT + [100, 101, 110],
                          max_new_tokens=2),
        ]

    pool = 21  # 1 scratch + 2 full 8-page allotments + change
    _, out_off, peak_off = _run(step, reqs(), sharing=False,
                                num_pages=pool)
    ex, out_on, peak_on = _run(step, reqs(), sharing=True,
                               num_pages=pool)
    solo = {}
    for r in reqs():
        _, one, _ = _run(step, [r], sharing=False, num_pages=0)
        solo.update(one)

    assert out_off == solo and out_on == solo  # semantically invisible
    assert peak_off == 2
    assert peak_on >= 2 * peak_off  # the fixed-pool concurrency win
    s = ex.summary()
    assert s["prefix_hits"] >= 4  # r1..r3 + rc (l0 registers, no hit)
    assert s["shared_pages"] >= 12 and s["prefix_tokens"] >= 48
    assert s["cow_copies"] >= 1  # rc's mid-page divergence
    assert s["private_pages"] == (ex.total_admitted * 8
                                  - s["shared_pages"])
    # pool fully drained at the end: every refcount returned to zero
    assert ex.allocator.free_pages == pool - 1  # scratch still held
    # extension-only summary: the roll-up keys never leak when off
    ex_off, _, _ = _run(step, reqs()[:2], sharing=False, num_pages=0)
    assert "prefix_hits" not in ex_off.summary()


def test_preemption_and_expiry_with_shared_pages():
    """Preemption + deadline expiry composed with shared pages: the
    victim's eviction only drops refcounts (the registrar's cache
    survives for the high-priority claimant), the expired request
    frees nothing it never held, and the preempted stream continues
    token-identically after re-admission."""
    from flexflow_tpu.runtime.decode import (
        DecodeRequest,
        compiled_decode_step,
    )

    m = _sharing_model(pages_per_seq=6, batch=2)
    step = compiled_decode_step(m, prefill_chunk=4)
    l0 = DecodeRequest(rid="l0", prompt=SYS_PROMPT + [100, 101, 102,
                                                      103],
                       max_new_tokens=4)
    l1 = DecodeRequest(rid="l1", prompt=SYS_PROMPT + [30, 31],
                       max_new_tokens=4)
    e = DecodeRequest(rid="e", prompt=[1, 2], max_new_tokens=2,
                      deadline_frames=1)
    h = DecodeRequest(rid="h", prompt=SYS_PROMPT + [60, 61],
                      max_new_tokens=2, priority=5)

    pool = 13
    ex, out, _ = _run(step, [l0, l1, e], sharing=True, num_pages=pool,
                      max_seqs=2, pages_per_seq=6,
                      submit_later=[(1, [h])])
    assert ex.total_preempted == 1  # h evicted the shared claimant l1
    assert ex.total_expired == 1 and "e" in ex.expired
    assert set(out) == {"l0", "l1", "h"}
    solo = {}
    for r in (l0, l1, h):
        _, one, _ = _run(step, [DecodeRequest(
            rid=r.rid, prompt=list(r.prompt),
            max_new_tokens=r.max_new_tokens)],
            sharing=False, num_pages=0, max_seqs=2, pages_per_seq=6)
        solo.update(one)
    assert out == solo  # incl. l1's continued stream across preemption
    assert ex.summary()["prefix_hits"] >= 2  # l1 and h both claimed
    # every page returned: refcounts never freed a live sibling's page
    assert ex.allocator.free_pages == pool - 1


# ---------------------------------------------------------------------------
# pool precision: extension-only defaults + the accuracy contract
# ---------------------------------------------------------------------------
def test_fp32_pool_is_the_pre_pr_decode_path():
    """kv_dtype="fp32" adds NO attr, NO extra state and NO signature
    drift, and dtype adoption with fp32 is an exact no-op — the
    default pool is byte-identical to the tree before the lane."""
    from flexflow_tpu.model import _adopt_kv_dtype
    from flexflow_tpu.models import build_gpt_decode
    from flexflow_tpu.ops.decode_attention import DecodeAttentionOp

    cfg = ff.FFConfig(batch_size=4, num_devices=1, cost_cache_file="")
    m = build_gpt_decode(cfg, vocab=64, num_layers=1, hidden=32,
                         num_heads=2, ff_dim=32, page_size=4,
                         pages_per_seq=4)
    ops = [n.op for n in m.graph.topo_order()
           if isinstance(n.op, DecodeAttentionOp)]
    assert ops and all("kv_dtype" not in op.attrs for op in ops)
    assert all(op.kv_dtype == "fp32" for op in ops)
    specs = {op.name: op.state_specs() for op in ops}
    assert all("k_scale" not in json.dumps(str(s))
               for s in specs.values())
    nodes_before = {g: n for g, n in m.graph.nodes.items()}
    _adopt_kv_dtype(m.graph, "fp32")  # no-op by contract
    _adopt_kv_dtype(m.graph, None)
    assert all(m.graph.nodes[g] is n for g, n in nodes_before.items())
    # int8 adoption DOES retype (sanity that the no-op above is real)
    _adopt_kv_dtype(m.graph, "int8")
    ops2 = [n.op for n in m.graph.topo_order()
            if isinstance(n.op, DecodeAttentionOp)]
    assert all(op.attrs.get("kv_dtype") == "int8" for op in ops2)


def test_int8_accuracy_contract_and_kernel_parity():
    """The EQuARX-style contract the searched int8 pool rides on:
    per-token symmetric quantization keeps decode attention within a
    bounded drift of the fp32 pool, and the quant Pallas kernel agrees
    with its XLA fallback to float tolerance."""
    import jax.numpy as jnp

    from flexflow_tpu.kernels.ragged_paged_attention import (
        _xla_ragged_paged_quant,
        ragged_paged_attention,
        ragged_paged_attention_quant,
    )
    from flexflow_tpu.ops.decode_attention import _quantize_kv

    rng = np.random.default_rng(11)
    P, ps, H, D, B, pps = 16, 8, 4, 16, 4, 4
    k = jnp.asarray(rng.normal(size=(P, ps, H * D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(P, ps, H * D)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    table = jnp.asarray(
        rng.permutation(P)[:B * pps].reshape(B, pps), jnp.int32)
    lens = jnp.asarray(rng.integers(ps, ps * pps, size=B), jnp.int32)

    ref = ragged_paged_attention(q, k, v, table, lens)
    kq, ks = _quantize_kv(k)
    vq, vs = _quantize_kv(v)
    assert kq.dtype == jnp.int8 and ks.shape == (P, ps)
    got = ragged_paged_attention_quant(q, kq, vq, ks, vs, table, lens)
    assert float(jnp.max(jnp.abs(got - ref))) < 0.05  # the contract
    xla = _xla_ragged_paged_quant(q, kq, vq, ks, vs, table, lens,
                                  1.0 / np.sqrt(D))
    assert float(jnp.max(jnp.abs(got - xla))) < 1e-5
    # bf16 pool: strictly tighter than int8 on the same pages
    bf = ragged_paged_attention(
        q, k.astype(jnp.bfloat16).astype(jnp.float32),
        v.astype(jnp.bfloat16).astype(jnp.float32), table, lens)
    assert float(jnp.max(jnp.abs(bf - ref))) < 0.05


def test_kv_off_keys_and_signatures_byte_identical():
    """With the lane off, every persisted identity is byte-identical
    to the pre-lane tree: train-objective search keys ignore the kv
    knobs entirely, serve keys only extend when armed, and the
    ServingSpec signature only grows a ("shared", n) element when
    sharing is set."""
    from flexflow_tpu.models import build_gpt_decode
    from flexflow_tpu.search.cost_cache import CostCache
    from flexflow_tpu.search.serving import ServingSpec

    kw = dict(vocab=64, num_layers=1, hidden=32, num_heads=2,
              ff_dim=32, page_size=4, pages_per_seq=4)
    base = dict(batch_size=4, num_devices=N_DEV, cost_cache_file="")
    m = build_gpt_decode(ff.FFConfig(**base), **kw)

    # train objective: the kv knobs are serve-only — keys CANNOT move
    k_train = CostCache.search_key(m.graph, ff.FFConfig(**base))
    k_train_kv = CostCache.search_key(m.graph, ff.FFConfig(
        **base, kv_precision="search", serve_shared_prefix_pages=3))
    assert k_train == k_train_kv

    # serve objective: defaults stay put, arming the lane re-keys
    k_serve = CostCache.search_key(
        m.graph, ff.FFConfig(**base, objective="serve"))
    assert k_serve == CostCache.search_key(m.graph, ff.FFConfig(
        **base, objective="serve", kv_precision="off",
        serve_shared_prefix_pages=0))
    assert k_serve != CostCache.search_key(m.graph, ff.FFConfig(
        **base, objective="serve", kv_precision="search"))
    assert k_serve != CostCache.search_key(m.graph, ff.FFConfig(
        **base, objective="serve", serve_shared_prefix_pages=2))

    spec = ServingSpec(max_seqs=8, page_size=4, pages_per_seq=4)
    shared = ServingSpec(max_seqs=8, page_size=4, pages_per_seq=4,
                         shared_prefix_pages=2)
    assert "shared" not in spec.signature()
    assert shared.signature()[-2:] == ("shared", 2)
    # the residency discount: s of pps pages held once instead of
    # max_seqs times
    assert spec.shared_residency_factor() == 1.0
    assert shared.shared_residency_factor() == (8 * 2 + 2) / (8 * 4)


# ---------------------------------------------------------------------------
# __meta__.kv: digest-gated persistence, import re-lint, STR213
# ---------------------------------------------------------------------------
def test_kv_meta_roundtrip_and_corrupt_import(tmp_path):
    """compile(objective=serve, kv_precision=search) persists
    __meta__.kv behind the digest gate; import re-lints (SHD168/169)
    BEFORE adopting the dtype onto the decode ops, so a corrupted
    artifact fails loudly and a clean one reproduces the searched
    pool."""
    from flexflow_tpu.analysis import AnalysisError
    from flexflow_tpu.models import GPT_DECODE_KW, build_gpt_decode
    from flexflow_tpu.ops.decode_attention import DecodeAttentionOp
    from flexflow_tpu.search.strategy_io import read_meta

    path = str(tmp_path / "kv_strategy.json")
    cfg = ff.FFConfig(batch_size=8, num_devices=N_DEV, search_budget=0,
                      objective="serve", cost_cache_file="",
                      kv_precision="search",
                      serve_shared_prefix_pages=2,
                      export_strategy_file=path)
    m = build_gpt_decode(cfg, **GPT_DECODE_KW)
    m.compile(loss_type="sparse_categorical_crossentropy", metrics=[],
              comp_mode="inference")
    meta = read_meta(path)
    kv = meta.get("kv")
    assert kv and kv["searched"] and kv["shared_prefix_pages"] == 2
    assert kv["dtype"] in ("fp32", "bf16", "int8")
    assert set(kv["predicted_p99_step_ms"]) == {"fp32", "bf16", "int8"}

    # clean import: digest gate passes, the dtype is adopted
    cfg2 = ff.FFConfig(batch_size=8, num_devices=N_DEV,
                       import_strategy_file=path, cost_cache_file="")
    m2 = build_gpt_decode(cfg2, **GPT_DECODE_KW)
    m2.compile(loss_type="sparse_categorical_crossentropy", metrics=[],
               comp_mode="inference")
    ops = [n.op for n in m2.graph.topo_order()
           if isinstance(n.op, DecodeAttentionOp)]
    want = None if kv["dtype"] == "fp32" else kv["dtype"]
    assert all(op.attrs.get("kv_dtype") == want for op in ops)

    # corrupt scale layout -> SHD169 refuses the import
    def corrupt(name, mutate):
        data = json.load(open(path))
        mutate(data["__meta__"]["kv"])
        bad = str(tmp_path / name)
        json.dump(data, open(bad, "w"))
        cfgx = ff.FFConfig(batch_size=8, num_devices=N_DEV,
                           import_strategy_file=bad,
                           cost_cache_file="")
        mx = build_gpt_decode(cfgx, **GPT_DECODE_KW)
        with pytest.raises(AnalysisError):
            mx.compile(loss_type="sparse_categorical_crossentropy",
                       metrics=[], comp_mode="inference")

    corrupt("bad_layout.json",
            lambda kv: kv.update(scale_layout="per_tensor",
                                 dtype="int8"))
    corrupt("bad_shared.json",
            lambda kv: kv.update(shared_prefix_pages=999))
    corrupt("bad_factor.json",
            lambda kv: kv.update(shared_residency_factor=0.1))


def test_lint_kv_shd168_shd169():
    from flexflow_tpu.analysis import lint_kv
    from flexflow_tpu.model import _adopt_kv_dtype
    from flexflow_tpu.models import build_gpt_decode
    from flexflow_tpu.search.serving import ServingSpec

    cfg = ff.FFConfig(batch_size=4, num_devices=1, cost_cache_file="")
    m = build_gpt_decode(cfg, vocab=64, num_layers=1, hidden=32,
                         num_heads=2, ff_dim=32, page_size=4,
                         pages_per_seq=4)
    s = _trivial_strategy(m.graph)
    spec = ServingSpec(max_seqs=4, page_size=4, pages_per_seq=4,
                       shared_prefix_pages=2)
    good = {"dtype": "int8", "searched": True,
            "scale_layout": "page_slot", "shared_prefix_pages": 2,
            "shared_residency_factor": (4 * 2 + 2) / (4 * 4)}
    assert lint_kv(m.graph, s, good, serving=spec) == []
    codes = lambda meta, **kw: {  # noqa: E731
        f.code for f in lint_kv(m.graph, s, meta, **kw)}
    assert "SHD169" in codes({**good, "dtype": "fp4"}, serving=spec)
    assert "SHD169" in codes({**good, "scale_layout": "none"},
                             serving=spec)
    assert "SHD169" in codes({**good, "dtype": "fp32"}, serving=spec)
    assert "SHD168" in codes({**good, "shared_prefix_pages": 4},
                             serving=spec)
    assert "SHD168" in codes(
        {**good, "shared_residency_factor": 0.2}, serving=spec)
    assert "SHD168" in codes({**good, "shared_prefix_pages": 1},
                             serving=spec)  # disagrees with the spec
    assert "SHD169" in codes("not-a-mapping", serving=spec)
    # post-adoption coherence: ops carrying a DIFFERENT dtype than the
    # meta is a lie about the pool
    _adopt_kv_dtype(m.graph, "bf16")
    assert "SHD169" in codes(good, serving=spec)


def test_str213_kv_meta_lint(tmp_path):
    """fflint strategy catches seeded __meta__.kv corruptions
    stdlib-only (the pre-commit gate's view of the artifact)."""
    sys.path.insert(0, "tools")
    try:
        from fflint import lint_strategy_file
    finally:
        sys.path.pop(0)

    good = {
        "graph_digest": "d" * 32,
        "serving": {"objective": "serve", "max_seqs": 8,
                    "page_size": 16, "pages_per_seq": 4,
                    "quantile": 0.99, "p99_budget_ms": 0.0,
                    "predicted_p99_step_ms": 0.05,
                    "kv_bytes_per_device": 2.1e6},
        "kv": {"dtype": "int8", "searched": True,
               "scale_layout": "page_slot", "shared_prefix_pages": 2,
               "shared_residency_factor": (8 * 2 + 2) / (8 * 4),
               "predicted_p99_step_ms": {"fp32": 0.06, "bf16": 0.055,
                                         "int8": 0.05},
               "kv_bytes_per_device": 5.25e5},
    }
    base = {"lm_head": {"dims": [8, 1, 1], "replica": 1, "start": 0}}

    def write(meta):
        p = tmp_path / "strategy.json"
        p.write_text(json.dumps({**base, "__meta__": meta}))
        return str(p)

    assert not [f for f in lint_strategy_file(write(good))
                if f[1] == "STR213"]

    def mut(**kw):
        return {**good, "kv": {**json.loads(json.dumps(good["kv"])),
                               **kw}}

    corruptions = [
        ("not-an-object", {**good, "kv": [1]}),
        ("unknown dtype", mut(dtype="fp4")),
        ("int8 without page_slot scales", mut(scale_layout="none")),
        ("fp32 with scales", mut(dtype="fp32")),
        ("non-bool searched", mut(searched="yes")),
        ("negative shared pages", mut(shared_prefix_pages=-1)),
        ("shared >= pages_per_seq", mut(shared_prefix_pages=4)),
        ("factor vs refcount arithmetic", mut(
            shared_residency_factor=0.9)),
        ("factor != 1 with sharing off", mut(
            shared_prefix_pages=0, shared_residency_factor=0.5)),
        ("nan priced p99", mut(predicted_p99_step_ms={
            "fp32": 0.06, "bf16": 0.055, "int8": float("nan")})),
        ("chosen dtype unpriced", mut(predicted_p99_step_ms={
            "fp32": 0.06})),
        ("negative pool bytes", mut(kv_bytes_per_device=-1.0)),
    ]
    for label, meta in corruptions:
        found = [f for f in lint_strategy_file(write(meta))
                 if f[1] == "STR213" and f[0] == "error"]
        assert found, f"corruption {label!r} not caught by STR213"
