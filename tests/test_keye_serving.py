"""Keye-VL-2.0's language model on the SERVING path (models/keye_vl.py
``build_keye_vl_decode``): grouped decode attention behind a learned
indexer that chooses ``index_topk`` cached positions a query from a
third paged pool, and softmax top-k routing over the held experts —
against the plain float32 reference (benchmarks/reference/keye.py), at
the tiny preset's sizes (page 8 x 16 pages, 4 index heads of 16 choosing
16 positions in key blocks of 16, 2 key/value heads x 2 query heads, 8
of 16 experts held, top 4).  CPU only: values, control flow and counts,
never a time.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import flexflow_tpu as ff
from benchmarks.reference import keye
from flexflow_tpu.core.machine import MachineView
from flexflow_tpu.core.ptensor import DataType, ParallelTensorShape
from flexflow_tpu.kernels import sparse_attention as sa
from flexflow_tpu.models import build_keye_vl_decode
from flexflow_tpu.models.joyai_flash import held_experts_ffn
from flexflow_tpu.obs.metrics import METRICS
from flexflow_tpu.ops.base import LoweringContext, Operator
from flexflow_tpu.ops.decode_attention import (
    GroupedDecodeAttentionOp,
    SparseDecodeAttentionOp,
)
from flexflow_tpu.ops.moe import MoERouterOp
from flexflow_tpu.runtime.decode import (
    ContinuousBatchingExecutor,
    DecodeRequest,
    compiled_decode_step,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmarks", "configs",
                       "tiny-keye-serve.json")) as f:
    KW = json.load(f)["builder_kwargs"]
PAGE, PPS, CHUNK, SLOTS, TOPK = 8, 16, 8, 2, 16
SPEC = keye.Spec(top_k=4, index_topk=TOPK, index_rope_dim=8)
# float32 everywhere: the program and the reference differ by the ORDER
# of float32 sums (online softmax a key block at a time, fused
# projections), and both choose the same positions; observed 3e-7 at
# logits of std 0.2.  bfloat16 weights and products with a choice that
# keeps every position (index_topk 128 = the context): every layer
# rounds its matmul inputs to 8 bits and K, V and the index keys are
# stored in bf16; the harness's bound (serve.PROBE_LOGIT_ATOL 0.03)
# applies, observed 0.0034.  With a choice of 16 of ~50 positions bf16
# scores CHOOSE otherwise near the threshold and one position of 16
# moves a logit by 0.17 at this width — the float32 case holds the
# choice itself.  A wrong page, a stale index key or a position outside
# the choice moves a logit by 0.05 or more.
CASES = {"float32-choose-16": ("float32", TOPK, 1e-4),
         "bfloat16-keep-all": ("bfloat16", PPS * PAGE, 0.03)}


def counter(name):
    return METRICS.snapshot()["counters"].get(name, 0)


def build(dtype="float32", slots=SLOTS, **more):
    ffc = ff.FFConfig(batch_size=slots, seed=11, num_devices=1,
                      cost_cache_file="", compute_dtype=dtype,
                      param_dtype=dtype)
    pool = "fp32" if dtype == "float32" else "bf16"
    model = build_keye_vl_decode(ffc, **{**KW, "kv_dtype": pool, **more})
    model.compile(loss_type="sparse_categorical_crossentropy", metrics=[],
                  comp_mode="inference")
    return model


class Tap:
    """Pulls every frame's logits and files the rows that produce a
    GENERATED token by request."""

    def __init__(self, step):
        self.step, self.ex, self.got = step, None, {}
        self.attention_path = step.attention_path

    def __call__(self, ids, table, lens):
        out = self.step(ids, table, lens)
        logits = np.asarray(out, np.float32)
        for i, live in enumerate(self.ex.slots):
            if live is not None and lens[i] >= len(live.req.prompt) - 1:
                self.got.setdefault(live.req.rid, []).append(logits[i, 0])
        return out


def requests(seed=0):
    """r0: 93 tokens — past the choice (16) many times over, and 92
    prefilled tokens are not a multiple of the chunk (8); r1 stays below
    the choice and leaves early; r2 is admitted into r1's slot, over its
    stale pages."""
    rng = np.random.default_rng(seed)
    return [DecodeRequest(rid=f"r{i}",
                          prompt=rng.integers(1, 128, size=n).tolist(),
                          max_new_tokens=new)
            for i, (n, new) in enumerate(((93, 8), (5, 3), (61, 6)))]


# ---- prefill then decode through the cache vs the reference --------------
@pytest.mark.parametrize("case", sorted(CASES))
def test_served_logits_equal_the_reference(case):
    dtype, topk, tol = CASES[case]
    model = build(dtype, index_topk=topk)
    step = compiled_decode_step(model, prefill_chunk=CHUNK)
    assert step.attention_path == "xla"
    tap = Tap(step)
    ex = ContinuousBatchingExecutor(
        tap, max_seqs=SLOTS, page_size=PAGE, pages_per_seq=PPS,
        prefill_fn=step.prefill, prefill_chunk=CHUNK)
    tap.ex = ex
    reqs = requests()
    out = ex.run(reqs)
    spec = SPEC._replace(index_topk=topk)
    for r in reqs:
        ids = np.asarray([list(r.prompt) + out[r.rid][:-1]], np.int32)
        want = np.asarray(keye.forward(model.params, ids, spec))[
            0, len(r.prompt) - 1:]
        got = np.stack(tap.got[r.rid])
        assert got.shape == want.shape
        assert float(np.abs(got - want).max()) <= tol, r.rid
        assert float(want.std()) > 0.1  # a gap of 0.03 means something


def test_the_counters_count_chosen_and_cached_positions():
    model = build("float32")
    step = compiled_decode_step(model, prefill_chunk=CHUNK)
    before = {c: counter(c) for c in SparseDecodeAttentionOp.COUNTERS}
    prompt = list(range(1, 41))  # 39 prefilled in 5 chunks, then frames
    ex = ContinuousBatchingExecutor(
        step, max_seqs=SLOTS, page_size=PAGE, pages_per_seq=PPS,
        prefill_fn=step.prefill, prefill_chunk=CHUNK)
    ex.run([DecodeRequest(rid="a", prompt=prompt, max_new_tokens=3)])
    step.publish_obs(block=True)
    # by hand: a chunk's queries at 0..38 and its pads at 39 (all write);
    # then 3 frames of 2 rows — the live row at 39, 40, 41, the idle row
    # at 0 — each query reads its own position too
    chunk = np.arange(1, 41)
    frames = np.asarray([40, 41, 42, 1, 1, 1])
    want_cached = int(chunk.sum() + frames.sum())
    want_attended = int(np.minimum(chunk, TOPK).sum()
                        + np.minimum(frames, TOPK).sum())
    layers = KW["num_layers"]
    assert counter("sparse.keys_cached") - before["sparse.keys_cached"] \
        == layers * want_cached
    assert (counter("sparse.keys_attended")
            - before["sparse.keys_attended"]) == layers * want_attended


# ---- the sparse op at contexts within the choice is the dense grouped op --
def op_pair(b=3):
    """A sparse op and a grouped op (no window, no gate) of a frame of
    ``b`` rows (a chunk is handed to ``forward_chunk`` as [b, C, E]), and
    weights both read."""
    e, hq, hkv, d = 32, 4, 2, 16
    shape = [ParallelTensorShape.make((b, 1, e), DataType.FLOAT32),
             ParallelTensorShape.make((b, PPS), DataType.INT32),
             ParallelTensorShape.make((b,), DataType.INT32)]
    common = dict(num_heads=hq, num_kv_heads=hkv, head_dim=d, page_size=PAGE,
                  pages_per_seq=PPS, rope_theta=1e4, qk_norm_eps=1e-6,
                  kv_dtype="fp32")
    sparse = SparseDecodeAttentionOp(
        "s", shape, index_heads=4, index_head_dim=16, index_topk=TOPK,
        index_block=16, **common)
    dense = GroupedDecodeAttentionOp("g", shape, **common)
    rng = np.random.default_rng(4)
    weights = {w.name: jnp.asarray(
        rng.normal(0, 0.3, w.shape) if len(w.shape) > 1
        else 1.0 + rng.normal(0, 0.1, w.shape), jnp.float32)
        for w in sparse._weight_specs}
    return sparse, dense, weights


def states(op, rng, pages):
    out = {}
    for name, shape, dtype, _ in op.state_specs():
        fill = (rng.normal(size=shape) if len(shape) == 3
                else np.zeros(shape))
        out[f"{op.name}/{name}"] = jnp.asarray(fill, dtype)
    return out


@pytest.mark.parametrize("path", ["frame", "chunk"])
def test_within_the_choice_the_sparse_op_is_the_dense_grouped_op(path):
    rng = np.random.default_rng(7)
    if path == "frame":
        sparse, dense, w = op_pair(b=4)
        x = jnp.asarray(rng.normal(size=(4, 1, 32)), jnp.float32)
        where = jnp.asarray([0, 5, 8, TOPK - 1], jnp.int32)  # lens <= TOPK
    else:
        sparse, dense, w = op_pair(b=1)
        x = jnp.asarray(rng.normal(size=(1, 8, 32)), jnp.float32)
        where = jnp.asarray([[4 + i for i in range(8)]], jnp.int32)  # <= 12
    b = x.shape[0]
    table = jnp.asarray(np.arange(b * PPS).reshape(b, PPS)[:, ::-1].copy(),
                        jnp.int32)  # not slot-aligned: any table serves
    st = states(sparse, rng, b * PPS)
    gst = {k.replace("s/", "g/"): v for k, v in st.items()
           if k.endswith(("k_cache", "v_cache"))}
    outs, pools = [], []
    for op, state in ((sparse, st), (dense, gst)):
        ctx = LoweringContext(compute_dtype=jnp.float32, train=False,
                              state_in=state)
        fn = op.forward if path == "frame" else op.forward_chunk
        mine = {k: v for k, v in w.items()
                if k in {s.name for s in op._weight_specs}}
        with jax.default_matmul_precision("highest"):
            outs.append(np.asarray(fn(ctx, [x, table, where], mine)[0]))
        pools.append([np.asarray(ctx.state_out[f"{op.name}/{leaf}"])
                      for leaf in ("k_cache", "v_cache")])
    np.testing.assert_allclose(outs[0], outs[1], atol=2e-5, rtol=0)
    for mine, theirs in zip(*pools):  # the same K/V rows written
        np.testing.assert_array_equal(mine, theirs)


# ---- the choice is exactly top-k -------------------------------------------
# (queries, positions, the first query's position): a chunk behind a short
# context, one far behind, and one whose queries see fewer than TOPK
LAYOUTS = {"q24-s80": (24, 80, 40), "q8-s300": (8, 300, 290),
           "q16-s24": (16, 24, 4)}


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("ties", [False, True], ids=["tie-free", "ties"])
def test_the_chosen_set_is_the_references_top_k(ties, layout):
    """The chunk's mask and the frame's positions are top_k's set."""
    rng = np.random.default_rng(9)
    q, s, start = LAYOUTS[layout]
    scores = rng.normal(size=(2, q, s)).astype(np.float32)
    if ties:  # a few values repeated across the threshold's neighbourhood
        scores = np.round(scores * 2) / 2
    want = np.asarray(keye.chosen_positions(jnp.asarray(scores), start, TOPK))
    rows = start + np.arange(q)[:, None]
    valid = jnp.asarray(np.arange(s)[None, :] <= rows)[None]
    got = np.asarray(sa.topk_mask(jnp.asarray(scores), valid, TOPK))
    np.testing.assert_array_equal(got, want)
    assert (got.sum(-1) == np.minimum(rows[:, 0] + 1, TOPK)).all()
    # one query a row: the positions top_k hands the frame, the same set
    idx, chosen = sa.topk_indices(jnp.asarray(scores[:, -1]),
                                  valid[:, -1], TOPK)
    frame = np.zeros((2, s), bool)
    for r in range(2):
        frame[r, np.asarray(idx[r])[np.asarray(chosen[r])]] = True
    np.testing.assert_array_equal(frame, want[:, -1])


def test_fewer_valid_positions_than_the_choice_keeps_them_all():
    scores = jnp.asarray(np.random.default_rng(1).normal(size=(3, 40)),
                         jnp.float32)
    valid = jnp.arange(40)[None, :] < jnp.asarray([[0], [7], [40]])
    got = np.asarray(sa.topk_mask(scores, valid, TOPK))
    assert got.sum(-1).tolist() == [0, 7, TOPK]
    assert not got[1, 7:].any()


def scatter_mask(scores, valid, k):
    """top_k's positions scattered into a mask: the direct form of the
    chunk's choice, the oracle of the compare-only one."""
    idx, chosen = sa.topk_indices(scores, valid, k)
    return jnp.put_along_axis(jnp.zeros(scores.shape, bool), idx, chosen,
                              axis=-1, inplace=False)


def straddled(rng, shape):
    """A few 3s above many 2s: each row's k-th value is a 2 with 2s on
    both sides of the cut."""
    return rng.choice([3.0, 2.0, 1.0], size=shape, p=[0.1, 0.4, 0.5])


def signed_zeros(rng, shape):
    """A few positive scores, then +0.0 and -0.0 at random (a position
    whose ReLU terms are all 0 under negative head weights scores -0.0),
    then negatives: the cut falls among the zeros."""
    u = rng.random(shape)
    zero = np.where(rng.random(shape) < 0.5, 0.0, -0.0)
    return np.where(u < 0.1, 1.0 + u, np.where(u < 0.7, zero, -u))


# (scores of shape [B, Q, S], the first query's position, k) — each query
# sees the positions up to its own, as in a chunk
SETS = {
    "ties-straddle": lambda r: (straddled(r, (2, 8, 96)), 80, TOPK),
    "all-zero": lambda r: (np.zeros((2, 8, 64)), 40, TOPK),
    "signed-zeros": lambda r: (signed_zeros(r, (2, 8, 64)), 40, TOPK),
    # rows that see 0, 1, .., TOPK + 1 positions: 0, k-1, k and k+1 among them
    "valid-0-to-k+1": lambda r: (r.normal(size=(2, TOPK + 2, 40)), -1, TOPK),
    "k-is-width": lambda r: (np.round(r.normal(size=(2, 4, TOPK))), 12, TOPK),
    # forward_chunk's block: [1, C, width], -inf past the walked key blocks
    "chunk-layout": lambda r: (np.where(np.arange(PPS * PAGE) < 48,
                                        r.normal(size=(1, CHUNK, PPS * PAGE)),
                                        -np.inf), 40, TOPK),
}


@pytest.mark.parametrize("case", sorted(SETS))
def test_the_chunks_mask_is_top_ks_set_to_the_position(case):
    """Read off the k-th value and its position, the chunk's mask is
    top_k's set exactly: ties cut where top_k cuts them (lower position
    first) and -0.0 ranked below +0.0, as top_k ranks it."""
    scores, start, k = SETS[case](np.random.default_rng(43))
    scores = jnp.asarray(scores, jnp.float32)
    q, s = scores.shape[1:]
    rows = start + np.arange(q)[:, None]
    valid = jnp.asarray(np.arange(s)[None, :] <= rows)[None]
    got = np.asarray(sa.topk_mask(scores, valid, k))
    np.testing.assert_array_equal(
        got, np.asarray(keye.chosen_positions(scores, start, k)))
    np.testing.assert_array_equal(got, np.asarray(scatter_mask(scores,
                                                               valid, k)))
    assert (got.sum(-1) == np.clip(rows[:, 0] + 1, 0, k)).all()


def test_the_chunks_choice_lowers_to_no_gather_and_no_scatter():
    """The chunk's choice is top_k and one compare over the block: the
    k positions are never gathered from ``valid`` nor scattered into a
    mask."""
    block = jax.ShapeDtypeStruct((1, 64, 4096), jnp.float32)
    text = jax.jit(sa.topk_mask, static_argnums=2).lower(
        block, jax.ShapeDtypeStruct(block.shape, bool), 2048).as_text()
    assert "top_k" in text
    assert "scatter" not in text and "gather" not in text


# ---- a key block no query of the chunk chose is skipped ---------------------
def test_a_key_block_no_query_chose_is_skipped():
    """The indexer's head weights at 0 score every position 0: each
    query keeps the LOWEST 16 (top_k's order), positions 0..15 — so the
    chunk at 40..47 chooses nothing of key blocks 1 and 2 (16..47).  NaN
    V rows in block 1 would reach the output through any product over
    the block, masked or not: the output is finite only if the block is
    skipped, and it equals attention over positions 0..15."""
    rng = np.random.default_rng(3)
    sparse, _, w = op_pair(b=1)
    w = {**w, "w_index": jnp.zeros_like(w["w_index"])}
    st = states(sparse, rng, PPS)
    v = np.asarray(st["s/v_cache"]).copy()
    v[2:4] = np.nan  # pages 2, 3: positions 16..31, key block 1
    st["s/v_cache"] = jnp.asarray(v)
    x = jnp.asarray(rng.normal(size=(1, 8, 32)), jnp.float32)
    where = jnp.arange(40, 48, dtype=jnp.int32)[None, :]
    table = jnp.arange(PPS, dtype=jnp.int32)[None, :]
    ctx = LoweringContext(compute_dtype=jnp.float32, train=False,
                          state_in=st)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(sparse.forward_chunk(ctx, [x, table, where], w)[0])
        assert np.isfinite(got).all()
        # the reference's attention over the 16 lowest positions
        q, _, _, _ = sparse._project(x, where, w, jnp.float32)
        k = np.asarray(ctx.state_out["s/k_cache"]).reshape(-1, 2, 16)[:16]
        vv = np.asarray(ctx.state_out["s/v_cache"]).reshape(-1, 2, 16)[:16]
        qg = np.asarray(q).reshape(8, 2, 2, 16)
        s = np.einsum("chgd,khd->chgk", qg, k) / 4.0
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        o = np.einsum("chgk,khd->chgd", p, vv).reshape(1, 8, -1)
        want = o @ np.asarray(w["wo"])
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)


# ---- the softmax router -----------------------------------------------------
def router(score="sigmoid", scale=2.5):
    return MoERouterOp(
        "r", [ParallelTensorShape.make((3, 5, 64), DataType.FLOAT32)],
        n_experts=16, k=4, scale=scale, experts_held=8, score=score)


def router_inputs():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(3, 5, 64)), jnp.float32)
    w = jnp.asarray(rng.normal(0, 0.3, (64, 16)), jnp.float32)
    bias = jnp.asarray(rng.normal(0, 0.5, (16,)), jnp.float32)
    return x, w, bias


def test_the_softmax_router_renormalises_the_chosen_and_equals_the_reference():
    op = router("softmax", scale=1.0)
    assert op.state_specs() == () and op.attrs["score"] == "softmax"
    x, w, _ = router_inputs()
    ctx = LoweringContext(compute_dtype=jnp.float32, train=False,
                          state_in={})
    weights, chosen = op.forward(ctx, [x], {"kernel": w})
    with jax.default_matmul_precision("highest"):
        want = np.asarray(keye.routing_weights(x, {"kernel": w}, 4))
    got = np.zeros_like(want)
    np.put_along_axis(got, np.asarray(chosen), np.asarray(weights), axis=-1)
    np.testing.assert_allclose(got, want, atol=1e-6, rtol=0)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, atol=1e-6)
    with pytest.raises(AssertionError):
        router("softmax", scale=2.5)


def test_the_sigmoid_router_is_what_it_was():
    """The signature the cost cache keys rows by, the state and the
    output of a sigmoid router, as the tree before the softmax form read
    them."""
    op = router()
    assert op.signature() == (
        "moe_router", ((3, 5, 64),), ("float32",),
        (("experts_held", 8), ("k", 4), ("n_experts", 16), ("scale", 2.5)))
    assert [s[0] for s in op.state_specs()] == ["bias"]
    x, w, bias = router_inputs()
    ctx = LoweringContext(compute_dtype=jnp.float32, train=False,
                          state_in={"r/bias": bias})
    weights, chosen = op.forward(ctx, [x], {"kernel": w})
    assert np.asarray(chosen)[0, 0].tolist() == [11, 6, 8, 9]
    np.testing.assert_array_equal(
        np.asarray(weights)[0, 0],
        np.asarray([0.6772967576980591, 0.4955938458442688,
                    0.6613876819610596, 0.6657217741012573], np.float32))
    assert int(np.asarray(chosen).sum()) == 356


# ---- the experts' shares sum to the uncut layer ----------------------------
def routed_part(x, full, offset, held):
    """What ``held`` experts from ``offset`` on add for x [B, 1, E], by
    the SYSTEM's ops (softmax router, dispatch, grouped products,
    combine) in a graph of their own."""
    b, _, e = x.shape
    ffc = ff.FFConfig(batch_size=b, seed=0, num_devices=1,
                      cost_cache_file="", compute_dtype="float32")
    model = ff.FFModel(ffc)
    t = model.create_tensor([b, 1, e], dtype="float32", name="x")
    held_experts_ffn(
        model, t, "l_moe", hidden=e, expert_ff_dim=32, n_routed_experts=16,
        experts_held=held, expert_offset=offset, experts_per_token=4,
        expert_rows=b * 4, routed_scaling_factor=1.0, score="softmax")
    model.compile(loss_type="mean_squared_error", metrics=[],
                  comp_mode="inference")
    params = {"l_moe_router": {"kernel": full["l_moe_router"]["kernel"]}}
    for part in ("gate", "up", "down"):
        name = f"l_moe_experts_{part}"
        params[name] = {"kernel": full[name]["kernel"][offset:offset + held]}
    y, _ = model.compiled.apply(params, model.state, [x], None, False)
    return np.asarray(y)


def test_eight_shares_of_the_experts_sum_to_the_uncut_layer():
    rng = np.random.default_rng(2)
    e, f, n = 64, 32, 16
    full = {"l_moe_router": {"kernel": jnp.asarray(
        rng.normal(0, 0.3, (e, n)), jnp.float32)}}
    for part, shape in (("gate", (n, e, f)), ("up", (n, e, f)),
                        ("down", (n, f, e))):
        full[f"l_moe_experts_{part}"] = {"kernel": jnp.asarray(
            rng.normal(0, 0.1, shape), jnp.float32)}
    x = jnp.asarray(rng.normal(size=(12, 1, e)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        uncut = np.asarray(keye.routed_experts(
            x, full, "l", keye.Spec(top_k=4)))
    shares = [routed_part(x, full, offset, 2) for offset in range(0, 16, 2)]
    # norm_topk_prob divides by the sum over ALL chosen experts, held or
    # not: only then do the shares add up (float32 rounding)
    np.testing.assert_allclose(sum(shares), uncut, atol=2e-5, rtol=0)
    assert sum(np.abs(s).max() > 1e-3 for s in shares) >= 6


# ---- the cost hooks price the indexer and the chosen rows ------------------
def test_the_cost_hooks_price_the_indexer_and_the_chosen_rows():
    sparse, dense, _ = op_pair(b=4)
    cap = PPS * PAGE
    # flops: the projections (attention and indexer), 4 heads x 16 of the
    # indexer against every cached position, attention over 16 of them
    proj = 2 * 4 * 32 * (2 * 64 + 2 * 32 + 4 * 16 + 16 + 4)
    assert sparse.flops() == proj + 2 * 4 * 4 * 16 * cap + 4 * 4 * 64 * TOPK
    assert sparse.flops() < dense.flops() + 2 * 4 * 4 * 16 * cap
    # bytes: the index keys of every cached position, K/V of the chosen
    index = 16 * 4
    kv = 2 * 2 * 16 * 4
    assert sparse.bytes_accessed() - Operator.bytes_accessed(sparse) \
        == 4 * (cap * index + TOPK * kv)
    mv = MachineView.trivial(3)
    assert sparse.kv_cache_bytes(mv) == 4 * PPS * PAGE * (kv + index)
