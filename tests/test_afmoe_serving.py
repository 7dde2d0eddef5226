"""A mixture-of-experts model with sliding-window and global attention
layers on the SERVING path (models/afmoe.py ``build_afmoe_decode``):
grouped-query decode attention over two kinds of KV page, the expert ops
on the decode path, weights held once — against the plain float32
reference (benchmarks/reference/trinity.py), at the tiny preset's sizes
(window 32, page 8, a ring of 6 pages that wraps, 2 key/value heads x 3
query heads, 8 of 16 experts held, top 2).  CPU only: values, control
flow and counts, never a time.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import flexflow_tpu as ff
from benchmarks.reference import trinity
from flexflow_tpu.kernels import ragged_paged_attention as rpa
from flexflow_tpu.models import build_afmoe_decode
from flexflow_tpu.models.joyai_flash import held_experts_ffn
from flexflow_tpu.obs import device_counters
from flexflow_tpu.obs.metrics import METRICS
from flexflow_tpu.runtime.decode import (
    ContinuousBatchingExecutor,
    DecodeRequest,
    compiled_decode_step,
)

WINDOW, PAGE, PPS, CHUNK, SLOTS = 32, 8, 16, 8, 2
KW = dict(
    vocab=128,
    layer_types=["sliding_attention", "sliding_attention", "full_attention",
                 "sliding_attention"],
    first_dense_layers=1, hidden=64, num_heads=6, num_kv_heads=2, head_dim=16,
    window=WINDOW, dense_ff_dim=128, expert_ff_dim=32, n_routed_experts=16,
    experts_held=8, expert_offset=0, experts_per_token=2, route_scale=2.448,
    page_size=PAGE, pages_per_seq=PPS, prefill_chunk=CHUNK,
    head_init_std=0.025)
SPEC = trinity.Spec(window=WINDOW, top_k=2)
MOE_LAYERS = 3
# float32 everywhere: the program and the reference differ by the ORDER
# of float32 sums (online softmax a page at a time, fused projections);
# observed 3e-6 at logits of std 0.2.  bfloat16 weights and products
# against the float32 reference over the SAME (bf16-valued) weights: every
# layer rounds its matmul inputs to 8 bits and K/V are stored in bf16; the
# harness's own bound (serve.PROBE_LOGIT_ATOL 0.03, fitted at logits of std
# 0.2 — these are seeded to that spread) applies, observed 0.005-0.013 at
# this narrow width.  A wrong
# page, a pad row in a live page, a missing window mask or a flipped
# expert weight moves a logit by 0.1 or more.
TOLERANCE = {"float32": 1e-4, "bfloat16": 0.03}


def counter(name):
    return METRICS.snapshot()["counters"].get(name, 0)


def build(dtype="float32", use_kernel=True, slots=SLOTS, **more):
    ffc = ff.FFConfig(batch_size=slots, seed=11, num_devices=1,
                      cost_cache_file="", compute_dtype=dtype,
                      param_dtype=dtype)
    pool = "fp32" if dtype == "float32" else "bf16"
    model = build_afmoe_decode(
        ffc, use_kernel=use_kernel, **{**KW, "kv_dtype": pool, **more})
    model.compile(loss_type="sparse_categorical_crossentropy", metrics=[],
                  comp_mode="inference")
    return model


def set_router_bias(model, seed=5):
    """A non-zero correction bias in the STATE (choice by s + b, weight by
    s); returns the reference's view of the parameters with it."""
    rng = np.random.default_rng(seed)
    params = {k: dict(v) for k, v in model.params.items()}
    for key in list(model.state):
        if key.endswith("_router/bias"):
            bias = rng.normal(0.0, 0.5, model.state[key].shape).astype(
                np.float32)
            model.state[key] = jnp.asarray(bias)
            params[key.split("/")[0]]["bias"] = bias
    return params


class Tap:
    """Pulls every frame's logits (so the executor harvests each frame at
    once) and files the rows that produce a GENERATED token by request."""

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


def serve_and_compare(model, step, params, requests, **executor):
    tap = Tap(step)
    ex = ContinuousBatchingExecutor(
        tap, max_seqs=SLOTS, page_size=PAGE, pages_per_seq=PPS,
        prefill_fn=step.prefill, prefill_chunk=CHUNK, **executor)
    tap.ex = ex
    out = ex.run(requests)
    gaps = {}
    for r in requests:
        ids = np.asarray([list(r.prompt) + out[r.rid][:-1]], np.int32)
        want = np.asarray(trinity.forward(params, ids, SPEC))[
            0, len(r.prompt) - 1:]
        got = np.stack(tap.got[r.rid])
        assert got.shape == want.shape, (r.rid, got.shape, want.shape)
        gaps[r.rid] = float(np.abs(got - want).max())
        # each served token is the reference's best or within rounding of it
        best = want.max(axis=-1) - want[np.arange(len(want)), out[r.rid]]
        assert best.max() <= 2 * max(gaps[r.rid], 1e-6), (r.rid, best)
    return ex, gaps


def requests_past_window_and_wrap(seed=0):
    """r0: 93 tokens — past the window (32) and the ring's first wrap (6
    pages x 8 = 48), and 92 prefilled tokens are NOT a multiple of the
    chunk (8): the last chunk has 4 pad rows.  r1 leaves early; r2 is
    admitted into the slot r1 just left, over r1's stale ring pages."""
    rng = np.random.default_rng(seed)
    return [DecodeRequest(rid=f"r{i}",
                          prompt=rng.integers(1, 128, size=n).tolist(),
                          max_new_tokens=new)
            for i, (n, new) in enumerate(((93, 8), (5, 3), (61, 6)))]


# ---- (a) chunked prefill + decode through the executor vs the reference ----
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("use_kernel", [True, False],
                         ids=["kernel", "xla"])
def test_served_logits_equal_the_reference_past_window_and_wrap(
        dtype, use_kernel):
    model = build(dtype, use_kernel)
    params = set_router_bias(model)
    step = compiled_decode_step(model, prefill_chunk=CHUNK)
    assert step.attention_path == ("pallas" if use_kernel else "xla")
    ex, gaps = serve_and_compare(model, step, params,
                                 requests_past_window_and_wrap())
    assert ex.slot_aligned and ex.prefill_chunks > 12
    assert max(gaps.values()) <= TOLERANCE[dtype], gaps
    # window layers hold a ring of ceil((32 + 8) / 8) + 1 = 6 pages a slot
    shapes = {k: v.shape[0] for k, v in model.state.items()
              if k.endswith("k_cache")}
    assert shapes == {"layer0_attn_window/k_cache": SLOTS * 6,
                      "layer1_attn_window/k_cache": SLOTS * 6,
                      "layer2_attn_global/k_cache": SLOTS * PPS,
                      "layer3_attn_window/k_cache": SLOTS * 6}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_one_call_over_a_prompts_chunks_equals_a_call_a_chunk(dtype):
    """The prefill program's loop over a prompt's chunks, over ring
    pages: a 93-token prompt (12 chunks of 8, the last with 4 pad rows)
    crosses the window (32) and wraps the window layers' ring of 6
    pages; sent as ONE run it leaves every state leaf — the global
    pool, the rings, the experts' device counters — bit for bit as the
    same chunks sent one call each, from a state seeded everywhere."""
    from flexflow_tpu.runtime.prefill import run_chunked_prefill

    model = build(dtype)
    step = compiled_decode_step(model, prefill_chunk=CHUNK)
    rng = np.random.default_rng(9)
    seeded = {}
    for key, val in sorted(model.state.items()):
        draw = (rng.integers(0, 1000, val.shape)
                if jnp.issubdtype(val.dtype, jnp.integer)
                else rng.normal(0, 1, val.shape))
        seeded[key] = np.asarray(draw).astype(val.dtype)
    tokens = rng.integers(1, 128, size=93).tolist()
    slot = 1  # the ring is the slot's: page_table[0] // PPS
    pages = list(range(slot * PPS, (slot + 1) * PPS))

    def a_call_a_chunk(ids, positions, page_table):
        for c0 in range(0, ids.shape[1], CHUNK):
            step.prefill(ids[:, c0:c0 + CHUNK], positions[:, c0:c0 + CHUNK],
                         page_table)

    left = []
    for prefill in (step.prefill, a_call_a_chunk):
        model.state = {k: jnp.asarray(v) for k, v in seeded.items()}
        assert run_chunked_prefill(prefill, tokens, pages, chunk=CHUNK,
                                   cap=PAGE * PPS) == 12
        left.append({k: np.asarray(v) for k, v in model.state.items()})
    assert left[0].keys() == left[1].keys() == seeded.keys()
    for key, want in left[1].items():
        np.testing.assert_array_equal(left[0][key], want, err_msg=key)
    # the run wrote the rings and the global pool, and counted its chunks
    moved = {k for k in seeded if np.any(left[1][k] != seeded[k])}
    assert {"layer0_attn_window/k_cache", "layer2_attn_global/v_cache",
            "layer3_attn_window/v_cache"} <= moved
    assert any("/obs/" in k for k in moved)


def test_a_zero_bias_reference_differs_so_the_bias_is_in_the_choice():
    """The control of (a)'s non-zero bias: the reference WITHOUT it does
    not match, so the comparison sees choice-by-(s + b)."""
    model = build()
    set_router_bias(model)
    step = compiled_decode_step(model, prefill_chunk=CHUNK)
    with pytest.raises(AssertionError):
        _, gaps = serve_and_compare(model, step, model.params,
                                    requests_past_window_and_wrap())
        assert max(gaps.values()) <= TOLERANCE["float32"]


def test_a_full_sequence_and_clamped_pad_rows_stay_out_of_the_ring():
    """A prompt that fills the context to its last position: the final
    chunk's pad rows are clamped to ``cap - 1``, which in a ring aliases a
    live page of the window; they must not be written."""
    model = build()
    step = compiled_decode_step(model, prefill_chunk=CHUNK)
    rng = np.random.default_rng(3)
    cap = PAGE * PPS
    reqs = [DecodeRequest(rid="full", prompt=rng.integers(
        1, 128, size=cap - 4).tolist(), max_new_tokens=4)]
    _, gaps = serve_and_compare(model, step, model.params, reqs)
    assert gaps["full"] <= TOLERANCE["float32"], gaps


# ---- (b) the kernel alone ---------------------------------------------------
@pytest.mark.parametrize("window,ring", [(0, 0), (WINDOW, 0), (WINDOW, 6)],
                         ids=["global", "window-table", "window-ring"])
def test_grouped_kernel_equals_the_dense_reference(window, ring):
    rng = np.random.default_rng(1)
    hkv, g, d, b = 2, 3, 16, 9
    # below / at / above the window, on page edges, one token (an idle
    # row attends its own fresh token only)
    lens = np.asarray([1, 7, 8, 9, 31, 32, 33, 64, 97], np.int32)
    k_seq = rng.normal(size=(b, PPS * PAGE, hkv * d)).astype(np.float32)
    v_seq = rng.normal(size=(b, PPS * PAGE, hkv * d)).astype(np.float32)
    q = jnp.asarray(rng.normal(size=(b, hkv * g, d)), jnp.float32)
    pages = ring or PPS
    k_pool = np.zeros((b * pages, PAGE, hkv * d), np.float32)
    v_pool = np.zeros_like(k_pool)
    for s in range(b):  # write positions in order: a ring keeps the last
        for pos in range(int(lens[s])):
            page = s * pages + (pos // PAGE) % pages
            k_pool[page, pos % PAGE] = k_seq[s, pos]
            v_pool[page, pos % PAGE] = v_seq[s, pos]
    starts = np.maximum(lens - window, 0) // PAGE if window else 0 * lens
    n_walk = min(PPS, -(-window // PAGE) + 1) if window else PPS
    logical = starts[:, None] + np.arange(n_walk)[None, :]
    walk = (np.arange(b)[:, None] * pages
            + (logical % pages if ring else np.minimum(logical, PPS - 1)))
    args = (q, jnp.asarray(k_pool), jnp.asarray(v_pool),
            jnp.asarray(walk, jnp.int32), jnp.asarray(lens),
            jnp.asarray(starts, jnp.int32), window)
    want = rpa.dense_decode_reference(
        q, jnp.asarray(k_seq).reshape(b, -1, hkv, d),
        jnp.asarray(v_seq).reshape(b, -1, hkv, d), jnp.asarray(lens),
        window=window)
    for use_kernel in (True, False):
        got = rpa.grouped_paged_attention(*args, use_kernel=use_kernel)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-6, rtol=0)
    # a row with nothing cached walks no page and reads zeros, not NaN
    empty = rpa.grouped_paged_attention(
        q, args[1], args[2], args[3], 0 * args[4], args[5], window)
    assert np.all(np.asarray(empty) == 0.0)


# ---- (c) the share test, and (f) the experts' counters ---------------------
def routed_part(x, full, offset, held):
    """What ``held`` experts from ``offset`` on add for x [B, 1, E], by
    the SYSTEM's ops (router, dispatch, grouped products, combine) in a
    graph of their own; returns (y, the graph's state after the call)."""
    b, _, e = x.shape
    ffc = ff.FFConfig(batch_size=b, seed=0, num_devices=1,
                      cost_cache_file="", compute_dtype="float32")
    model = ff.FFModel(ffc)
    t = model.create_tensor([b, 1, e], dtype="float32", name="x")
    held_experts_ffn(
        model, t, "l_moe", hidden=e, expert_ff_dim=32, n_routed_experts=16,
        experts_held=held, expert_offset=offset, experts_per_token=2,
        expert_rows=b * 2, routed_scaling_factor=2.448)
    model.compile(loss_type="mean_squared_error", metrics=[],
                  comp_mode="inference")
    params = {"l_moe_router": {"kernel": full["l_moe_router"]["kernel"]}}
    for part in ("gate", "up", "down"):
        name = f"l_moe_experts_{part}"
        params[name] = {"kernel": full[name]["kernel"][offset:offset + held]}
    y, state = model.compiled.apply(params, model.state, [x], None, False)
    return np.asarray(y), state


def test_the_two_halves_and_the_shared_expert_once_equal_the_uncut_layer():
    rng = np.random.default_rng(2)
    e, f, n = 64, 32, 16
    full = {"l_moe_router": {"kernel": jnp.asarray(
        rng.normal(0, 0.3, (e, n)), jnp.float32)}}
    for part, shape in (("gate", (n, e, f)), ("up", (n, e, f)),
                        ("down", (n, f, e))):
        full[f"l_moe_experts_{part}"] = {"kernel": jnp.asarray(
            rng.normal(0, 0.1, shape), jnp.float32)}
        full[f"l_shared_{part}"] = {"kernel": jnp.asarray(
            rng.normal(0, 0.1, shape[1:]), jnp.float32)}
    x = jnp.asarray(rng.normal(size=(12, 1, e)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        uncut = np.asarray(trinity.ffn(x, full, "l", SPEC))
        shared = np.asarray(trinity.shared_expert(x, full, "l"))
        weights = np.asarray(trinity.routing_weights(
            x, full["l_moe_router"], SPEC))
    halves, touched = [], []
    for offset in (0, 8):
        y, state = routed_part(x, full, offset, 8)
        halves.append(y)
        touched.append(int(state["l_moe_dispatch/obs/moe.experts_touched"]))
        assert int(state["l_moe_dispatch/obs/moe.experts_held"]) == 8
        assert int(state["l_moe_dispatch/obs/moe.assignments_dropped"]) == 0
        # (f) by hand: held experts some token chose
        chosen = (weights[:, 0, offset:offset + 8] > 0).any(axis=0)
        assert touched[-1] == int(chosen.sum()) > 0
    # route_norm divides by the sum over ALL chosen experts, held or not:
    # only then do the halves add up (float32 rounding)
    np.testing.assert_allclose(halves[0] + halves[1] + shared, uncut,
                               atol=2e-5, rtol=0)
    assert np.abs(halves[0]).max() > 1e-3 and np.abs(halves[1]).max() > 1e-3
    assert sum(touched) <= 16


# ---- (d) weights held once --------------------------------------------------
def test_bf16_weights_are_held_once():
    model = build("bfloat16")
    before = counter("decode.weight_prepares")
    step = compiled_decode_step(model, prefill_chunk=CHUNK)
    assert counter("decode.weight_prepares") == before + 1
    leaves = 0
    for name, ws in model.params.items():
        for w, leaf in ws.items():
            assert step.weights[name][w] is leaf, (name, w)
            leaves += 1
    assert leaves > 40
    gauges = METRICS.snapshot()["gauges"]
    assert gauges["decode.weight_bytes"] == gauges[
        "decode.weight_bytes_master"] > 0
    matmul = [leaf for ws in model.params.values() for w, leaf in ws.items()
              if leaf.ndim >= 2 and "router" not in w]
    kinds = {str(leaf.dtype) for leaf in matmul}
    routers = {str(ws["kernel"].dtype) for n, ws in model.params.items()
               if n.endswith("_router")}
    assert kinds == {"bfloat16", "float32"} and routers == {"float32"}
    assert all(str(ws["kernel"].dtype) == "bfloat16"
               for n, ws in model.params.items() if "_experts_" in n)
    # the two kinds of page, as the gauges count them
    ring = 3 * 2 * SLOTS * 6 * PAGE * 2 * 16 * 2
    assert gauges["decode.kv_bytes_window"] == ring
    assert gauges["decode.kv_bytes_global"] == 2 * SLOTS * PPS * PAGE * 32 * 2


# ---- (e) pages from the free list ------------------------------------------
@pytest.mark.parametrize("case", ["oversubscribed_pool", "prefix_sharing",
                                  "step_behind_a_wrapper", "larger_chunk"])
def test_ring_pools_refuse_free_list_pages_at_build_time(case):
    """A window layer's ring is named by the SLOT of a table row: an
    executor that would hand out free-list pages is refused when it is
    built, with the reason — never served from another slot's ring."""
    model = build()
    step = compiled_decode_step(model, prefill_chunk=CHUNK)
    common = dict(max_seqs=SLOTS, page_size=PAGE, pages_per_seq=PPS,
                  prefill_fn=step.prefill, prefill_chunk=CHUNK)
    if case == "larger_chunk":  # the rings are sized for the builder's
        with pytest.raises(ValueError, match="prefill_chunk"):
            compiled_decode_step(model, prefill_chunk=2 * CHUNK)
        return
    refused = {
        "oversubscribed_pool": dict(step_fn=step,
                                    num_pages=SLOTS * PPS - 3),
        "prefix_sharing": dict(step_fn=step, prefix_sharing=True,
                               copy_page_fn=step.copy_page),
        # the step's attributes hidden: the prefill function still says so
        "step_behind_a_wrapper": dict(step_fn=lambda *a: step(*a),
                                      prefix_sharing=True),
    }[case]
    with pytest.raises(ValueError, match="ring of KV pages"):
        ContinuousBatchingExecutor(**refused, **common)
    # the same pool, slot-aligned, is served (case (a))
    assert ContinuousBatchingExecutor(step, **common).slot_aligned


# a padded chunk: the last of a prompt whose prefilled tokens are not a
# multiple of the chunk; ``run_chunked_prefill`` clamps its pad rows to
# ``cap - 1``.  At the cell's sizes (page 64 x 256, chunk 512, key blocks
# of 512): positions 7680..8190 and one pad at 16383
@pytest.mark.parametrize("window,ring,want_lo,want_blocks",
                         [(0, 0, 0, 16), (4096, 73, 56, 11)],
                         ids=["global", "window"])
def test_a_padded_chunk_walks_the_key_blocks_of_its_real_rows(
        window, ring, want_lo, want_blocks):
    from flexflow_tpu.core.ptensor import DataType, ParallelTensorShape
    from flexflow_tpu.ops import GroupedDecodeAttentionOp

    shapes = [ParallelTensorShape.make(s, d) for s, d in (
        ((1, 1, 64), DataType.FLOAT32), ((1, 256), DataType.INT32),
        ((1,), DataType.INT32))]
    op = GroupedDecodeAttentionOp(
        "attn", shapes, num_heads=6, num_kv_heads=2, head_dim=16,
        page_size=64, pages_per_seq=256, window=window, ring_pages=ring)
    cap = op.max_seq_len
    assert cap == 16384
    positions = jnp.asarray(
        [list(range(7680, 8191)) + [cap - 1]], jnp.int32)
    lo, blocks = op.chunk_walk(positions)
    # global: ceil(8191 / 512) = 16 blocks, not the 32 of the pad's
    # position; window: from the page of 7680 - 4095, a fixed count
    assert (int(lo[0]), int(blocks)) == (want_lo, want_blocks)
    # no pad: the same walk
    whole = jnp.arange(7680, 8192, dtype=jnp.int32)[None, :]
    lo2, blocks2 = op.chunk_walk(whole)
    assert (int(lo2[0]), int(blocks2)) == (want_lo, want_blocks)
    # the host counts the same walk from the same positions
    assert op.chunk_keys_walked(np.asarray(positions)) == want_blocks * 512


# ---- (f) counters -----------------------------------------------------------
def test_kv_pages_walked_and_live_on_hand_made_lengths():
    model = build()
    step = compiled_decode_step(model, prefill_chunk=CHUNK)
    table = np.arange(SLOTS * PPS, dtype=np.int32).reshape(SLOTS, PPS)
    walked, live = (counter("decode.kv_pages_walked"),
                    counter("decode.kv_pages_live"))
    # rows attend 100 + 1 and 0 + 1 positions: live pages 13 and 1; a
    # window layer walks positions 69..100 = pages 8..12 (5) and 1
    step(np.ones((SLOTS, 1), np.int32), table, np.asarray([100, 0], np.int32))
    assert counter("decode.kv_pages_live") - live == 4 * (13 + 1)
    assert counter("decode.kv_pages_walked") - walked == (13 + 1) + 3 * (5 + 1)


def test_the_experts_counters_are_published_on_the_serving_path():
    model = build()
    step = compiled_decode_step(model, prefill_chunk=CHUNK)
    names = ("moe.experts_held", "moe.experts_touched", "moe.assignments",
             "moe.assignments_dropped", "moe.row_slots", "moe.rows_filled")
    before = {n: counter(n) for n in names}
    ex = ContinuousBatchingExecutor(
        step, max_seqs=SLOTS, page_size=PAGE, pages_per_seq=PPS,
        prefill_fn=step.prefill, prefill_chunk=CHUNK)
    rng = np.random.default_rng(4)
    ex.run([DecodeRequest(rid="r", prompt=rng.integers(1, 128, 30).tolist(),
                          max_new_tokens=40)])
    # no blocking publish yet: frames 1..16 reached METRICS with frame 18
    early = counter("moe.experts_held") - before["moe.experts_held"]
    assert early > 0
    step.publish_obs(block=True)
    after = {n: counter(n) - before[n] for n in names}
    programs = ex.frame + ex.prefill_chunks
    assert after["moe.experts_held"] == 8 * MOE_LAYERS * programs > early
    assert 0 < after["moe.experts_touched"] <= after["moe.experts_held"]
    assert after["moe.assignments_dropped"] == 0
    assert after["moe.rows_filled"] == after["moe.assignments"] > 0
    # a frame's bound is its own 2 x 2 assignments, a chunk's 8 x 2
    assert after["moe.row_slots"] == MOE_LAYERS * (
        ex.frame * SLOTS * 2 + ex.prefill_chunks * CHUNK * 2)
    assert device_counters.metric_of(
        "l_moe_dispatch/obs/moe.experts_touched") == "moe.experts_touched"
