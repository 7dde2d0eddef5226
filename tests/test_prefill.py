"""Chunked prefill + prefill/decode disaggregation + SLO classes
(ISSUE 15 — the serving tier's prompt path off the decode loop).

Contract highlights:

* the chunked prefill lane (runtime/prefill.py) is TOKEN-IDENTICAL to
  the prefill-via-decode oracle across ragged prompt lengths,
  including single-token prompts and exact chunk boundaries;
* TTFT decomposes exactly into queue + prefill + first-decode-frame
  spans (the attribution the ffobs report renders);
* SLO classes: priority admission order, deadline expiry instead of
  late service, preemption by strictly-higher priority — all
  deterministic under a seeded arrival trace;
* the disaggregation search prices colocated vs two-block placement in
  the phase-split serve currency, adopts only past the margin
  (honest zero on the small config), is lint-gated (SHD164/165),
  persists as __meta__.disaggregation behind the digest gate, and
  re-lints on import (corrupt artifacts fail with findings);
* fflint STR211 catches file-level corruption of the persisted
  disaggregation/SLO meta stdlib-only.
"""

import json

import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu.core.machine import MachineView
from flexflow_tpu.runtime.decode import (
    ContinuousBatchingExecutor,
    DecodeRequest,
    SLOClass,
    compiled_decode_step,
)

N_DEV = 8

# the short-prompt interactive regime where disaggregation genuinely
# wins on the stock machine model
CHAT_KW = dict(vocab=4096, num_layers=2, hidden=2048, num_heads=16,
               ff_dim=4096, page_size=16, pages_per_seq=32)
CHAT_ARRIVAL = dict(serve_prompt_tokens_mean=128,
                    serve_decode_tokens_mean=32)

SMALL_KW = dict(vocab=256, num_layers=2, hidden=64, num_heads=4,
                ff_dim=64, page_size=4, pages_per_seq=8)


def _trivial_strategy(graph):
    return {
        n.guid: (n.op.fixed_machine_view()
                 or MachineView.trivial(n.op.output_shapes[0].ndim))
        for n in graph.topo_order()
    }


def _compiled_small(num_devices=1, batch=4, compute_dtype="bfloat16",
                    **overrides):
    from flexflow_tpu.models import build_gpt_decode

    kw = dict(SMALL_KW)
    kw.update(overrides)
    cfg = ff.FFConfig(batch_size=batch, num_devices=num_devices,
                      cost_cache_file="", compute_dtype=compute_dtype)
    m = build_gpt_decode(cfg, **kw)
    m.compile(loss_type="sparse_categorical_crossentropy", metrics=[],
              comp_mode="inference",
              strategy=_trivial_strategy(m.graph))
    return m


@pytest.fixture(scope="module")
def small_model():
    """One compiled small decode model shared by the executor-level
    tests.  Every ``compiled_decode_step`` over it serves from the ONE
    live pool in ``model.state`` (the state is donated), so a lane
    starts on the pages the lane before it left — which no sequence
    reads before writing them — without recompiling the model."""
    return _compiled_small()


# ---------------------------------------------------------------------------
# chunked prefill: token identity with the prefill-via-decode oracle
# ---------------------------------------------------------------------------
def _serve(model, chunk, prompts, max_new=4, slots=4):
    step = compiled_decode_step(model, prefill_chunk=chunk)
    ex = ContinuousBatchingExecutor(
        step, max_seqs=slots, page_size=SMALL_KW["page_size"],
        pages_per_seq=SMALL_KW["pages_per_seq"],
        prefill_fn=getattr(step, "prefill", None), prefill_chunk=chunk)
    reqs = [DecodeRequest(rid=f"r{i}", prompt=list(p),
                          max_new_tokens=max_new)
            for i, p in enumerate(prompts)]
    out = ex.run(reqs, max_frames=600)
    return out, ex


def test_chunked_prefill_token_identity_ragged(small_model):
    """THE acceptance contract: the chunked lane's generated tokens
    equal the token-by-token oracle's for ragged prompt lengths
    including single-token (nothing to prefill), chunk-boundary
    (len-1 a multiple of the chunk), and cross-chunk prompts."""
    rng = np.random.default_rng(3)
    chunk = 8
    # 1 = single-token; 9 = exactly one full chunk of prefill (8 = L-1);
    # 17 = two full chunks; 5/12/23 = ragged tails
    lengths = (1, 2, 5, 9, 12, 17, 23)
    prompts = [list(map(int, rng.integers(1, 255, size=L)))
               for L in lengths]
    out_oracle, ex0 = _serve(small_model, 0, prompts)
    out_chunk, ex1 = _serve(small_model, chunk, prompts)
    assert out_oracle == out_chunk
    # the lane genuinely ran and genuinely saved frames
    assert ex1.prefill_tokens == sum(L - 1 for L in lengths)
    assert ex1.prefill_chunks == sum(
        -(-(L - 1) // chunk) for L in lengths if L > 1)
    assert ex1.frame < ex0.frame


@pytest.mark.slow
def test_chunked_prefill_on_searched_multidevice_strategy():
    """The lane composes with a SEARCHED sharded strategy on the host
    mesh: the chunk writer updates the placed KV state (the
    state_shardings discipline), still token-identical."""
    from flexflow_tpu.core.machine import MachineSpec
    from flexflow_tpu.models import build_gpt_decode

    kw = dict(vocab=256, num_layers=1, hidden=64, num_heads=4,
              ff_dim=64, page_size=4, pages_per_seq=4)

    def build():
        cfg = ff.FFConfig(batch_size=8, num_devices=N_DEV,
                          search_budget=4, search_timeout_s=20.0,
                          cost_cache_file="",
                          machine_spec=MachineSpec.host_cpu(N_DEV))
        m = build_gpt_decode(cfg, **kw)
        m.compile(loss_type="sparse_categorical_crossentropy",
                  metrics=[], comp_mode="inference")
        return m

    prompts = [[5, 6, 7, 8, 9, 10], [3], [11, 12, 13]]

    def run(chunk):
        m = build()
        step = compiled_decode_step(m, prefill_chunk=chunk)
        ex = ContinuousBatchingExecutor(
            step, max_seqs=8, page_size=4, pages_per_seq=4,
            prefill_fn=getattr(step, "prefill", None),
            prefill_chunk=chunk)
        return ex.run([DecodeRequest(rid=f"r{i}", prompt=list(p),
                                     max_new_tokens=4)
                       for i, p in enumerate(prompts)], max_frames=200)

    assert run(0) == run(4)


@pytest.mark.parametrize("compute_dtype", ["bfloat16", "float32"])
def test_chunk_state_is_the_same_over_the_served_tree(compute_dtype):
    """The prefill chunk handed ``step.weights`` (what ``step.prefill``
    hands it) and handed ``model.params`` leaves every state leaf bit
    for bit the same — over two chunks, the second attending to the
    first's rows — and ``step.prefill`` is the first of the two."""
    import jax
    import jax.numpy as jnp

    m = _compiled_small(compute_dtype=compute_dtype)
    step = compiled_decode_step(m, prefill_chunk=8)
    rng = np.random.default_rng(5)
    ids = rng.integers(1, 255, size=(2, 1, 8)).astype(np.int32)
    table = np.arange(8, dtype=np.int32)[None, :]

    def write(run):
        m.state = jax.tree.map(jnp.zeros_like, m.state)
        for c in range(2):
            run(ids[c], (8 * c + np.arange(8, dtype=np.int32))[None, :],
                table)
        return {k: np.asarray(v) for k, v in m.state.items()}

    def over(tree):
        def run(ids, positions, page_table):
            m.state = step.chunk_fn(tree, m.state, ids, positions,
                                    page_table, np.int32(1))
        return run

    served = write(over(step.weights))
    for other in (write(over(m.params)), write(step.prefill)):
        assert served.keys() == other.keys()
        for key, val in served.items():
            np.testing.assert_array_equal(val, other[key], err_msg=key)
    assert all(np.any(v[:2] != 0) for v in served.values())


# ---------------------------------------------------------------------------
# one call a prompt: the prefill program loops over the prompt's chunks
# ---------------------------------------------------------------------------
def _seeded_state(model, seed):
    """Every state leaf filled with seeded values of its dtype (no zero
    in a pool), so a row a run writes, a row it leaves and a row of a
    page it never touches all show in a comparison."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    state = {}
    for key, val in sorted(model.state.items()):
        if jnp.issubdtype(val.dtype, jnp.integer):
            draw = rng.integers(-127, 128, val.shape)
        elif key.endswith("_scale"):
            draw = rng.uniform(0.004, 0.012, val.shape)
        else:
            draw = rng.normal(0, 1, val.shape)
        state[key] = jnp.asarray(draw, val.dtype)
    return state


def _chunk_by_chunk(step, chunk):
    """``step.prefill`` handed a run one chunk at a time: the same
    program at n = 1, called once a chunk — what the lane did before
    a prompt's chunks ran in one call."""
    def prefill(ids, positions, page_table):
        for c0 in range(0, ids.shape[1], chunk):
            step.prefill(ids[:, c0:c0 + chunk],
                         positions[:, c0:c0 + chunk], page_table)
    return prefill


# (prompt length, start): ragged; one chunk exactly; a last chunk that
# reaches ``cap - 1`` (cap 32: 30 prefilled tokens, positions 24..31);
# ``start > 0`` (prefix sharing's skip-ahead), starting inside a page
RUNS = {"ragged": (12, 0), "one_chunk": (9, 0), "to_cap": (31, 0),
        "four_chunks_from_5": (30, 5)}


@pytest.mark.parametrize("pool", ["fp32", "int8"])
@pytest.mark.parametrize("compute_dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("run", RUNS)
def test_one_call_over_n_chunks_leaves_the_state_of_n_calls(
        run, compute_dtype, pool):
    """THE equality of the loop: a prompt's chunks sent as ONE run and
    prefilled by one call leave every state leaf, bit for bit, as the
    same chunks sent one call each — the chunk i + 1 of the loop
    attends to the K/V chunk i wrote, as the next call's did — over a
    pool seeded everywhere, for ragged lengths, a run clamped at
    ``cap - 1``, a run that starts past a shared prefix, and the int8
    pool with its scales."""
    from flexflow_tpu.models import build_gpt_decode
    from flexflow_tpu.runtime.prefill import run_chunked_prefill

    chunk = 8
    lane = (dict(objective="serve", kv_precision="int8")
            if pool == "int8" else {})
    cfg = ff.FFConfig(batch_size=4, num_devices=1, cost_cache_file="",
                      compute_dtype=compute_dtype, **lane)
    m = build_gpt_decode(cfg, **SMALL_KW)
    m.compile(loss_type="sparse_categorical_crossentropy", metrics=[],
              comp_mode="inference")
    assert {str(v.dtype) for k, v in m.state.items()
            if k.endswith("k_cache")} == {"int8" if lane else "float32"}
    step = compiled_decode_step(m, prefill_chunk=chunk)
    length, start = RUNS[run]
    cap = SMALL_KW["page_size"] * SMALL_KW["pages_per_seq"]
    tokens = np.random.default_rng(length).integers(1, 255, size=length)
    pages = np.random.default_rng(7).permutation(32)[:8].tolist()
    left = {}
    for how, prefill in (("one call", step.prefill),
                         ("a call a chunk", _chunk_by_chunk(step, chunk))):
        m.state = _seeded_state(m, seed=len(run))
        sent = run_chunked_prefill(prefill, tokens.tolist(), pages,
                                   chunk=chunk, cap=cap, start=start)
        assert sent == -(-(length - 1 - start) // chunk)
        left[how] = {k: np.asarray(v) for k, v in m.state.items()}
    assert sent > 1 or run == "one_chunk"
    seeded = _seeded_state(m, seed=len(run))
    for key, want in left["a call a chunk"].items():
        np.testing.assert_array_equal(left["one call"][key], want,
                                      err_msg=key)
        if key.endswith("_cache"):  # the run wrote its pages, only them
            assert np.any(want != np.asarray(seeded[key])), key


def test_the_executor_calls_the_prefill_program_once_a_prompt(small_model):
    """Engagement: behind ``ContinuousBatchingExecutor`` every admitted
    prompt that has tokens to prefill is ONE ``prefill_fn`` call —
    counted by ``decode.prefill_calls`` — while ``decode.prefill_chunks``
    counts its chunks as before; a one-token prompt makes no call."""
    from flexflow_tpu.obs.metrics import METRICS

    chunk = 8
    lengths = (1, 2, 9, 12, 17, 23, 31)
    step = compiled_decode_step(small_model, prefill_chunk=chunk)
    runs = []

    def prefill(ids, positions, page_table):
        runs.append(ids.shape)
        step.prefill(ids, positions, page_table)

    counters = ("decode.prefill_calls", "decode.prefill_chunks")
    before = [METRICS.counter(c).value for c in counters]
    ex = ContinuousBatchingExecutor(
        step, max_seqs=4, page_size=SMALL_KW["page_size"],
        pages_per_seq=SMALL_KW["pages_per_seq"], prefill_fn=prefill,
        prefill_chunk=chunk)
    rng = np.random.default_rng(2)
    out = ex.run([DecodeRequest(rid=f"r{i}", max_new_tokens=1,
                                prompt=rng.integers(1, 255, L).tolist())
                  for i, L in enumerate(lengths)], max_frames=200)
    assert len(out) == len(lengths)
    chunks = [-(-(L - 1) // chunk) for L in lengths if L > 1]
    assert sorted(runs) == sorted((1, n * chunk) for n in chunks)
    assert ex.prefill_calls == len(chunks) and ex.prefill_chunks == sum(chunks)
    assert [METRICS.counter(c).value - b for c, b in zip(counters, before)] \
        == [len(chunks), sum(chunks)]


def test_prompts_of_every_chunk_count_run_one_program():
    """Prompts of 1 to the context's chunks (the largest clamped at
    ``cap - 1``) are padded to one width and run by ONE compiled
    program: once the program has been called, no prompt length makes
    a compile request.  (It is called twice first: the first call takes
    the state ``init_params`` made, the second the program's own output,
    committed to its device — jax keys its cache by that too, whatever
    the chunk count; the serving cells' warm-up and probe make both.)"""
    from flexflow_tpu.obs.metrics import METRICS
    from flexflow_tpu.runtime.compile_cache import watch_jax_compiles
    from flexflow_tpu.runtime.prefill import run_chunked_prefill

    watch_jax_compiles()
    chunk = 8
    cap = SMALL_KW["page_size"] * SMALL_KW["pages_per_seq"]
    m = _compiled_small()
    step = compiled_decode_step(m, prefill_chunk=chunk)
    requests = METRICS.counter("jax.compile_requests")
    ours = METRICS.counter("jax.compile_requests|fun=jit(fwd)")
    table = list(range(SMALL_KW["pages_per_seq"]))
    rng = np.random.default_rng(4)

    def prompt(chunks):
        return rng.integers(1, 255, chunks * chunk + 1).tolist()

    for _ in range(2):
        run_chunked_prefill(step.prefill, prompt(1), table, chunk=chunk,
                            cap=cap)
    assert ours.value >= 1
    before = requests.value
    for n in range(1, cap // chunk + 1):
        tokens = prompt(n)[:cap - 1]  # n = 4: 30 tokens, clamped at 31
        assert run_chunked_prefill(step.prefill, tokens, table, chunk=chunk,
                                   cap=cap) == n
    assert requests.value == before


# ---------------------------------------------------------------------------
# the chunk's own write and read of the pool (DecodeAttentionOp.forward_chunk)
# ---------------------------------------------------------------------------
CHUNK_OP = dict(embed_dim=64, num_heads=4, page_size=8, pages_per_seq=8,
                num_pages=24)


def _chunk_op(kv_dtype="fp32", **overrides):
    from flexflow_tpu.core.ptensor import DataType, ParallelTensorShape
    from flexflow_tpu.ops import DecodeAttentionOp

    kw = dict(CHUNK_OP, **overrides)
    shapes = [ParallelTensorShape.make(s, d) for s, d in (
        ((2, 1, kw["embed_dim"]), DataType.FLOAT32),
        ((2, kw["pages_per_seq"]), DataType.INT32), ((2,), DataType.INT32))]
    return DecodeAttentionOp("attn", shapes, kv_dtype=kv_dtype, **kw)


def _chunk_fixture(op, c, seed=0):
    """Seeded weights, a pool with no zero in it, two rows over pages
    dealt out of order, and a [2, c, E] chunk."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    a = op.attrs
    e, hd = a["embed_dim"], a["num_heads"] * op.head_dim
    weights = {n: jnp.asarray(rng.normal(0, e ** -0.5, shape), jnp.float32)
               for n, shape in (("wq", (e, hd)), ("wk", (e, hd)),
                                ("wv", (e, hd)), ("wo", (hd, e)))}
    state = {}
    for leaf, shape, dtype, _ in op.state_specs():
        if dtype == jnp.int8:
            val = rng.integers(-127, 128, shape)
        elif leaf.endswith("_scale"):  # int8 rows of about unit size
            val = rng.uniform(0.004, 0.012, shape)
        else:  # rows the compute dtype could have made
            val = np.asarray(jnp.asarray(rng.normal(0, 1, shape),
                                         jnp.bfloat16))
        state[f"{op.name}/{leaf}"] = jnp.asarray(val, dtype)
    table = rng.permutation(a["num_pages"])[:2 * a["pages_per_seq"]]
    table = jnp.asarray(table.reshape(2, -1), jnp.int32)
    hidden = jnp.asarray(rng.normal(0, 1, (2, c, e)), jnp.float32)
    return weights, state, table, hidden


def _dense_chunk(op, ctx, inputs, weights):
    """The whole-table formulation ``forward_chunk`` had: one row-sized
    scatter a token, then every query against the sequence's WHOLE page
    table, densified, with one softmax — the reference of the page-wise
    write and the blocked walk."""
    import math

    import jax
    import jax.numpy as jnp

    from flexflow_tpu.kernels.ragged_paged_attention import (
        NEG_INF,
        gather_kv_pages,
        gather_kv_pages_quant,
    )
    from flexflow_tpu.ops.decode_attention import _quantize_kv

    a = op.attrs
    hidden, page_table, positions = inputs
    cd = ctx.compute_dtype
    x = hidden.astype(cd)
    w = op.serving_weights(weights, cd)
    q, k_new, v_new = op._project(x, w)
    h, ps = a["num_heads"], a["page_size"]
    qf = q.reshape(*x.shape[:2], h, op.head_dim)
    slot = positions % ps
    page = jnp.take_along_axis(
        page_table, jnp.minimum(positions // ps, a["pages_per_seq"] - 1),
        axis=1)
    rows = {"k_cache": k_new, "v_cache": v_new}
    if op.kv_dtype == "int8":
        rows["k_cache"], rows["k_scale"] = _quantize_kv(k_new)
        rows["v_cache"], rows["v_scale"] = _quantize_kv(v_new)
    pools = {}
    for leaf, r in rows.items():
        pool = ctx.state_in[f"{op.name}/{leaf}"]
        pools[leaf] = pool.at[page, slot].set(r.astype(pool.dtype))
        ctx.state_out[f"{op.name}/{leaf}"] = pools[leaf]
    if op.kv_dtype == "int8":
        k_dense = gather_kv_pages_quant(pools["k_cache"], pools["k_scale"],
                                        page_table, h)
        v_dense = gather_kv_pages_quant(pools["v_cache"], pools["v_scale"],
                                        page_table, h)
    else:
        k_dense = gather_kv_pages(pools["k_cache"], page_table, h)
        v_dense = gather_kv_pages(pools["v_cache"], page_table, h)
    s = jnp.einsum("bchd,bshd->bchs", qf, k_dense) / math.sqrt(op.head_dim)
    seen = (jnp.arange(k_dense.shape[1])[None, None, :]
            <= positions[:, :, None])
    p = jax.nn.softmax(jnp.where(seen[:, :, None, :], s, NEG_INF), axis=-1)
    out = jnp.einsum("bchs,bshd->bchd", p, v_dense)
    y = jnp.dot(out.astype(cd).reshape(*x.shape[:2], -1), w["wo"],
                preferred_element_type=jnp.float32)
    return [y.astype(hidden.dtype)]


def _run_chunk(fn, op, compute_dtype, weights, state, inputs):
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.ops.base import LoweringContext

    def call(state, *inputs):
        ctx = LoweringContext(compute_dtype=jnp.dtype(compute_dtype),
                              train=False, state_in=state)
        (y,) = fn(ctx, list(inputs), weights)
        return y, {**state, **ctx.state_out}

    return jax.jit(call)(state, *inputs)


# two rows a case, (c0 of row 0, c0 of row 1); chunk 16, page 8, cap 64
CHUNK_STARTS = {
    "first": (0, 0),
    "second": (16, 32),
    "inside_a_page": (21, 3),
    "pad_tail": (40, 45),
    "clamped_at_cap": (56, 50),
}


@pytest.mark.parametrize("kv_dtype", ["fp32", "int8"])
@pytest.mark.parametrize("compute_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", CHUNK_STARTS)
def test_chunk_matches_the_dense_whole_table_reference(
        case, compute_dtype, kv_dtype):
    """The chunk — its K/V written page by page, its queries walking key
    blocks to the chunk's last position — against the whole-table
    formulation: the outputs agree, and EVERY state leaf is the row
    scatter's, bit for bit (the rows the chunk wrote, the rows of a
    touched page it did not, every other page), wherever the run
    starts: at 0, at a later chunk, inside a page, with a pad tail
    (zero embeddings), folded onto ``cap - 1`` by the clamp."""
    import jax.numpy as jnp

    c = 16
    op = _chunk_op(kv_dtype)
    weights, state, table, hidden = _chunk_fixture(op, c, seed=len(case))
    cap = op.max_seq_len
    starts = np.asarray(CHUNK_STARTS[case])[:, None]
    positions = jnp.asarray(
        np.minimum(starts + np.arange(c), cap - 1), jnp.int32)
    if case == "pad_tail":
        hidden = hidden.at[:, 5:].set(0.0)
    inputs = (hidden, table, positions)
    want_y, want_state = _run_chunk(
        lambda *a: _dense_chunk(op, *a), op, compute_dtype, weights, state,
        inputs)
    got_y, got_state = _run_chunk(
        op.forward_chunk, op, compute_dtype, weights, state, inputs)
    tol = 2e-2 if compute_dtype == "bfloat16" else 2e-5
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y),
                               atol=tol, rtol=tol)
    assert got_state.keys() == want_state.keys()
    for key, want in want_state.items():
        np.testing.assert_array_equal(
            np.asarray(got_state[key]), np.asarray(want), err_msg=key)
        # the pages the run does not touch are the ones it was handed
        touched = np.unique(np.asarray(jnp.take_along_axis(
            table, positions // op.attrs["page_size"], axis=1)))
        rest = np.setdiff1d(np.arange(op.attrs["num_pages"]), touched)
        np.testing.assert_array_equal(
            np.asarray(got_state[key])[rest], np.asarray(state[key])[rest],
            err_msg=key)


def test_chunk_takes_the_row_scatter_for_a_run_that_is_not_contiguous():
    """Nothing sends one — ``run_chunked_prefill`` sends one contiguous
    run — but positions that are not (here: reversed) still land row by
    row, as the reference's do."""
    import jax.numpy as jnp

    c = 16
    op = _chunk_op()
    weights, state, table, hidden = _chunk_fixture(op, c)
    positions = jnp.asarray(
        np.stack([np.arange(c)[::-1], 2 * np.arange(c) + 7]), jnp.int32)
    inputs = (hidden, table, positions)
    want_y, want_state = _run_chunk(
        lambda *a: _dense_chunk(op, *a), op, "float32", weights, state,
        inputs)
    got_y, got_state = _run_chunk(
        op.forward_chunk, op, "float32", weights, state, inputs)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y),
                               atol=2e-5, rtol=2e-5)
    for key, want in want_state.items():
        np.testing.assert_array_equal(
            np.asarray(got_state[key]), np.asarray(want), err_msg=key)


def _pool_updates(jaxpr, fallback=False):
    """(primitive, update shape, inside the non-contiguous fallback) of
    every scatter and dynamic_update_slice of a jaxpr, sub-jaxprs
    included; a ``cond``'s branch 0 is what runs when its predicate is
    False."""
    found = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name.startswith("scatter"):
            found.append((name, eqn.invars[2].aval.shape, fallback))
        elif name == "dynamic_update_slice":
            found.append((name, eqn.invars[1].aval.shape, fallback))
        if name == "cond":
            for i, branch in enumerate(eqn.params["branches"]):
                found += _pool_updates(branch.jaxpr, fallback or i == 0)
            continue
        for sub in eqn.params.values():
            for j in (sub if isinstance(sub, (tuple, list)) else (sub,)):
                inner = getattr(j, "jaxpr", j)
                if hasattr(inner, "eqns"):
                    found += _pool_updates(inner, fallback)
    return found


@pytest.mark.parametrize("kv_dtype", ["fp32", "int8"])
def test_chunk_program_holds_no_row_sized_update_of_a_pool(kv_dtype):
    """The chunk's jaxpr at the serving cell's geometry (page 32, chunk
    64): every update of a pool on the path a contiguous run takes has
    a PAGE-sized window, 64 / 32 + 1 = 3 of them a sequence; the one
    scatter with 64 row-sized updates a pool sits in the branch that a
    run which is not contiguous takes, and nowhere else."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.ops.base import LoweringContext

    c, ps = 64, 32
    op = _chunk_op(kv_dtype, embed_dim=128, num_heads=2, page_size=ps,
                   pages_per_seq=8)
    weights, state, table, hidden = _chunk_fixture(op, c)
    positions = jnp.asarray(
        np.stack([np.arange(c), 70 + np.arange(c)]), jnp.int32)

    def call(state, hidden, table, positions):
        ctx = LoweringContext(compute_dtype=jnp.bfloat16, train=False,
                              state_in=state)
        (y,) = op.forward_chunk(ctx, [hidden, table, positions], weights)
        return y, ctx.state_out

    jaxpr = jax.make_jaxpr(call)(state, hidden, table, positions).jaxpr
    updates = _pool_updates(jaxpr)
    hd = 128
    pools = 4 if kv_dtype == "int8" else 2
    taken = [(n, shape) for n, shape, fallback in updates if not fallback]
    by_page = [shape for n, shape in taken if n.startswith("scatter")]
    # [B, pages a run touches, page, (H·D)]: page-sized windows
    assert sorted(by_page) == sorted(
        [(2, 3, ps, hd)] * 2 + [(2, 3, ps)] * (pools - 2)), updates
    # what else the taken path updates is a row's worth of the CHUNK, laid
    # at its offset into the pages it covers: never a pool
    assert all(shape[0] == c for n, shape in taken
               if n == "dynamic_update_slice"), updates
    by_row = [shape for n, shape, fallback in updates if fallback]
    assert sorted(by_row) == sorted(
        [(2, c, hd)] * 2 + [(2, c)] * (pools - 2)), updates


def test_prefill_keys_counters_count_what_the_chunk_walks():
    """``decode.prefill_keys_walked`` / ``_table`` at the serving cell's
    geometry (page 32 x 32, chunk 64): a prompt of 65 tokens is one
    chunk that walks one key block, one of 897 fourteen chunks, the
    last of which walks the whole table — counted on the host from the
    positions sent, by the function the op takes its trip count from."""
    import jax.numpy as jnp

    from flexflow_tpu.obs.metrics import METRICS
    from flexflow_tpu.runtime.prefill import run_chunked_prefill

    chunk, cap = 64, 1024
    m = _compiled_small(batch=2, page_size=32, pages_per_seq=32, hidden=32,
                        ff_dim=32, vocab=64)
    step = compiled_decode_step(m, prefill_chunk=chunk)
    ops = [n.op for n in m.graph.topo_order()
           if n.op.op_type.name == "DECODE_ATTENTION"]
    assert len(ops) == 2
    block = ops[0].chunk_block_pages * 32
    assert block == ops[0].CHUNK_KEY_BLOCK and cap % block == 0

    def counters():
        c = METRICS.snapshot()["counters"]
        return (c.get("decode.prefill_keys_walked", 0),
                c.get("decode.prefill_keys_table", 0))

    sent = []

    def prefill(ids, positions, table):
        sent.append(positions)
        step.prefill(ids, positions, table)

    rng = np.random.default_rng(0)
    for length, chunks in ((65, 1), (897, 14)):
        del sent[:]
        before = counters()
        tokens = list(map(int, rng.integers(1, 63, size=length)))
        assert run_chunked_prefill(prefill, tokens, list(range(32)),
                                   chunk=chunk, cap=cap) == chunks
        walked, table = (a - b for a, b in zip(counters(), before))
        assert table == len(ops) * chunks * cap
        # by hand: a chunk at c0 walks the blocks up to position c0 + 63
        by_hand = sum((c0 + chunk - 1) // block + 1
                      for c0 in range(0, length - 1, chunk)) * block
        assert walked == len(ops) * by_hand
        # and by the op: the trip count ``forward_chunk`` loops to, a
        # chunk of the ONE run each prompt is sent
        (run,) = sent
        assert run.shape == (1, chunks * chunk)
        trips = sum(int(op.chunk_walk(jnp.asarray(run[:, c0:c0 + chunk]))[1])
                    for op in ops for c0 in range(0, run.shape[1], chunk))
        assert walked == trips * block
    assert walked < table  # the last prompt: 14 chunks, 2 to 4 blocks of 4


def test_chunk_forward_rejects_non_decode_graph():
    from flexflow_tpu.models import build_mlp_unify
    from flexflow_tpu.runtime.prefill import build_chunk_forward

    cfg = ff.FFConfig(batch_size=4, num_devices=1, cost_cache_file="")
    m = build_mlp_unify(cfg, in_dim=16, hidden=(16,))
    with pytest.raises(ValueError, match="no DecodeAttentionOp"):
        build_chunk_forward(m.graph, np.float32)


def test_prefill_weight_bridge():
    """The weight-correspondence bridge: build_gpt_prefill and
    build_gpt_decode share one parameter set name-for-name (the
    positional table as a prefix); a vocab mismatch is a hard error."""
    from flexflow_tpu.models import (
        build_gpt_decode,
        build_gpt_prefill,
        derive_prefill_model,
    )
    from flexflow_tpu.runtime.prefill import prefill_weight_bridge

    cfg = ff.FFConfig(batch_size=4, num_devices=1, cost_cache_file="")
    dec = build_gpt_decode(cfg, **SMALL_KW)
    pre, _ = derive_prefill_model(dec.graph, cfg, seq_len=16)
    bridge = prefill_weight_bridge(pre.graph, dec.graph)
    # every prefill weight maps to a same-named decode weight
    assert all(k.split("/")[0] == v.split("/")[0]
               for k, v in bridge.items())
    assert "lm_head/kernel" in bridge and "tok_embed/table" in bridge
    # positional prefix rule: prefill pos table (16 rows) maps onto the
    # decode table (page_size * pages_per_seq = 32 rows)
    assert "pos_embed/table" in bridge
    # vocab mismatch must NOT ride the prefix rule
    wrong = build_gpt_prefill(
        cfg, **{**{k: v for k, v in SMALL_KW.items()
                   if k not in ("page_size", "pages_per_seq")},
                "vocab": 128}, seq_len=16)
    with pytest.raises(ValueError, match="shape mismatch"):
        prefill_weight_bridge(wrong.graph, dec.graph)


# ---------------------------------------------------------------------------
# TTFT split telemetry
# ---------------------------------------------------------------------------
def test_ttft_splits_into_queue_prefill_first_frame(tmp_path,
                                                    small_model):
    from flexflow_tpu.obs.events import BUS, validate_event

    log = str(tmp_path / "obs.jsonl")
    BUS.configure(log)
    try:
        out, ex = _serve(small_model, 4, [[1, 2, 3, 4, 5, 6, 7], [9]])
        s = ex.summary()
        assert s["requests_recorded"] == 2
        for r in ex.request_records:
            assert r["phase"] == "finish"
            # the split sums to TTFT exactly (same stamps, no gaps)
            assert r["ttft_s"] == pytest.approx(
                r["queue_s"] + r["prefill_s"] + r["first_frame_s"],
                rel=1e-6, abs=1e-9)
        assert s["prefill_p50_s"] is not None
        assert s["first_frame_p99_s"] is not None
        BUS.flush()
        with open(log) as f:
            events = [json.loads(line) for line in f]
        for e in events:
            assert validate_event(e) == []
        kinds = {e["kind"] for e in events}
        assert "decode.prefill" in kinds  # the lane emitted its event
    finally:
        BUS.close()


# ---------------------------------------------------------------------------
# SLO classes: priority admission, deadline expiry, preemption
# ---------------------------------------------------------------------------
def _synthetic_step(vocab=97):
    def step(ids, table, lens):
        ids = np.asarray(ids)
        lens = np.asarray(lens)
        nxt = (ids[:, 0] * 7 + lens * 13 + 5) % vocab
        logits = np.zeros((ids.shape[0], 1, vocab), np.float32)
        logits[np.arange(ids.shape[0]), 0, nxt] = 1.0
        return logits

    return step


SLO_TABLE = (
    SLOClass("interactive", priority=2, deadline_frames=0),
    SLOClass("standard", priority=1, deadline_frames=0),
    SLOClass("batch", priority=0, deadline_frames=0),
)


def test_priority_admission_order():
    """With one open slot and a full queue, the higher-priority class
    admits first regardless of submission order."""
    ex = ContinuousBatchingExecutor(
        _synthetic_step(), max_seqs=1, page_size=4, pages_per_seq=4,
        slo_classes=SLO_TABLE)
    ex.submit([DecodeRequest(rid="batch", prompt=[1], max_new_tokens=2,
                             slo="batch"),
               DecodeRequest(rid="inter", prompt=[2], max_new_tokens=2,
                             slo="interactive")])
    ex.step()
    live = [s for s in ex.slots if s is not None]
    assert live and live[0].req.rid == "inter"
    ex.run(max_frames=50)
    assert set(ex.finished) == {"batch", "inter"}


def test_deadline_expiry_refuses_late_service():
    """A queued request whose deadline_frames passes is EXPIRED (never
    served late): recorded in .expired, absent from .finished."""
    ex = ContinuousBatchingExecutor(
        _synthetic_step(), max_seqs=1, page_size=4, pages_per_seq=4)
    ex.submit([DecodeRequest(rid="long", prompt=[1], max_new_tokens=10),
               DecodeRequest(rid="dead", prompt=[2], max_new_tokens=2,
                             deadline_frames=3)])
    out = ex.run(max_frames=100)
    assert "dead" not in out and "dead" in ex.expired
    assert ex.total_expired == 1
    assert len(out["long"]) == 10


def test_preemption_by_higher_priority_continues_stream():
    """A strictly-higher-priority arrival preempts the lowest-priority
    live sequence; the victim re-queues with its tokens so far and —
    regeneration being deterministic — finishes with EXACTLY the
    tokens of an unpreempted run."""
    solo = ContinuousBatchingExecutor(
        _synthetic_step(), max_seqs=1, page_size=4, pages_per_seq=4)
    expect = solo.run([DecodeRequest(rid="low", prompt=[3, 4],
                                     max_new_tokens=6)], max_frames=60)

    ex = ContinuousBatchingExecutor(
        _synthetic_step(), max_seqs=1, page_size=4, pages_per_seq=4,
        slo_classes=SLO_TABLE)
    ex.submit([DecodeRequest(rid="low", prompt=[3, 4], max_new_tokens=6,
                             slo="batch")])
    ex.step()  # low admitted and running
    assert ex.slots[0] is not None and ex.slots[0].req.rid == "low"
    ex.submit([DecodeRequest(rid="hi", prompt=[9], max_new_tokens=2,
                             slo="interactive")])
    out = ex.run(max_frames=100)
    assert ex.total_preempted == 1
    assert out["low"] == expect["low"]  # the stream survived preemption
    assert len(out["hi"]) == 2


def test_slo_scheduling_deterministic_under_seeded_trace():
    """The acceptance determinism gate: a seeded ragged arrival trace
    with mixed classes, deadlines, and pool pressure produces
    IDENTICAL admissions, expirations, preemptions and token streams
    across runs."""

    def run():
        rng = np.random.default_rng(11)
        ex = ContinuousBatchingExecutor(
            _synthetic_step(), max_seqs=2, page_size=4, pages_per_seq=4,
            num_pages=8, slo_classes=SLO_TABLE)
        outs = {}
        for wave in range(4):
            reqs = []
            for j in range(3):
                cls = ("interactive", "standard", "batch")[
                    int(rng.integers(0, 3))]
                L = int(rng.integers(1, 6))
                reqs.append(DecodeRequest(
                    rid=f"w{wave}r{j}",
                    prompt=list(map(int, rng.integers(1, 96, size=L))),
                    max_new_tokens=int(rng.integers(1, 5)),
                    slo=cls,
                    deadline_frames=(6 if cls == "interactive"
                                     else None)))
            ex.submit(reqs)
            for _ in range(3):
                ex.step()
        outs = ex.run(max_frames=300)
        return (outs, dict(ex.expired), ex.total_preempted,
                ex.total_expired, ex.total_admitted)

    assert run() == run()


def test_measured_request_p99_per_class(tmp_path):
    from flexflow_tpu.obs.events import BUS

    BUS.configure(str(tmp_path / "obs.jsonl"))
    try:
        ex = ContinuousBatchingExecutor(
            _synthetic_step(), max_seqs=2, page_size=4, pages_per_seq=4,
            slo_classes=SLO_TABLE)
        reqs = [DecodeRequest(rid=f"r{i}", prompt=[1 + i],
                              max_new_tokens=2,
                              slo=("interactive" if i % 2 else "batch"))
                for i in range(6)]
        ex.run(reqs, max_frames=60)
        s = ex.summary()
        assert set(s["slo_classes"]) == {"interactive", "batch"}
        for name in ("interactive", "batch"):
            v = ex.measured_request_p99("ttft_s", slo=name)
            assert v is not None and v > 0
        assert ex.measured_request_p99("ttft_s") is not None
    finally:
        BUS.close()


# ---------------------------------------------------------------------------
# disaggregation: search, lints, persistence, import
# ---------------------------------------------------------------------------
def _chat_cfg(**overrides):
    kw = dict(batch_size=32, num_devices=N_DEV, search_budget=8,
              search_timeout_s=60.0, objective="serve",
              comp_mode="inference", cost_cache_file="",
              **CHAT_ARRIVAL)
    kw.update(overrides)
    return ff.FFConfig(**kw)


@pytest.fixture(scope="module")
def chat_search():
    from flexflow_tpu.models import build_gpt_decode
    from flexflow_tpu.search.driver import optimize_strategy

    cfg = _chat_cfg()
    m = build_gpt_decode(cfg, **CHAT_KW)
    g, s = optimize_strategy(m.graph, cfg, return_graph=True)
    return cfg, m.graph, g, s


def test_disaggregation_adopts_where_handoff_is_cheap(chat_search):
    """THE acceptance scenario (prefill/decode disaggregation,
    simulated): on the short-prompt interactive
    config — the weight-streaming-bound prefill regime, where a
    prompt's KV handoff is cheap relative to the phase interference
    colocation pays — the search PICKS disaggregation."""
    from flexflow_tpu.search.disaggregation import propose_disaggregation

    cfg, base, g, s = chat_search
    prop = propose_disaggregation(
        g, s, cfg, base_graph=base if g is not base else None)
    assert prop is not None and prop.adopted
    assert prop.disagg_step_s < prop.colocated_step_s
    assert prop.handoff_s > 0
    assert prop.prefill_devices + prop.decode_devices <= N_DEV
    assert prop.prefill_strategy and prop.decode_strategy


def test_disaggregation_honest_zero_on_long_cache_config():
    """The long-cache serving-regime config keeps colocation (its
    decode phase wants every device and its handoff payload is fat):
    the proposal is still returned — both prices recorded — but NOT
    adopted.  The search does not manufacture divergence."""
    from flexflow_tpu.models import (
        GPT_DECODE_SERVE_KW,
        SERVE_FRAME_SLOTS,
        build_gpt_decode,
    )
    from flexflow_tpu.search.disaggregation import propose_disaggregation
    from flexflow_tpu.search.driver import optimize_strategy

    cfg = ff.FFConfig(batch_size=SERVE_FRAME_SLOTS, num_devices=N_DEV,
                      search_budget=4, search_timeout_s=45.0,
                      objective="serve", comp_mode="inference",
                      cost_cache_file="")
    m = build_gpt_decode(cfg, **GPT_DECODE_SERVE_KW)
    g, s = optimize_strategy(m.graph, cfg, return_graph=True)
    prop = propose_disaggregation(
        g, s, cfg, base_graph=m.graph if g is not m.graph else None)
    assert prop is not None and not prop.adopted
    assert prop.colocated_step_s < prop.disagg_step_s


def test_lint_disaggregation_codes(chat_search):
    from flexflow_tpu.analysis import errors_only, lint_disaggregation
    from flexflow_tpu.search.disaggregation import propose_disaggregation

    cfg, base, g, s = chat_search
    prop = propose_disaggregation(
        g, s, cfg, base_graph=base if g is not base else None)
    meta = prop.to_meta()
    graph = base  # un-rewritten: the import-path shape
    assert not errors_only(lint_disaggregation(graph, meta, cfg))
    # SHD164: overflowing blocks
    bad = dict(meta, prefill_devices=N_DEV)
    codes = [f.code for f in lint_disaggregation(graph, bad, cfg)]
    assert "SHD164" in codes
    # SHD164: zero-width block / bad chunk
    codes = [f.code for f in lint_disaggregation(
        graph, dict(meta, decode_devices=0), cfg)]
    assert "SHD164" in codes
    codes = [f.code for f in lint_disaggregation(
        graph, dict(meta, chunk=0), cfg)]
    assert "SHD164" in codes
    # SHD165: pool geometry disagreement across the handoff
    codes = [f.code for f in lint_disaggregation(
        graph, dict(meta, page_size=meta["page_size"] * 2), cfg)]
    assert "SHD165" in codes
    # SHD165: malformed SLO classes
    codes = [f.code for f in lint_disaggregation(
        graph, dict(meta, slo_classes=[{"name": "a", "quantile": 2.0}]),
        cfg)]
    assert "SHD165" in codes
    codes = [f.code for f in lint_disaggregation(
        graph, dict(meta, slo_classes=[{"name": "a"}, {"name": "a"}]),
        cfg)]
    assert "SHD165" in codes


@pytest.mark.slow
def test_disaggregation_meta_round_trip(tmp_path):
    """compile(serve_disaggregation=search) persists
    __meta__.disaggregation behind the digest gate; import re-lints it
    (SHD164/165) against the target graph; corrupt pool geometry fails
    the gate with findings."""
    from flexflow_tpu.models import build_gpt_decode
    from flexflow_tpu.search.strategy_io import read_meta

    path = str(tmp_path / "disagg_strategy.json")
    # budget 0: a rewriting search keys its export to the rewritten
    # graph, which deliberately cannot re-import onto a fresh build
    # (STR201) — the round trip is the un-rewritten artifact's story.
    # Half-width chat geometry (still the adopting short-prompt
    # regime) keeps the three compiles in this test cheap.
    kw = dict(CHAT_KW, hidden=1024, num_heads=8, ff_dim=2048)
    cfg = _chat_cfg(serve_disaggregation="search",
                    serve_slo_classes="interactive:2:64,batch:0:0:0.9",
                    export_strategy_file=path, search_budget=0,
                    search_timeout_s=30.0)
    m = build_gpt_decode(cfg, **kw)
    m.compile(loss_type="sparse_categorical_crossentropy", metrics=[],
              comp_mode="inference")
    assert m.disaggregation is not None and m.disaggregation.adopted
    meta = read_meta(path)
    dm = meta.get("disaggregation")
    assert dm and dm["prefill_devices"] + dm["decode_devices"] <= N_DEV
    assert [c["name"] for c in dm["slo_classes"]] == ["interactive",
                                                      "batch"]
    # geometry agrees with the sibling serving block (STR211's rule)
    assert dm["page_size"] == meta["serving"]["page_size"]
    # ... and that sibling is the record of the search whose strategy
    # the file holds — the full-mesh one — not of the last narrow block
    # the disaggregation pass solved after it (the parent of PR 30
    # exported 0.228317 ms / 134,217,728 B against 0.134191 ms /
    # 33,554,432 B)
    from flexflow_tpu.search.driver import search_plan

    full_mesh = search_plan(build_gpt_decode(cfg, **kw).graph, cfg)
    assert meta["serving"] == full_mesh.serving == m.plan.serving
    assert meta.get("kv") == full_mesh.kv

    # clean re-import
    cfg2 = ff.FFConfig(batch_size=32, num_devices=N_DEV,
                       cost_cache_file="", import_strategy_file=path)
    m2 = build_gpt_decode(cfg2, **kw)
    m2.compile(loss_type="sparse_categorical_crossentropy", metrics=[],
               comp_mode="inference")
    assert m2.strategy

    # corrupt geometry -> import gate fails with findings
    from flexflow_tpu.analysis import AnalysisError

    data = json.load(open(path))
    data["__meta__"]["disaggregation"]["pages_per_seq"] = 999
    bad_path = str(tmp_path / "bad.json")
    json.dump(data, open(bad_path, "w"))
    cfg3 = ff.FFConfig(batch_size=32, num_devices=N_DEV,
                       cost_cache_file="",
                       import_strategy_file=bad_path)
    m3 = build_gpt_decode(cfg3, **kw)
    with pytest.raises(AnalysisError):
        m3.compile(loss_type="sparse_categorical_crossentropy",
                   metrics=[], comp_mode="inference")


def test_str211_disagg_meta_lint(tmp_path):
    import sys

    sys.path.insert(0, "tools")
    try:
        from fflint import lint_strategy_file
    finally:
        sys.path.pop(0)

    good = {
        "graph_digest": "d" * 32,
        "serving": {"objective": "serve", "max_seqs": 32,
                    "page_size": 16, "pages_per_seq": 32,
                    "quantile": 0.99, "p99_budget_ms": 0.0},
        "disaggregation": {
            "num_devices": 8, "prefill_devices": 4,
            "decode_devices": 4, "chunk": 32, "prefill_seq_len": 128,
            "max_seqs": 32, "page_size": 16, "pages_per_seq": 32,
            "colocated_step_ms": 0.4, "disagg_step_ms": 0.35,
            "handoff_ms": 0.09, "prefill_tokens_per_frame": 128.0,
            "spans_dcn": False,
            "slo_classes": [{"name": "interactive", "priority": 2,
                             "deadline_frames": 64, "quantile": 0.99}],
        },
    }
    base = {"lm_head": {"dims": [8, 1, 1], "replica": 1, "start": 0}}

    def write(meta):
        p = tmp_path / "strategy.json"
        p.write_text(json.dumps({**base, "__meta__": meta}))
        return str(p)

    assert not [f for f in lint_strategy_file(write(good))
                if f[1] == "STR211"]
    dg = good["disaggregation"]
    corruptions = [
        ("not-an-object", {**good, "disaggregation": [1]}),
        ("zero block", {**good, "disaggregation": {
            **dg, "prefill_devices": 0}}),
        ("overflow", {**good, "disaggregation": {
            **dg, "decode_devices": 7}}),
        ("bool chunk", {**good, "disaggregation": {**dg, "chunk": True}}),
        ("geometry vs serving", {**good, "disaggregation": {
            **dg, "page_size": 64}}),
        ("nan price", {**good, "disaggregation": {
            **dg, "handoff_ms": float("nan")}}),
        ("dup slo", {**good, "disaggregation": {
            **dg, "slo_classes": [{"name": "a"}, {"name": "a"}]}}),
        ("bad quantile", {**good, "disaggregation": {
            **dg, "slo_classes": [{"name": "a", "quantile": 1.5}]}}),
        ("negative deadline", {**good, "disaggregation": {
            **dg, "slo_classes": [{"name": "a",
                                   "deadline_frames": -1}]}}),
    ]
    for label, meta in corruptions:
        found = [f for f in lint_strategy_file(write(meta))
                 if f[1] == "STR211" and f[0] == "error"]
        assert found, f"corruption {label!r} not caught by STR211"


def test_serving_spec_signature_unchanged_by_phase_fields():
    """Bit-identity guard: the phase-split arrival fields must NOT
    enter the cost-row signature — serve cost rows keyed before this
    PR must keep serving."""
    from flexflow_tpu.search.serving import ServingSpec

    a = ServingSpec(max_seqs=16, page_size=16, pages_per_seq=16)
    b = ServingSpec(max_seqs=16, page_size=16, pages_per_seq=16,
                    prompt_tokens_mean=128, decode_tokens_mean=32)
    assert a.signature() == b.signature()
    assert b.prefill_tokens_per_frame() == 16 * (128.0 / 32.0)
