"""The program's span timeline (obs/annotate.py): every closed
``phase_span`` is one entry ``(seq, parent_seq, tag, t0_ns, t1_ns, key)``
of a bounded ring, always on.  CPU only: what is held here is order,
parentage and counts, never a time.
"""

import threading
import time
from collections import Counter

import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu.core.machine import MachineView
from flexflow_tpu.obs import annotate
from flexflow_tpu.obs.annotate import PHASE_PREFIX, phase_span, timeline
from flexflow_tpu.obs.metrics import METRICS
from flexflow_tpu.runtime.decode import (
    ContinuousBatchingExecutor,
    DecodeRequest,
    compiled_decode_step,
)

SERVE_PHASES = ("admit", "compose", "dispatch", "wait", "harvest", "evict")
SMALL_KW = dict(vocab=256, num_layers=2, hidden=64, num_heads=4,
                ff_dim=64, page_size=4, pages_per_seq=8)


@pytest.fixture(autouse=True)
def fresh_registry():
    METRICS.reset()
    yield
    METRICS.reset()


def tagged(spans, name):
    return [s for s in spans if s[2] == PHASE_PREFIX + name]


def test_nesting_names_the_parent_and_hands_the_roots_key_down():
    a, b, c = (PHASE_PREFIX + f"t.level{i}" for i in range(3))
    since = time.perf_counter_ns()
    with phase_span(a, key=41):
        with phase_span(b):
            with phase_span(c):
                pass
            with phase_span(c, key=7):  # a key is a ROOT's to give
                pass
    with phase_span(a):
        pass
    spans = timeline(since)
    # in the order they closed: a child before the span around it
    assert [s[2] for s in spans] == [c, c, b, a, a]
    c1, c2, mid, root, bare = spans
    assert root[1] == 0 and bare[1] == 0
    assert mid[1] == root[0] and c1[1] == c2[1] == mid[0]
    assert [s[5] for s in spans] == [41, 41, 41, 41, None]
    # seq is taken when a span OPENS: process-wide, running
    assert root[0] < mid[0] < c1[0] < c2[0] < bare[0]
    for _, _, _, t0, t1, _ in spans:
        assert since <= t0 <= t1
    assert root[3] <= mid[3] <= c1[3] and c2[4] <= mid[4] <= root[4]
    assert timeline(time.perf_counter_ns()) == []


def test_an_exception_closes_the_span_and_unwinds_the_stack():
    outer, inner = PHASE_PREFIX + "t.raise_outer", PHASE_PREFIX + "t.raise_in"
    since = time.perf_counter_ns()
    with pytest.raises(ValueError):
        with phase_span(outer):
            with phase_span(inner):
                raise ValueError("boom")
    with phase_span(outer):
        pass
    first_in, first_out, second = timeline(since)
    assert first_in[1] == first_out[0]
    assert first_out[1] == 0 and second[1] == 0


def test_two_threads_never_parent_each_others_spans():
    """Each thread nests under its own open spans only, whatever the
    other has open at that moment."""
    tags = {name: PHASE_PREFIX + f"t.thread_{name}" for name in "ab"}
    inner = PHASE_PREFIX + "t.thread_inner"
    both_open = threading.Barrier(2, timeout=20)
    since = time.perf_counter_ns()

    def work(name):
        for k in range(50):
            with phase_span(tags[name], key=ord(name) * 1000 + k):
                if k == 0:
                    both_open.wait()  # the two roots ARE open together
                with phase_span(inner):
                    pass

    threads = [threading.Thread(target=work, args=(n,)) for n in "ab"]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in threads)
    spans = timeline(since)
    by_seq = {s[0]: s for s in spans}
    assert len(by_seq) == len(spans) == 200  # no seq handed out twice
    roots = [s for s in spans if s[2] in tags.values()]
    assert len(roots) == 100 and all(s[1] == 0 for s in roots)
    for s in tagged(spans, "t.thread_inner"):
        parent = by_seq[s[1]]
        assert parent[2] in tags.values() and parent[5] == s[5]
        assert parent[3] <= s[3] and s[4] <= parent[4]
    keys = Counter(s[5] // 1000 for s in tagged(spans, "t.thread_inner"))
    assert keys == {ord("a"): 50, ord("b"): 50}


def test_the_ring_is_bounded_and_drops_its_oldest():
    n = annotate.RING_SPANS
    assert n >= 65536 and annotate._RING.maxlen == n
    tag = PHASE_PREFIX + "t.bounded"
    with phase_span(PHASE_PREFIX + "t.first_to_go"):
        pass
    first = timeline()[-1]
    for _ in range(n + 9):
        with phase_span(tag):
            pass
    spans = timeline()
    assert len(spans) == n
    assert first not in spans
    seqs = [s[0] for s in spans]
    assert seqs == list(range(seqs[0], seqs[0] + n))  # the newest n, in order
    assert all(s[2] == tag for s in spans)


def test_the_exhausted_fetch_is_in_the_ring_and_not_in_the_histogram():
    tag = PHASE_PREFIX + "t.timeline_fetch"
    since = time.perf_counter_ns()
    assert list(annotate.spanned(tag, iter("ab"))) == ["a", "b"]
    assert METRICS.histogram("t.timeline_fetch_s").count == 2
    assert len(tagged(timeline(since), "t.timeline_fetch")) == 3


@pytest.fixture(scope="module")
def small_model():
    cfg = ff.FFConfig(batch_size=4, num_devices=1, cost_cache_file="",
                      compute_dtype="bfloat16")
    from flexflow_tpu.models import build_gpt_decode

    m = build_gpt_decode(cfg, **SMALL_KW)
    strategy = {n.guid: (n.op.fixed_machine_view()
                         or MachineView.trivial(n.op.output_shapes[0].ndim))
                for n in m.graph.topo_order()}
    m.compile(loss_type="sparse_categorical_crossentropy", metrics=[],
              comp_mode="inference", strategy=strategy)
    return m


def test_a_served_run_is_one_tree_a_frame_with_the_calls_inside(small_model):
    """Over the compiled tiny model, one frame in flight: every harvested
    frame is one ``decode_frame`` root keyed by its number with the six
    phases as children in order; the jitted programs are called under
    ``serve.dispatch`` and under ``prefill_chunk`` under ``serve.admit``;
    each program's FIRST call is ``setup.first_call.*`` and in neither
    ``call.*_s`` histogram."""
    chunk = 8
    since = time.perf_counter_ns()
    step = compiled_decode_step(small_model, prefill_chunk=chunk)
    ex = ContinuousBatchingExecutor(
        step, max_seqs=4, page_size=SMALL_KW["page_size"],
        pages_per_seq=SMALL_KW["pages_per_seq"], prefill_fn=step.prefill,
        prefill_chunk=chunk)
    rng = np.random.default_rng(5)
    out = ex.run([DecodeRequest(rid=f"r{i}", max_new_tokens=4,
                                prompt=list(map(int, rng.integers(
                                    1, 255, size=length))))
                  for i, length in enumerate((3, 9, 12, 17, 23, 5))],
                 max_frames=200)
    assert len(out) == 6
    spans = timeline(since)
    by_seq = {s[0]: s for s in spans}

    def parent_tag(span):
        return by_seq[span[1]][2]

    roots = tagged(spans, "decode_frame")
    assert [r[5] for r in roots] == list(range(ex.frame)) and ex.frame > 4
    assert all(r[1] == 0 for r in roots)
    for root in roots:
        children = sorted((s for s in spans if s[1] == root[0]),
                          key=lambda s: s[3])
        assert [c[2] for c in children] == [
            PHASE_PREFIX + "serve." + p for p in SERVE_PHASES]
        assert all(c[5] == root[5] for c in children)
    # the frame's program: every call under the step's dispatch span
    calls = tagged(spans, "call.decode_frame")
    first = tagged(spans, "setup.first_call.decode_frame")
    assert len(first) == 1 and first[0][0] < calls[0][0]
    assert len(calls) + len(first) == ex.frame  # dispatched = harvested
    for s in calls + first:
        assert parent_tag(s) == PHASE_PREFIX + "serve.dispatch"
    assert METRICS.histogram("call.decode_frame_s").count == len(calls)
    assert METRICS.histogram("setup.first_call.decode_frame_s").count == 1
    # the chunk's: under its chunk span, which is under admit
    chunks = tagged(spans, "prefill_chunk")
    chunk_calls = tagged(spans, "call.prefill_chunk")
    chunk_first = tagged(spans, "setup.first_call.prefill_chunk")
    # one call a prompt, however many chunks it runs
    assert len(chunks) == ex.prefill_calls == 6
    assert ex.prefill_chunks == 1 + 1 + 2 + 2 + 3 + 1
    assert len(chunk_first) == 1 and len(chunk_calls) == len(chunks) - 1
    assert chunk_first[0][1] == chunks[0][0]
    for s in chunk_calls + chunk_first:
        assert parent_tag(s) == annotate.PREFILL_PHASE
        assert by_seq[s[1]][5] == s[5] is not None  # the frame's number
    for s in chunks:
        assert parent_tag(s) == PHASE_PREFIX + "serve.admit"
    assert METRICS.histogram("call.prefill_chunk_s").count == len(chunk_calls)
    assert METRICS.histogram("serve.prefill_chunk_s").count == len(chunks)
    # a second step function over the model has called nothing yet
    again = compiled_decode_step(small_model, prefill_chunk=chunk)
    since = time.perf_counter_ns()
    again.prefill(np.zeros((1, chunk), np.int32),
                  np.arange(chunk, dtype=np.int32)[None, :],
                  np.arange(SMALL_KW["pages_per_seq"], dtype=np.int32)[None])
    assert [s[2] for s in timeline(since)] == [
        PHASE_PREFIX + "setup.first_call.prefill_chunk"]


def test_fit_keys_every_step_span_by_the_models_running_step():
    cfg = ff.FFConfig(batch_size=8, num_devices=1, epochs=1,
                      compute_dtype="float32", cost_cache_file="")
    m = ff.FFModel(cfg)
    x = m.create_tensor([8, 16], name="x")
    m.dense(m.dense(x, 32, activation="relu"), 4)
    m.compile(loss_type="mean_squared_error", metrics=[])
    rng = np.random.default_rng(0)
    data = dict(x=rng.normal(size=(24, 16)).astype(np.float32),
                y=rng.normal(size=(24, 4)).astype(np.float32))
    since = time.perf_counter_ns()
    m.fit(**data, epochs=2, verbose=False, shuffle=False)
    steps = tagged(timeline(since), "step")
    assert [s[5] for s in steps] == list(range(1, 7))
    first = tagged(timeline(since), "setup.first_call.train_step")
    assert len(first) == 1 and first[0][1] == steps[0][0] and first[0][5] == 1
