"""One frame in flight (runtime/decode.py ``ContinuousBatchingExecutor.step``):
a step function whose output carries the frame's tokens is run AHEAD —
frame n+1 goes out, fed frame n's tokens where they are, before frame n
is harvested — and must hand every request exactly the stream the
synchronous loop (a plain callable returning host logits) hands it.

The model behind both is ``PagedToy``: a NumPy paged pool in which a
row's next token hashes EVERY token cached for it through its page
table, so a wrong page, position, order of writes or fed token shows in
the stream.  It executes a frame when it is dispatched, as the device
executes programs in the order they were dispatched.  CPU only: what is
held here is streams and counts, never a time.
"""

import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu.obs.metrics import METRICS
from flexflow_tpu.runtime import decode
from flexflow_tpu.runtime.decode import (
    ContinuousBatchingExecutor,
    DecodeRequest,
    FrameOutput,
    SLOClass,
    compiled_decode_step,
)

VOCAB, PAGE, PPS = 97, 4, 8
COUNTERS = ("decode.frames", "decode.frames_ahead", "decode.rows_dropped",
            "decode.tokens_generated")


class PagedToy:
    """The step_fn contract over a NumPy pool.  ``on_device=False``: a
    plain callable, host ids in, host logits [B, 1, V] out.
    ``on_device=True``: the output carries the tokens
    (``FrameOutput``) and an id of -1 takes the token the call before
    chose for that row.  ``alter=(call, row)`` plants a fault: the host
    is shown another token than the one the next frame is fed."""

    def __init__(self, num_pages, on_device, alter=None):
        self.pool = np.zeros((num_pages, PAGE), np.int64)
        self.on_device = on_device
        self.alter = alter
        self.last = None
        self.calls = 0
        self.seen_ids = []

    def _write(self, row, pos, token):
        self.pool[row[pos // PAGE], pos % PAGE] = token

    def prefill(self, ids, positions, table):
        for token, pos in zip(ids[0], positions[0]):
            self._write(table[0], int(pos), int(token))

    def copy_page(self, src, dst):
        self.pool[dst] = self.pool[src]

    def __call__(self, ids, table, lens):
        ids = np.asarray(ids)[:, 0].astype(np.int64)
        self.seen_ids.append(ids.copy())
        if (ids < 0).any():
            assert self.on_device, "a plain callable was handed a -1"
            ids = np.where(ids < 0, self.last, ids)
        tokens = np.zeros(len(ids), np.int64)
        for i, pos in enumerate(np.asarray(lens)):
            self._write(table[i], int(pos), ids[i])
            ctx = [self.pool[table[i][j // PAGE], j % PAGE]
                   for j in range(int(pos) + 1)]
            tokens[i] = (7 * sum((j + 1) * int(t)
                                 for j, t in enumerate(ctx)) + 5) % VOCAB
        self.last = tokens
        self.calls += 1
        logits = np.zeros((len(ids), 1, VOCAB), np.float32)
        logits[np.arange(len(ids)), 0, tokens] = 1.0
        if not self.on_device:
            return logits
        shown = tokens.astype(np.int32)
        if self.alter is not None and self.alter[0] == self.calls - 1:
            shown[self.alter[1]] = (shown[self.alter[1]] + 1) % VOCAB
        return FrameOutput(logits, shown)


def serve(on_device, drive, *, slots=4, lane=True, alter=None, **kw):
    """One executor over a fresh toy; ``drive(ex)`` submits and steps.
    Returns (executor, toy, what the counters gained)."""
    pages = slots * PPS + 1
    toy = PagedToy(pages, on_device, alter)
    if kw.get("prefix_sharing"):
        kw["copy_page_fn"] = toy.copy_page
    if lane:
        kw.update(prefill_fn=toy.prefill, prefill_chunk=4)
    ex = ContinuousBatchingExecutor(
        toy, max_seqs=slots, page_size=PAGE, pages_per_seq=PPS,
        num_pages=pages if kw.get("prefix_sharing") else 0, **kw)
    before = {c: METRICS.counter(c).value for c in COUNTERS}
    drive(ex)
    assert not ex.has_work()  # run() left nothing in flight
    gained = {c.split(".")[1]: METRICS.counter(c).value - before[c]
              for c in COUNTERS}
    return ex, toy, gained


def both(drive, **kw):
    """The same drive through the two loops; asserts what must agree
    and returns (ahead, synchronous) as ``serve`` gives them."""
    ahead = serve(True, drive, **kw)
    sync = serve(False, drive, **kw)
    agree(ahead, sync)
    assert sync[2]["frames_ahead"] == 0 == sync[2]["rows_dropped"]
    return ahead, sync


def agree(ahead, sync):
    """THE comparison: every request got the same tokens from both
    loops, none is missing, and the tokens counted as generated are the
    tokens handed out."""
    (ex_a, _, gained_a), (ex_s, _, gained_s) = ahead, sync
    assert ex_a.finished == ex_s.finished
    assert ex_a.expired == ex_s.expired
    handed = sum(len(t) for t in ex_a.finished.values())
    assert gained_a["tokens_generated"] == gained_s["tokens_generated"]
    if not ex_a.total_preempted:  # a preempted sequence generates twice
        assert gained_a["tokens_generated"] == handed


def requests(n, seed=0, prompt=(3, 14), new=(1, 12), **kw):
    rng = np.random.default_rng(seed)
    return [DecodeRequest(
        rid=f"r{i}", prompt=rng.integers(1, VOCAB, rng.integers(*prompt)).tolist(),
        max_new_tokens=int(rng.integers(*new)), **kw) for i in range(n)]


def run_all(reqs):
    return lambda ex: ex.run(reqs, max_frames=2000)


# ---- the same streams ----------------------------------------------------

def test_sixteen_full_slots_with_staggered_ends():
    reqs = requests(72, prompt=(2, 12), new=(1, 20))
    (ex, toy, gained), (_, toy_s, gained_s) = both(run_all(reqs), slots=16)
    assert len(ex.finished) == 72
    assert all(len(ex.finished[r.rid]) == r.max_new_tokens for r in reqs)
    # the mechanism engages: nearly every frame went out while the one
    # before it was unharvested, and a continuing row's id stayed away
    assert gained["frames_ahead"] / gained["frames"] >= 0.9
    assert any((ids < 0).any() for ids in toy.seen_ids)
    assert not any((ids < 0).any() for ids in toy_s.seen_ids)
    # an end by max_new_tokens is known a frame ahead: no row is sent
    # in vain, no frame is added, no slot waits a frame for its next
    # request (the two loops fill their frames alike)
    assert gained["rows_dropped"] == 0
    assert toy.calls == gained["frames"] == ex.frame
    assert gained["frames"] <= gained_s["frames"]
    assert len(ex.frame_seconds) == ex.frame


def test_eos_in_mid_stream_drops_the_row_in_flight():
    reqs = requests(12, seed=1, new=(8, 16))
    plain = serve(False, run_all(reqs))[0].finished
    # each request's EOS is a token it emits in mid-stream
    for r in reqs:
        r.eos_id = plain[r.rid][len(plain[r.rid]) // 2]
    (ex, _, gained), _ = both(run_all(reqs))
    cut = 0
    for r in reqs:
        got = ex.finished[r.rid]
        first = plain[r.rid].index(r.eos_id)
        assert got == plain[r.rid][:first + 1]  # the stream ends AT it
        cut += len(got) < r.max_new_tokens
    # every stream cut short had one more row in flight
    assert gained["rows_dropped"] == cut > 0


@pytest.mark.parametrize("lane", [True, False], ids=["chunk_lane",
                                                     "via_decode"])
def test_prefix_sharing_with_a_mid_page_divergence(lane):
    rng = np.random.default_rng(2)
    shared = rng.integers(1, VOCAB, 10).tolist()  # 2.5 pages
    reqs = [DecodeRequest(rid=f"r{i}", max_new_tokens=3 + i % 5,
                          prompt=shared + rng.integers(1, VOCAB, 1 + i).tolist())
            for i in range(10)]
    (ex, _, _), _ = both(run_all(reqs), lane=lane, prefix_sharing=True)
    assert ex.prefix_hits > 0 and ex.cow_copies > 0
    # ... and the streams are those of serving without sharing
    assert ex.finished == serve(False, run_all(reqs), lane=lane)[0].finished
    assert ex.allocator.pages_in_use == 1  # the scratch page alone


def test_slo_preemption_with_a_frame_in_flight():
    classes = [SLOClass("batch", priority=0), SLOClass("live", priority=5)]
    low = requests(6, seed=3, new=(10, 16), slo="batch")
    high = [DecodeRequest(rid=f"h{i}", prompt=[9, 8, 7 + i],
                          max_new_tokens=4, slo="live") for i in range(3)]

    def drive(ex):
        ex.submit(low)
        for _ in range(4):
            ex.step()
        ex.submit(high)  # every slot is busy and a frame is in flight
        ex.run(max_frames=2000)

    (ex, _, gained), (ex_s, _, _) = both(drive, slo_classes=classes)
    assert ex.total_preempted > 0 and ex_s.total_preempted > 0
    assert gained["rows_dropped"] > 0  # the victims' rows in flight
    assert len(ex.finished) == 9
    # ... and what a preempted sequence got is what it gets unpreempted
    alone = serve(False, run_all(low + high), slots=16)[0].finished
    assert ex.finished == alone


def test_deadline_expiry_with_a_frame_in_flight():
    busy = requests(2, seed=4, new=(14, 15))
    late = DecodeRequest(rid="late", prompt=[1, 2, 3], max_new_tokens=2,
                         deadline_frames=3)
    patient = DecodeRequest(rid="patient", prompt=[4, 5, 6],
                            max_new_tokens=2)
    (ex, _, _), _ = both(run_all(busy + [late, patient]), slots=2)
    assert set(ex.expired) == {"late"} and "patient" in ex.finished


def test_without_a_prefill_lane_host_ids_override_device_tokens():
    reqs = requests(10, seed=5, prompt=(2, 9), new=(2, 9))
    (ex, toy, gained), _ = both(run_all(reqs), lane=False)
    assert ex.prefill_chunks == 0
    # a prompt token is the host's to give even while the row's frame
    # before is in flight; only past the prompt is the id the device's
    mixed = [ids for ids in toy.seen_ids[1:] if (ids > 0).any()
             and (ids < 0).any()]
    assert mixed
    assert gained["frames_ahead"] / gained["frames"] >= 0.9


@pytest.mark.parametrize("n", [1, 2, 7])
@pytest.mark.parametrize("lane", [True, False], ids=["chunk_lane",
                                                     "via_decode"])
def test_a_lone_request_of_n_tokens_dispatches_exactly_n_frames(n, lane):
    req = DecodeRequest(rid="lone", prompt=[5, 6, 7, 8, 9],
                        max_new_tokens=n)
    (ex, toy, _), (_, toy_s, _) = both(run_all([req]), lane=lane)
    frames = n if lane else n + 4  # via decode: one a prompt token too
    assert toy.calls == toy_s.calls == ex.frame == frames
    assert len(ex.finished["lone"]) == n


# ---- a planted fault is caught -------------------------------------------

def test_a_device_token_altered_for_one_row_is_caught():
    reqs = requests(8, seed=6, new=(6, 12))
    sync = serve(False, run_all(reqs))
    agree(serve(True, run_all(reqs)), sync)  # sound: they agree
    with pytest.raises(AssertionError):
        agree(serve(True, run_all(reqs), alter=(5, 2)), sync)


def test_a_dropped_rows_token_kept_is_caught(monkeypatch):
    reqs = requests(12, seed=1, new=(8, 16))
    plain = serve(False, run_all(reqs))
    for r in reqs:
        r.eos_id = plain[0].finished[r.rid][3]
    sync = serve(False, run_all(reqs))
    agree(serve(True, run_all(reqs)), sync)
    harvest = ContinuousBatchingExecutor._harvest

    def keeps(self, out, rows, now, tr):
        for _, live in rows:
            live.closed = False
        return harvest(self, out, rows, now, tr)

    monkeypatch.setattr(ContinuousBatchingExecutor, "_harvest", keeps)
    with pytest.raises(AssertionError):
        agree(serve(True, run_all(reqs)), sync)


# ---- the one rule of an output -------------------------------------------

@pytest.mark.parametrize("kind", ["logits", "ids_b1", "ids_b", "carried"])
def test_an_output_is_its_tokens_or_its_logits(kind):
    tokens = np.array([3, 0, 5, 6], np.int32)
    logits = np.zeros((4, 1, 7), np.float32)
    logits[np.arange(4), 0, tokens] = 2.0
    logits[2, 0, 5:] = 2.0  # a tie: the first index wins
    out = {"logits": logits, "ids_b1": tokens[:, None], "ids_b": tokens,
           "carried": FrameOutput(logits, tokens)}[kind]
    got = decode._host_tokens(out)
    assert got.dtype == np.int32 and got.tolist() == [3, 0, 5, 6]
    assert decode._in_flight(out) == (kind == "carried")


def test_an_output_someone_pulled_is_harvested_at_once():
    """A tap that reads every frame's logits (the benchmark's probe):
    that frame has run, nothing is left to overlap — the loop is the
    synchronous one, ids from the host, the token still the device's."""
    toy = PagedToy(4 * PPS + 1, on_device=True)

    def tapped(ids, table, lens):
        out = toy(ids, table, lens)
        assert np.asarray(out, np.float32).shape == (4, 1, VOCAB)
        assert out.pulled and out[0, 0].shape == (VOCAB,)
        return out

    ex = ContinuousBatchingExecutor(
        tapped, max_seqs=4, page_size=PAGE, pages_per_seq=PPS,
        prefill_fn=toy.prefill, prefill_chunk=4)
    ahead0 = METRICS.counter("decode.frames_ahead").value
    reqs = requests(6, seed=7)
    got = ex.run(reqs, max_frames=500)
    assert METRICS.counter("decode.frames_ahead").value == ahead0
    assert not any((ids < 0).any() for ids in toy.seen_ids)
    assert got == serve(False, run_all(reqs))[0].finished


# ---- the compiled decode model -------------------------------------------

MODEL_KW = dict(vocab=97, num_layers=2, hidden=32, num_heads=4, ff_dim=64,
                page_size=8, pages_per_seq=4)


def compiled_model(**ffconfig):
    from flexflow_tpu.models import build_gpt_decode

    ffconfig.setdefault("num_devices", 1)
    cfg = ff.FFConfig(batch_size=4, cost_cache_file="",
                      compute_dtype="bfloat16", seed=3, **ffconfig)
    m = build_gpt_decode(cfg, **MODEL_KW)
    m.compile(loss_type="sparse_categorical_crossentropy", metrics=[],
              comp_mode="inference")
    return m


@pytest.mark.parametrize("variant", ["fp32_pool", "int8_pool",
                                     "prefix_sharing", "four_devices"])
def test_compiled_step_serves_the_synchronous_loops_streams(variant):
    """``compiled_decode_step``'s frame chooses the token on the device
    and takes a continuing row's id from its state: the streams are
    those of pulling its logits every frame and choosing on the host —
    fp32 and int8 pools, prefix sharing, the XLA path on a mesh."""
    import jax
    import jax.numpy as jnp
    from flexflow_tpu.core.machine import MachineSpec

    extra = {}
    if variant == "int8_pool":
        extra = dict(objective="serve", kv_precision="int8")
    if variant == "four_devices":
        extra = dict(num_devices=4, search_budget=2, search_timeout_s=20.0,
                     machine_spec=MachineSpec.host_cpu(4))
    m = compiled_model(**extra)
    step = compiled_decode_step(m, prefill_chunk=4)
    assert step.attention_path == ("xla" if variant == "four_devices"
                                   else "pallas")
    rng = np.random.default_rng(0)
    shared = rng.integers(1, 97, size=11).tolist()
    reqs = [DecodeRequest(rid=f"r{i}", max_new_tokens=2 + i,
                          prompt=shared + rng.integers(1, 97, 1 + i).tolist())
            for i in range(7)]

    def through(step_fn):
        m.state = jax.tree.map(jnp.zeros_like, m.state)
        sharing = (dict(prefix_sharing=True, copy_page_fn=step.copy_page)
                   if variant == "prefix_sharing" else {})
        ex = ContinuousBatchingExecutor(
            step_fn, max_seqs=4, page_size=8, pages_per_seq=4,
            prefill_fn=step.prefill, prefill_chunk=4, **sharing)
        before = {c: METRICS.counter(c).value for c in COUNTERS}
        out = ex.run(reqs, max_frames=300)
        return out, {c.split(".")[1]: METRICS.counter(c).value - before[c]
                     for c in COUNTERS}

    ahead, gained = through(step)
    sync, gained_s = through(lambda *frame: np.asarray(step(*frame)))
    assert ahead == sync and len(ahead) == 7
    assert all(len(ahead[r.rid]) == r.max_new_tokens for r in reqs)
    assert gained["frames_ahead"] / gained["frames"] >= 0.9
    assert gained_s["frames_ahead"] == 0
    assert gained["rows_dropped"] == 0


def test_the_frame_program_takes_a_continuing_rows_token_from_its_state():
    """``step.frame_fn`` over ``step.state["state"]`` — what the
    benchmark lowers: an id of -1 reads the token the call before chose,
    an id from the host overrides it, and the tokens are the logits'
    argmax."""
    import jax.numpy as jnp

    m = compiled_model()
    step = compiled_decode_step(m)
    table = np.arange(16, dtype=np.int32).reshape(4, 4)
    ids = np.array([[5], [6], [7], [8]], np.int32)
    first = step(ids, table, np.zeros(4, np.int32))
    chosen = np.asarray(first.tokens)
    assert chosen.tolist() == np.asarray(first)[:, 0].argmax(-1).tolist()
    assert np.asarray(step.state["state"]["last_tokens"]).tolist() \
        == chosen.tolist()
    assert "last_tokens" not in m.state  # the step's own, not the model's
    mixed = np.array([[-1], [9], [-1], [-1]], np.int32)
    lens = np.ones(4, np.int32)
    pool = {k: np.asarray(v) for k, v in m.state.items()}
    got = np.asarray(step(mixed, table, lens))
    m.state = {k: jnp.asarray(v) for k, v in pool.items()}
    told = np.where(mixed < 0, chosen[:, None], mixed).astype(np.int32)
    want = np.asarray(step(told, table, lens))
    np.testing.assert_array_equal(got, want)
