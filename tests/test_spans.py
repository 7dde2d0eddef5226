"""The program's own spans and counters (obs/annotate.py ``phase_span``
over ``obs/metrics.py`` ``METRICS``): always on, on the profiler's
clock, placed in the decode frame, the fit loop and set-up.  CPU only:
what is held here is counts and containment, never a time.
"""

import glob
import os

import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu.obs import annotate
from flexflow_tpu.obs.annotate import PHASE_PREFIX, hist_name, phase_span
from flexflow_tpu.obs.events import BUS
from flexflow_tpu.obs.metrics import METRICS
from flexflow_tpu.runtime.decode import (
    ContinuousBatchingExecutor,
    DecodeRequest,
    FrameOutput,
)

SERVE_PHASES = ("admit", "compose", "dispatch", "wait", "harvest", "evict")


@pytest.fixture(autouse=True)
def fresh_registry():
    METRICS.reset()
    yield
    METRICS.reset()


def hist(name):
    return METRICS.histogram(name)


def synthetic_step(vocab=97):
    def step(ids, table, lens):
        ids, lens = np.asarray(ids), np.asarray(lens)
        nxt = (ids[:, 0] * 7 + lens * 13 + 5) % vocab
        logits = np.zeros((ids.shape[0], 1, vocab), np.float32)
        logits[np.arange(ids.shape[0]), 0, nxt] = 1.0
        return logits

    return step


def carrying_step(vocab=97):
    """``synthetic_step`` with the token chosen where the logits are:
    the output carries it, and an id of -1 is the token of the call
    before — the executor then keeps one frame in flight."""
    plain, last = synthetic_step(vocab), {}

    def step(ids, table, lens):
        ids = np.asarray(ids)
        if (ids < 0).any():
            ids = np.where(ids < 0, last["tokens"], ids)
        logits = plain(ids, table, lens)
        last["tokens"] = logits.argmax(-1).astype(np.int32)  # [B, 1]
        return FrameOutput(logits, last["tokens"][:, 0])

    return step


def run_executor(n_requests=5, prompt_len=9, new_tokens=3, step=None, **kw):
    """The tiny size: 2 slots, 4-token pages, a 4-token chunk lane."""
    written = []
    ex = ContinuousBatchingExecutor(
        step or synthetic_step(), max_seqs=2, page_size=4, pages_per_seq=4,
        prefill_fn=lambda ids, pos, table: written.append(ids.shape),
        prefill_chunk=4, **kw)
    out = ex.run([DecodeRequest(rid=f"r{i}",
                                prompt=list(range(1, prompt_len + 1)),
                                max_new_tokens=new_tokens)
                  for i in range(n_requests)], max_frames=200)
    assert len(out) == n_requests
    return ex


# ---- the primitive ------------------------------------------------------

@pytest.mark.parametrize("tag,name", [
    (PHASE_PREFIX + "serve.admit", "serve.admit_s"),
    (PHASE_PREFIX + "setup.first_call.train_step",
     "setup.first_call.train_step_s"),
    (annotate.DECODE_PHASE, "serve.step_s"),
    (annotate.STEP_PHASE, "fit.dispatch_s"),
    (annotate.PREFILL_PHASE, "serve.prefill_chunk_s"),
])
def test_a_tag_names_its_histogram(tag, name):
    assert hist_name(tag) == name
    with phase_span(tag):
        pass
    assert hist(name).count == 1 and hist(name).sum >= 0.0


def test_arming_is_gone():
    for gone in ("arm", "disarm", "armed", "_ARMED"):
        assert not hasattr(annotate, gone), gone


def test_spans_nest_and_an_exception_still_closes_them():
    outer, inner = PHASE_PREFIX + "t.outer", PHASE_PREFIX + "t.inner"
    with pytest.raises(ValueError):
        with phase_span(outer):
            with phase_span(inner):
                raise ValueError("boom")
    assert hist("t.outer_s").count == hist("t.inner_s").count == 1
    assert hist("t.inner_s").sum <= hist("t.outer_s").sum


def test_spanned_times_every_fetch_but_the_exhausted_one():
    tag = PHASE_PREFIX + "t.fetch"
    assert list(annotate.spanned(tag, iter("abc"))) == ["a", "b", "c"]
    assert hist("t.fetch_s").count == 3
    assert list(annotate.spanned(tag, [])) == []
    assert hist("t.fetch_s").count == 3


def test_a_span_lands_in_the_registry_and_in_a_profiler_capture(tmp_path):
    """No arm(): whoever starts a profiler session sees the span under
    its tag, on the profiler's clock."""
    import jax
    from jax.profiler import ProfileData

    tag = PHASE_PREFIX + "t.captured"
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            with phase_span(tag):
                jax.block_until_ready(jax.numpy.ones(8) + 1)
    finally:
        jax.profiler.stop_trace()
    assert hist("t.captured_s").count == 3
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    events = [ev for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events
              if ev.name == tag]
    assert len(events) == 3
    assert all(ev.duration_ns > 0 for ev in events)
    # outside a session the same span records into the registry alone
    with phase_span(tag):
        pass
    assert hist("t.captured_s").count == 4


# ---- the decode frame ---------------------------------------------------

def test_every_serve_phase_has_one_sample_a_frame_inside_the_frame_span():
    ex = run_executor()
    frames = ex.frame
    assert frames > 0
    assert METRICS.counter("decode.frames").value == frames
    assert len(ex.frame_seconds) == frames
    assert hist("serve.step_s").count == frames
    assert hist("decode.frame_s").count == frames
    children = 0.0
    for phase in SERVE_PHASES:
        h = hist(f"serve.{phase}_s")
        assert h.count == frames, phase
        children += h.sum
    assert children <= hist("serve.step_s").sum
    # frame_seconds keeps its meaning: dispatch + wait, nothing else
    both = hist("serve.dispatch_s").sum + hist("serve.wait_s").sum
    assert both <= sum(ex.frame_seconds) <= hist("serve.step_s").sum
    assert hist("decode.frame_s").sum == pytest.approx(
        sum(ex.frame_seconds))
    # the chunk lane's dispatches are spans of their own, inside admit:
    # one a prompt, whose chunks it runs in one call
    assert hist("serve.prefill_chunk_s").count == ex.prefill_calls \
        == ex.prefill_chunks // 2 > 0
    assert hist("serve.prefill_chunk_s").sum <= hist("serve.admit_s").sum


def test_with_a_frame_in_flight_every_phase_still_has_one_sample_a_frame():
    """The output carries its tokens, so frame n+1 is dispatched before
    frame n is harvested: every ``step`` still harvests ONE frame and
    yields one sample of each phase under the frame's span — a step
    that finds nothing in flight dispatches two frames inside its one
    dispatch span, the last steps of a run none."""
    ex = run_executor(n_requests=6, new_tokens=5, step=carrying_step())
    assert ex.finished == run_executor(n_requests=6, new_tokens=5).finished
    frames = ex.frame
    assert METRICS.counter("decode.frames").value == 2 * frames  # two runs
    ahead = METRICS.counter("decode.frames_ahead").value
    assert 0.8 * frames <= ahead < frames
    assert len(ex.frame_seconds) == frames
    assert hist("serve.step_s").count == hist("decode.frame_s").count \
        == 2 * frames
    for phase in SERVE_PHASES:
        assert hist(f"serve.{phase}_s").count == 2 * frames, phase
    # frame_seconds: the time a frame had the pipeline to itself — the
    # step's period with a frame in flight, so the sum is the run's time
    assert hist("serve.wait_s").sum <= sum(ex.frame_seconds)
    assert all(dt > 0 for dt in ex.frame_seconds)


def test_counters_agree_with_the_executors_summary():
    ex = run_executor(n_requests=5, prompt_len=9, new_tokens=3)
    s = ex.summary()
    c = {k: v for k, v in METRICS.snapshot()["counters"].items()
         if k.startswith("decode.")}
    assert c["decode.frames"] == s["frames"]
    assert c["decode.prefill_chunks"] == s["prefill_chunks"] == 5 * 2
    assert c["decode.prefill_tokens"] == s["prefill_tokens"] == 5 * 8
    assert c["decode.prompt_tokens"] == 5 * 9
    assert c["decode.tokens_generated"] == 5 * 3 == sum(
        len(t) for t in ex.finished.values())
    assert c["decode.slot_frames"] == s["frames"] * 2
    assert 0 < c["decode.active_slot_frames"] <= c["decode.slot_frames"]
    assert c["decode.prefix_hit_tokens"] == 0


def test_prefix_hits_are_counted_as_the_summary_counts_them():
    ex = run_executor(n_requests=4, prompt_len=13, new_tokens=2,
                      prefix_sharing=True)
    s = ex.summary()
    assert s["prefix_tokens"] > 0
    assert (METRICS.counter("decode.prefix_hit_tokens").value
            == s["prefix_tokens"])
    assert (METRICS.counter("decode.prefill_tokens").value
            == s["prefill_tokens"])


def test_request_histograms_fill_with_the_bus_off():
    assert not BUS.enabled
    ex = run_executor(n_requests=5, new_tokens=3)
    assert ex.request_records == []  # records and events stay bus-gated
    for name in ("queue_s", "ttft_s", "tpot_s", "e2e_s", "prefill_s",
                 "first_frame_s"):
        assert hist(f"decode.{name}").count == 5, name
    assert hist("decode.ttft_s").min > 0
    assert hist("decode.ttft_s").max <= hist("decode.e2e_s").max
    # and per SLO class, in the registry's name|key=value form
    assert hist("decode.ttft_s|slo=standard").count == 5


# ---- the fit loop and set-up --------------------------------------------

@pytest.fixture(scope="module")
def tiny_mlp():
    cfg = ff.FFConfig(batch_size=8, num_devices=1, epochs=2,
                      compute_dtype="float32", cost_cache_file="")
    m = ff.FFModel(cfg)
    x = m.create_tensor([8, 16], name="x")
    h = m.dense(x, 32, activation="relu")
    m.dense(h, 4)
    return m


def test_compile_splits_set_up_into_its_spans(tiny_mlp):
    tiny_mlp.compile(loss_type="mean_squared_error", metrics=[])
    for phase in ("native_build", "search", "lower", "init_params"):
        assert hist(f"setup.{phase}_s").count == 1, phase
    # init_params jits the initialisers: the listeners compile() installed
    # counted it, by program
    counters = METRICS.snapshot()["counters"]
    assert counters["jax.compile_requests"] >= 1
    assert any(k.startswith("jax.compile_requests|fun=") for k in counters)
    assert counters["jax.cache_misses"] == 0  # no cache under test: a total that reads 0


def test_fit_counts_its_steps_and_times_every_fetch_and_dispatch(tiny_mlp):
    if tiny_mlp.compiled is None:
        tiny_mlp.compile(loss_type="mean_squared_error", metrics=[])
    tiny_mlp.compiled._train_step_fn = None  # a program never called yet
    rng = np.random.default_rng(0)
    x = rng.normal(size=(24, 16)).astype(np.float32)
    y = rng.normal(size=(24, 4)).astype(np.float32)
    tiny_mlp.fit(x=x, y=y, epochs=2, verbose=False, shuffle=False)
    steps = 2 * 3
    assert METRICS.counter("fit.steps").value == steps
    assert hist("fit.data_s").count == steps
    assert hist("fit.dispatch_s").count == steps
    assert hist("fit.epoch_sync_s").count == 2
    assert hist("setup.first_call.train_step_s").count == 1
    # the first call holds trace + lower + compile; later ones dispatch
    assert (hist("setup.first_call.train_step_s").sum
            <= hist("fit.dispatch_s").max)


def test_a_fresh_jit_is_a_compile_request_and_its_second_call_is_not():
    import jax

    from flexflow_tpu.runtime.compile_cache import watch_jax_compiles

    watch_jax_compiles()
    watch_jax_compiles()  # idempotent: one listener, not two
    requests = METRICS.counter("jax.compile_requests")

    def fresh_program(a):
        return a * 3 + 1

    f = jax.jit(fresh_program)
    x = jax.numpy.arange(4.0)
    jax.block_until_ready(x)
    before = requests.value
    jax.block_until_ready(f(x))
    assert requests.value == before + 1
    assert METRICS.counter(
        "jax.compile_requests|fun=jit(fresh_program)").value == 1
    assert hist("jax.backend_compile_s").count >= 1
    assert hist("jax.trace_s").count >= 1 and hist("jax.lower_s").count >= 1
    jax.block_until_ready(f(x))
    assert requests.value == before + 1
    # another shape is another program
    jax.block_until_ready(f(jax.numpy.arange(5.0)))
    assert METRICS.counter(
        "jax.compile_requests|fun=jit(fresh_program)").value == 2


def test_the_flash_kernels_carry_their_names():
    """What a trace reduction finds on the device plane: the
    ``pallas_call``'s name is the XLA op's."""
    import importlib

    import jax
    import jax.numpy as jnp

    fa = importlib.import_module("flexflow_tpu.kernels.flash_attention")
    q = jnp.ones((1, 128, 2, 64), jnp.float32)

    def fwd_bwd(q, k, v, do):
        o, lse = fa._flash_forward(q, k, v, True, 0.125, 64, 64, True,
                                   save_lse=True)
        return fa._flash_backward(q, k, v, o, lse, do, True, 0.125, 64, 64,
                                  True)

    text = str(jax.make_jaxpr(fwd_bwd)(q, q, q, q))
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert f"name={name}" in text, name
    partial = str(jax.make_jaxpr(
        lambda q: fa._flash_forward_partial(q, q, q, True, 0.125, 64, 64,
                                            True))(q))
    assert "name=flash_fwd_partial" in partial
