"""trace_reduce.py on a hand-built trace: busy union, idle share, kernel
time, module attribution and gap attribution come out as computed by
hand (the numbers are in data/handbuilt_trace.textproto's header)."""

import os
import sys
import types

import pytest
from jax.profiler import ProfileData

from benchmarks.harness import readers, spec, trace_reduce

MS = 1e-3


@pytest.fixture
def cell_of(monkeypatch):
    """A cell whose configuration names a hand-made ``work`` module with
    round prices, and the attention kernels the caller gives."""
    work = types.ModuleType("handmade_work")
    work.attention_kernel_flops = (
        lambda config, batch, seq_len: 1e9 * batch * seq_len)
    work.cached_token_bytes = lambda config, itemsize: 1000 * itemsize
    monkeypatch.setitem(sys.modules, "handmade_work", work)

    def make(*kernels):
        config = {"work": "handmade_work",
                  "harness": {"attention_kernels": list(kernels)}}
        return spec.Cell(name="handmade", chips=1, config=config, traffic={},
                         end_to_end=[], per_layer=[], run_seconds=1)
    return make


@pytest.fixture(scope="module")
def trace():
    path = os.path.join(os.path.dirname(__file__), "data",
                        "handbuilt_trace.textproto")
    with open(path) as f:
        return trace_reduce.load(ProfileData.from_text_proto(f.read()))


def test_load_keeps_device_lines_and_bench_spans(trace):
    dev = trace["devices"]["/device:TPU:0"]
    assert [n for n, _, _ in dev["ops"]] == [
        "fusion.1", "ragged_paged_attention.3", "jvp__.1",
        "ragged_paged_attention.3"]  # names, not the whole HLO text
    assert trace["mosaic_ops"] == {"ragged_paged_attention.3", "jvp__.1"}
    assert [n for n, _, _ in dev["modules"]] == [
        "jit_fwd(123)", "jit__lambda_(456)"]
    assert [n for n, _, _ in trace["host_spans"]] == [
        "bench.submit", "bench.executor_step"]  # "other" is not ours


def test_busy_is_the_union_not_the_sum(trace):
    busy = trace_reduce.device_busy(trace)
    assert busy["busy_s"] == pytest.approx(9 * MS)      # not 4+4+2+1 = 11
    assert busy["window_s"] == pytest.approx(14 * MS)
    assert busy["idle_share"] == pytest.approx(5 / 14)


def test_kernel_time_and_module_attribution(trace):
    kernel = trace_reduce.op_seconds(
        trace, readers._named("ragged_paged_attention"))
    assert kernel == pytest.approx(5 * MS)
    inside = trace_reduce.ops_inside_modules(
        trace, lambda m: m.startswith("jit_fwd("))
    assert inside == pytest.approx(6 * MS)
    assert readers.prefill_device_share({"trace": trace}) == pytest.approx(
        6 / 9 * 100)


def test_flash_share_and_roofline_from_the_kernels_the_config_names(
        trace, cell_of):
    # the trace holds two Mosaic kernels; the configuration names ONE as
    # attention, so the other (another kernel of the same program) does
    # not count: 2 ms of 9 ms busy, not 4 + 2 + 1 = 7
    ctx = {"trace": trace, "device_kind": "TPU v5 lite",
           "cell": cell_of("jvp__"),
           "facts": {"traced_steps": 2, "batch": 2, "seq_len": 100}}
    assert readers.flash_time_share(ctx) == pytest.approx(2 / 9 * 100)
    need = 2 * 1e9 * 2 * 100  # steps x the work module's FLOPs a step
    assert readers.flash_roofline_share(ctx) == pytest.approx(
        need / (2 * MS) / 197e12 * 100)
    ctx["cell"] = cell_of("jvp__", "ragged_paged_attention")
    assert readers.flash_time_share(ctx) == pytest.approx(7 / 9 * 100)
    ctx["cell"] = cell_of("no_such_kernel")
    assert readers.flash_time_share(ctx) is None       # nothing to read
    assert readers.flash_roofline_share(ctx) is None   # never 0


def test_idle_gaps_are_charged_to_the_span_that_covers_most(trace):
    assert trace_reduce.idle_gaps(trace) == [
        ["bench.submit", pytest.approx(4 * MS)],
        ["bench.executor_step", pytest.approx(1 * MS)]]
    top = trace_reduce.top_ops(trace, n=2)
    assert top[0] == ["ragged_paged_attention f32[16,16,64]",
                      pytest.approx(5 * MS)]  # two events, one family
    assert top[1] == ["fusion f32[8]", pytest.approx(4 * MS)]
    assert trace_reduce.op_family(
        "%copy.351 = f32[512,32,16,64]{3,2,1,0:T(8,128)} copy(f32[512,32,16,"
        "64]{0,3,2,1:T(8,128)} %k_cache__.40)") == "copy f32[512,32,16,64]"
    assert trace_reduce.op_family(
        "%jvp__.1 = (bf16[32,2048,64]{2,1,0}, f32[32,2048,1]{2,1,0}) "
        "custom-call(%x)") == "jvp__ bf16[32,2048,64]"


def test_ragged_roofline_share_from_live_bytes(trace, cell_of):
    ctx = {"trace": trace, "device_kind": "TPU v5 lite",
           "cell": cell_of("ragged_paged_attention"),
           "facts": {"traced_live_seq_lens": [100, 300], "pool_itemsize": 4}}
    want = 400 * 4000 / (5 * MS) / 819e9 * 100
    assert readers.ragged_roofline_share(ctx) == pytest.approx(want)
    ctx["facts"]["traced_live_seq_lens"] = []
    assert readers.ragged_roofline_share(ctx) is None  # nothing to read


def test_unknown_device_kind_is_an_error():
    from benchmarks.harness.peaks import peaks_for

    with pytest.raises(KeyError):
        peaks_for("TPU v99")


def test_load_and_breakdown_of_the_stored_trace_are_what_they_were(trace):
    """``trace_reduce.load`` and what the result line's ``breakdown`` is
    made of, whole: a reader added beside them (PR 34: device time by
    name scope) changes none of it."""
    ms = pytest.approx
    assert trace == {
        "devices": {"/device:TPU:0": {
            "ops": [("fusion.1", ms(1 * MS), ms(4 * MS)),
                    ("ragged_paged_attention.3", ms(3 * MS), ms(4 * MS)),
                    ("jvp__.1", ms(11 * MS), ms(2 * MS)),
                    ("ragged_paged_attention.3", ms(14 * MS), ms(1 * MS))],
            "modules": [("jit_fwd(123)", ms(1 * MS), ms(6.5 * MS)),
                        ("jit__lambda_(456)", ms(11 * MS), ms(4 * MS))]}},
        "mosaic_ops": {"ragged_paged_attention.3", "jvp__.1"},
        "families": {
            "fusion.1": "fusion f32[8]",
            "ragged_paged_attention.3": "ragged_paged_attention f32[16,16,64]",
            "jvp__.1": "jvp__ bf16[2,8,4]"},
        "host_spans": [("bench.submit", ms(7.5 * MS), ms(3 * MS)),
                       ("bench.executor_step", ms(10.6 * MS), ms(4.4 * MS))]}
    assert trace_reduce.breakdown(trace) == {
        "device_ops": [["ragged_paged_attention f32[16,16,64]", ms(5 * MS)],
                       ["fusion f32[8]", ms(4 * MS)],
                       ["jvp__ bf16[2,8,4]", ms(2 * MS)]],
        "idle_gaps": [["bench.submit", ms(4 * MS)],
                      ["bench.executor_step", ms(1 * MS)]]}


# ---- device time by name scope -------------------------------------------

class Compiled:
    """What ``device.scopes_of`` asks of a compiled program: its text."""

    def __init__(self, text):
        self.text = text

    def as_text(self):
        return self.text


# the stored trace's three instructions as a compiled program prints
# them, among others the trace never ran
PROGRAM = Compiled('''HloModule jit__raw_step, is_scheduled=true

%fused_computation (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %add.2 = f32[8]{0} add(%p, %p), metadata={op_name="jit(_raw_step)/ff.loop1/add" stack_frame_id=7}
}

ENTRY %main (p0: f32[8]) -> f32[16,16,64] {
  %p0 = f32[8]{0} parameter(0), metadata={op_name="params"}
  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %p0), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(_raw_step)/transpose(jvp(ff.exit))/mul" source_file="x.py" source_line=3}
  %copy-done.5 = s32[16,32]{1,0} copy-done(%copy-start.5)
  %jvp__.1 = (bf16[2,8,4]{2,1,0}, f32[2,8,1]{2,1,0}) custom-call(bf16[2,8,4]{2,1,0} %bitcast.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(_raw_step)/ff.loop1/jvp()/pallas_call"}
  ROOT %ragged_paged_attention.3 = f32[16,16,64]{2,1,0} custom-call(s32[16,32]{1,0} %copy-done.5), custom_call_target="tpu_custom_call", metadata={op_name="jit(_raw_step)/ff.moe.experts/pallas_call"}
}
''')


def scoped(trace, compiled=PROGRAM, **change):
    from benchmarks.harness import device

    facts = device.scope_facts(compiled)
    facts["scopes"].update(change)
    return {"trace": trace, "facts": facts}


def test_scopes_of_reads_every_instructions_op_name():
    from benchmarks.harness import device

    assert device.scopes_of(PROGRAM) == {
        "p": "", "add.2": "jit(_raw_step)/ff.loop1/add", "p0": "params",
        "fusion.1": "jit(_raw_step)/transpose(jvp(ff.exit))/mul",
        "copy-done.5": "",
        "jvp__.1": "jit(_raw_step)/ff.loop1/jvp()/pallas_call",
        "ragged_paged_attention.3":
            "jit(_raw_step)/ff.moe.experts/pallas_call"}
    families = device.families_of(PROGRAM)
    assert families["jvp__.1"] == "jvp__ bf16[2,8,4]"
    assert families["ragged_paged_attention.3"] == (
        "ragged_paged_attention f32[16,16,64]")
    assert device.mosaic_calls(PROGRAM) == 2


def test_scopes_of_a_program_jax_compiled_hold_its_named_scopes():
    """On the CPU the instruction names differ from a chip's; that the
    compiled text carries a ``jax.named_scope`` under ``op_name``, in
    the forward and in the transposed pass, does not."""
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import device

    def loss(w, x):
        with jax.named_scope("ff.exit"):
            return jnp.sum(jnp.tanh(x @ w))

    compiled = jax.jit(jax.grad(loss)).lower(
        jnp.ones((8, 8)), jnp.ones((4, 8))).compile()
    scopes = device.scopes_of(compiled)
    assert scopes and set(scopes) == set(device.families_of(compiled))
    under = [s for s in scopes.values() if "ff.exit" in s]
    assert any("transpose(" in s for s in under)
    assert any("transpose(" not in s for s in under)


def test_seconds_by_name_and_the_share_of_a_scope(trace):
    assert trace_reduce.seconds_by_name(trace) == {
        "fusion.1": pytest.approx(4 * MS),
        "ragged_paged_attention.3": pytest.approx(5 * MS),
        "jvp__.1": pytest.approx(2 * MS)}
    # over BUSY time (9 ms, the union), as every share of device time
    ctx = scoped(trace)
    assert readers.scope_time_share(ctx, ["ff.exit"]) == pytest.approx(
        4 / 9 * 100)
    assert readers.scope_time_share(ctx, ["ff.moe."]) == pytest.approx(
        5 / 9 * 100)
    assert readers.scope_time_share(ctx, ["ff.loop"]) == pytest.approx(
        2 / 9 * 100)
    assert readers.scope_time_share(ctx, ["ff.exit", "ff.loop"]) == \
        pytest.approx(6 / 9 * 100)
    assert readers.scope_unplaced_share(ctx) == 0.0
    # a scope nothing ran under, and a run whose driver handed no
    # scopes: nothing to read, never 0
    assert readers.scope_time_share(ctx, ["ff.mtp"]) is None
    assert readers.scope_time_share({"trace": trace, "facts": {}},
                                    ["ff.exit"]) is None
    assert readers.scope_unplaced_share({"trace": trace, "facts": {}}) is None


@pytest.mark.parametrize("how", ["no op_name", "not in the text",
                                 "another program's instruction"])
def test_a_share_is_left_out_where_too_much_time_is_unplaced(trace, how):
    """``jvp__.1`` is 2 of 9 busy ms: unplaced, it is past the tenth of
    busy time the reader allows, whatever scope is asked for."""
    if how == "no op_name":
        ctx = scoped(trace, **{"jvp__.1": ""})
    elif how == "not in the text":
        ctx = scoped(trace, Compiled(PROGRAM.text.replace("%jvp__.1 = ",
                                                          "%jvp__.7 = ")))
    else:  # the name is there, over another shape: not the traced op
        ctx = scoped(trace, Compiled(PROGRAM.text.replace(
            "(bf16[2,8,4]{2,1,0}, f32[2,8,1]{2,1,0}) custom-call",
            "(bf16[64,8,4]{2,1,0}, f32[2,8,1]{2,1,0}) custom-call")))
    assert readers.scope_unplaced_share(ctx) == pytest.approx(2 / 9 * 100)
    assert readers.scope_time_share(ctx, ["ff.exit"]) is None
    assert readers.scope_time_share(ctx, ["ff.moe."]) is None


def test_a_share_stands_under_a_little_unplaced_time(trace):
    """``fusion.1`` cut to 0.4 ms and its ``op_name`` lost: 5 % of busy
    time is unplaced, under the tenth, and the other scopes' shares
    stand."""
    events = [(n, s, d / 10 if n == "fusion.1" else d)
              for n, s, d in trace["devices"]["/device:TPU:0"]["ops"]]
    small = dict(trace, devices={"/device:TPU:0": dict(
        trace["devices"]["/device:TPU:0"], ops=events)})
    ctx = scoped(small, **{"fusion.1": ""})
    busy_ms = 0.4 + 4 + 2 + 1   # fusion.1 0.4 ms, overlapping nothing now
    assert readers.scope_unplaced_share(ctx) == pytest.approx(
        0.4 / busy_ms * 100)
    assert readers.scope_time_share(ctx, ["ff.moe."]) == pytest.approx(
        5 / busy_ms * 100)
