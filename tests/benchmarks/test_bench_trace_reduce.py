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
