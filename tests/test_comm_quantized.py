"""Quantized gradient collectives (comm/quantized.py) + their cost-model
and search integration (EQuARX, arXiv:2506.17615).

Three contracts:

* numerics — the fp32 path is bit-exact with a plain psum (and the
  whole lowering stays bit-exact when no group is compressed); the
  compressed paths obey ``allreduce_error_bound``; ZeRO-1 composes.
* pricing — the cost model prices int8 sync below fp32 for big groups,
  and the simulated sync-bound BERT allreduce term drops >= 1.5x under
  int8 (the acceptance number, simulated).
* search — the per-weight-group choice compresses in the sync-bound
  regime and keeps fp32 in the compute-bound regime (same model,
  large per-device batch: sync hides behind compute).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu.comm import (
    allreduce_error_bound,
    dequantize_chunked,
    quantize_chunked,
    quantized_allreduce,
    shard_map,
)

jnp_f32 = jnp.float32


# ---------------------------------------------------------------------------
# quantize/dequantize unit contract
def test_quantize_roundtrip_error_bound():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(1000,)).astype(np.float32) * 3.0)
    q, s = quantize_chunked(x, chunk=128)
    back = dequantize_chunked(q, s, x.size, x.shape)
    # half-ulp of the per-chunk scale, scale = amax/127
    amax = float(jnp.max(jnp.abs(x)))
    assert float(jnp.max(jnp.abs(back - x))) <= amax / 254.0 + 1e-7
    # all-zero chunks round-trip exactly (scale pinned to 1)
    z = jnp.zeros((256,), jnp_f32)
    qz, sz = quantize_chunked(z)
    np.testing.assert_array_equal(
        np.asarray(dequantize_chunked(qz, sz, z.size, z.shape)), 0.0)


# ---------------------------------------------------------------------------
# collective numerics on the 8-device mesh
def _per_device_allreduce(mesh, xs, precision):
    """Run quantized_allreduce over all mesh axes with DISTINCT
    per-device inputs (xs stacked on a leading device axis)."""
    from jax.sharding import PartitionSpec as P

    axes = tuple(mesh.axis_names)
    n = int(np.prod([mesh.shape[a] for a in axes]))
    spec = P(axes)

    def local(x):
        return quantized_allreduce(
            x[0], axes, precision=precision, axis_size=n)

    out = shard_map(
        local, mesh=mesh, in_specs=(spec,), out_specs=P(),
    )(xs)
    return np.asarray(out)


def test_fp32_path_matches_psum_bitwise(mesh8):
    rng = np.random.default_rng(1)
    xs = jnp.asarray(rng.normal(size=(8, 4, 33)).astype(np.float32))
    got = _per_device_allreduce(mesh8, xs, "fp32")
    from jax.sharding import PartitionSpec as P

    want = np.asarray(shard_map(
        lambda x: jax.lax.psum(x[0], tuple(mesh8.axis_names)),
        mesh=mesh8, in_specs=(P(tuple(mesh8.axis_names)),), out_specs=P(),
    )(xs))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("precision", ["int8", "bf16"])
def test_compressed_allreduce_error_bounded(mesh8, precision):
    rng = np.random.default_rng(2)
    xs = jnp.asarray(rng.normal(size=(8, 3, 200)).astype(np.float32))
    got = _per_device_allreduce(mesh8, xs, precision)
    want = np.sum(np.asarray(xs), axis=0)
    err = float(np.max(np.abs(got - want)))
    bound = allreduce_error_bound(list(np.asarray(xs)), precision)
    assert err <= bound, (err, bound)
    # the bound is a real contract, not vacuous: it is tight to within
    # a couple orders of magnitude of the observed error
    assert err > bound / 1e4


def test_error_feedback_tightens_accumulated_error(mesh8):
    """Error-feedback contract (``quantized_allreduce_ef``): over
    repeated steps the residual re-injects each round's quantization
    error, so the ACCUMULATED estimate error stays bounded instead of
    growing linearly — the property that keeps int8 sync safe at large
    replica counts (n independent per-step roundings on near-constant
    gradients otherwise accumulate the same bias every step)."""
    from jax.sharding import PartitionSpec as P

    from flexflow_tpu.comm import quantized_allreduce_ef

    axes = tuple(mesh8.axis_names)
    rng = np.random.default_rng(7)
    # near-constant per-device addends: the worst case for no-feedback
    # (each step rounds the same values the same way -> coherent bias)
    xs = jnp.asarray(rng.normal(size=(8, 600)).astype(np.float32))
    steps = 16

    def run(with_feedback):
        def local(x):
            g = x[0]
            res = jnp.zeros_like(g)
            acc = jnp.zeros_like(g)
            for _ in range(steps):
                if with_feedback:
                    y, res = quantized_allreduce_ef(
                        g, res, axes, precision="int8", axis_size=8)
                else:
                    y = quantized_allreduce(
                        g, axes, precision="int8", axis_size=8)
                acc = acc + y
            return acc

        # jitted: eager shard_map dispatches the unrolled steps op by op
        return np.asarray(jax.jit(shard_map(
            local, mesh=mesh8, in_specs=(P(axes),), out_specs=P(),
        ))(xs))

    want = np.sum(np.asarray(xs), axis=0) * steps
    err_plain = float(np.max(np.abs(run(False) - want)))
    err_ef = float(np.max(np.abs(run(True) - want)))
    # feedback must tighten the accumulated error substantially (the
    # no-feedback bias grows ~linearly in steps; EF keeps it ~one step)
    assert err_ef < err_plain / 3, (err_ef, err_plain)
    # single-step sanity: the EF result still obeys the one-step bound
    # headroom (residual starts at zero -> identical first step)
    def one(x):
        y, _ = quantized_allreduce_ef(
            x[0], jnp.zeros_like(x[0]), axes, precision="int8",
            axis_size=8)
        return y

    got = np.asarray(shard_map(
        one, mesh=mesh8, in_specs=(P(axes),), out_specs=P())(xs))
    bound = allreduce_error_bound(list(np.asarray(xs)), "int8")
    assert float(np.max(np.abs(got - np.sum(np.asarray(xs), 0)))) <= bound


# ---------------------------------------------------------------------------
# end-to-end training numerics
def _train(sync_precision, zero=False, seed=0):
    cfg = ff.FFConfig(batch_size=32, epochs=2, num_devices=8,
                      only_data_parallel=True, compute_dtype="float32",
                      sync_precision=sync_precision, zero_dp_shard=zero,
                      seed=seed)
    m = ff.FFModel(cfg)
    x = m.create_tensor([32, 64])
    t = m.dense(x, 2048, activation="relu", name="fc1")
    t = m.dense(t, 8, name="head")
    m.compile(optimizer=ff.AdamOptimizer(alpha=1e-3),
              loss_type="sparse_categorical_crossentropy", metrics=[])
    rng = np.random.default_rng(0)
    y = rng.integers(0, 8, 128).astype(np.int32)
    xd = rng.normal(size=(128, 64)).astype(np.float32)
    hist = m.fit(x=xd, y=y, verbose=False)
    return m, hist[-1]["loss"]


def test_fp32_sync_is_bitexact_with_default(mesh8):
    """sync_precision='fp32' must lower to the identical program as the
    historical default — no compression map, bitwise-equal params."""
    m_def, _ = _train("fp32")
    assert m_def.sync_precision_map == {}
    m2, _ = _train("fp32")
    for op, ws in m_def.params.items():
        for w, a in ws.items():
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(m2.params[op][w]))


def test_int8_sync_trains_close_to_fp32(mesh8):
    m32, l32 = _train("fp32")
    m8, l8 = _train("int8")
    # the big matmul group is compressed, the small head is declined by
    # the safety heuristic — the 'heuristic declines to compress' doc
    # behavior (README: sync-precision search)
    assert m8.sync_precision_map == {"fc1": "int8"}
    assert np.isfinite(l8)
    assert np.isclose(l32, l8, rtol=5e-3)
    for op, ws in m32.params.items():
        for w, a in ws.items():
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(m8.params[op][w]),
                rtol=5e-2, atol=5e-3,
            )


def test_int8_sync_composes_with_zero1(mesh8):
    """ZeRO-1 reduce-scatter placement + quantized sync in one step:
    the round trip runs before the update, so _constrain_update's
    shardings are untouched and numerics stay close to fp32."""
    m_z8, l_z8 = _train("int8", zero=True)
    _, l32 = _train("fp32")
    assert m_z8.sync_precision_map == {"fc1": "int8"}
    assert np.isfinite(l_z8) and np.isclose(l32, l_z8, rtol=5e-3)
    # optimizer state is still ZeRO-sharded (1/8 per device)
    v = m_z8.opt_state["v"]["fc1"]["kernel"]
    assert v.addressable_shards[0].data.size * 8 == v.size


# ---------------------------------------------------------------------------
# cost model + search integration
def _sync_bound_bert(batch, n_devices=8, sync_precision="search"):
    from zoo import SYNC_BOUND_BERT_KW
    from flexflow_tpu.models import build_transformer

    cfg = ff.FFConfig(batch_size=batch, num_devices=n_devices,
                      sync_precision=sync_precision)
    return build_transformer(cfg, **SYNC_BOUND_BERT_KW).graph


def test_int8_sync_priced_below_fp32():
    from flexflow_tpu.core.machine import MachineSpec, MachineView
    from flexflow_tpu.search.machine_model import CostModel

    cm = CostModel(MachineSpec.tpu_v5e(8), num_devices=8)
    nbytes = 4 * (1 << 22)  # 4M fp32 elements
    ar32 = cm.allreduce(nbytes, 8, precision="fp32")
    ar8 = cm.allreduce(nbytes, 8, precision="int8")
    arbf = cm.allreduce(nbytes, 8, precision="bf16")
    assert ar8 < arbf < ar32
    # int8 wire is ~3.9x smaller; overhead keeps the net win below that
    assert ar32 / ar8 > 2.0
    # reducescatter compresses too (the ZeRO-1 grad path)
    assert cm.reducescatter(nbytes, 8, precision="int8") < \
        cm.reducescatter(nbytes, 8, precision="fp32")


def test_sync_bound_bert_allreduce_term_drops_1p5x():
    """The acceptance number: the simulated DP weight-sync
    term of the sync-bound BERT config drops >= 1.5x under int8."""
    from flexflow_tpu.compiler.lowering import data_parallel_strategy
    from flexflow_tpu.search.simulator import Simulator

    g = _sync_bound_bert(batch=8)
    dp = data_parallel_strategy(g, 8)
    spec = ff.FFConfig(batch_size=8, num_devices=8).machine_spec

    def sync_term(precision):
        sim = Simulator(spec, num_devices=8, sync_precision=precision)
        return (
            sum(sim.cost.sync_cost(n.op, dp[n.guid]) for n in g.topo_order()),
            sim.simulate(g, dp),
        )

    s32, t32 = sync_term("fp32")
    s8, t8 = sync_term("int8")
    assert s32 / s8 >= 1.5, (s32, s8)
    assert t8 < t32  # the full simulated step prices the same drop


def test_search_flips_precision_only_when_sync_dominates():
    """Same model, two regimes: per-device batch 1 (sync-bound) must
    compress the big matmul groups; per-device batch 1024 (batch 8192
    over 8 devices, compute-bound) must keep every group fp32 — the
    allreduce hides
    behind compute and quantization would buy nothing
    (CostModel.SYNC_DOMINANCE gate)."""
    from flexflow_tpu.compiler.lowering import data_parallel_strategy
    from flexflow_tpu.search.simulator import Simulator
    from flexflow_tpu.search.sync_precision import choose_sync_precision

    spec = ff.FFConfig(batch_size=8, num_devices=8).machine_spec

    g_sync = _sync_bound_bert(batch=8)
    sim = Simulator(spec, num_devices=8, sync_precision="search")
    chosen = choose_sync_precision(
        g_sync, data_parallel_strategy(g_sync, 8), sim.cost)
    assert chosen, "sync-bound regime must compress at least one group"
    assert all(p in ("bf16", "int8") for p in chosen.values())

    g_comp = _sync_bound_bert(batch=8192)
    sim2 = Simulator(spec, num_devices=8, sync_precision="search")
    chosen2 = choose_sync_precision(
        g_comp, data_parallel_strategy(g_comp, 8), sim2.cost)
    assert chosen2 == {}, chosen2


def test_safety_heuristic_declines_small_and_norm_groups():
    from flexflow_tpu.search.sync_precision import grad_safe_to_compress

    m = ff.FFModel(ff.FFConfig(batch_size=8, num_devices=8,
                               only_data_parallel=True))
    x = m.create_tensor([8, 512])
    m.dense(x, 512, name="big")          # 512x512 = 256k elems: safe
    m.dense(x, 16, name="tiny")          # 8k elems: latency-bound
    ln_in = m.create_tensor([8, 16, 512])
    m.layer_norm(ln_in, name="ln")       # norm grads: never compressed
    assert grad_safe_to_compress(m.node_by_name("big").op)
    assert not grad_safe_to_compress(m.node_by_name("tiny").op)
    assert not grad_safe_to_compress(m.node_by_name("ln").op)
