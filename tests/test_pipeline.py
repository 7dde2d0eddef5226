"""Pipeline parallelism: scheduler numerics + model-level parity.

The reference has no pipeline implementation to mirror (OP_PIPELINE is
an unimplemented enum, reference: include/flexflow/ffconst.h:148), so
these tests assert against the mathematically-equivalent sequential
execution instead."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu.parallel import PipelineConfig
from flexflow_tpu.parallel.pipeline import (
    merge_microbatches,
    pipeline_spmd,
    split_microbatches,
)


def _pp_mesh(n):
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:n]).reshape(n), ("pp",))


class TestPipelineSpmd:
    def _setup(self, S=4, L=4, M=8, B=16, D=16):
        rng = np.random.default_rng(0)
        W = jnp.asarray(rng.normal(size=(L, D, D)).astype(np.float32) * 0.1)
        b = jnp.asarray(rng.normal(size=(L, D)).astype(np.float32) * 0.1)
        x = jnp.asarray(rng.normal(size=(B, D)).astype(np.float32))
        return (W, b), x

    @staticmethod
    def _stage(p, x, mb_index):
        del mb_index
        def blk(x, pb):
            return jnp.tanh(x @ pb[0] + pb[1]), None

        x, _ = jax.lax.scan(blk, x, p)
        return x

    def _ref(self, params, x, L):
        for s in range(L):
            x = jnp.tanh(x @ params[0][s] + params[1][s])
        return x

    @pytest.mark.parametrize("S,L,M", [(4, 4, 8), (2, 4, 4), (4, 8, 4), (1, 4, 2)])
    def test_forward_matches_sequential(self, S, L, M):
        params, x = self._setup(S=S, L=L)
        mesh = _pp_mesh(S)
        xm = split_microbatches(x, M)
        ym = jax.jit(
            lambda p, xm: pipeline_spmd(self._stage, p, xm, mesh=mesh)
        )(params, xm)
        y = merge_microbatches(ym)
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(self._ref(params, x, L)), atol=1e-5
        )

    def test_output_broadcast_uses_ppermute_not_allreduce(self):
        """The output epilogue hands the last stage's buffer around the
        ring with single-pair ppermutes — (S-1)·N bytes on the wire —
        instead of psumming the masked full buffer (~2(S-1)·N)."""
        params, x = self._setup(S=4, L=4, M=8)
        mesh = _pp_mesh(4)
        fn = jax.jit(lambda p, xm: pipeline_spmd(self._stage, p, xm, mesh=mesh))
        hlo = fn.lower(params, split_microbatches(x, 8)).as_text()
        assert "collective_permute" in hlo
        assert "all_reduce" not in hlo

    def test_grad_matches_sequential(self):
        params, x = self._setup(S=4, L=4, M=8)
        mesh = _pp_mesh(4)

        def loss_pp(p):
            ym = pipeline_spmd(self._stage, p, split_microbatches(x, 8), mesh=mesh)
            return jnp.sum(merge_microbatches(ym) ** 2)

        def loss_ref(p):
            return jnp.sum(self._ref(p, x, 4) ** 2)

        g_pp = jax.jit(jax.grad(loss_pp))(params)
        g_ref = jax.grad(loss_ref)(params)
        for a, b in zip(jax.tree.leaves(g_pp), jax.tree.leaves(g_ref)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


class TestPipelinedModel:
    def _build(self, num_devices, pipeline, layers=4):
        cfg = ff.FFConfig(
            batch_size=16, num_devices=num_devices,
            compute_dtype="float32", only_data_parallel=pipeline is None,
            learning_rate=1e-3,
        )
        from flexflow_tpu.models import build_transformer

        m = build_transformer(
            cfg, num_layers=layers, hidden=16, num_heads=2, ff_dim=32, seq_len=4
        )
        m.compile(
            pipeline=pipeline,
            loss_type="mean_squared_error",
            metrics=["mean_squared_error"],
        )
        return m

    def test_pipelined_forward_matches_flat(self):
        L = 4
        m = self._build(4, PipelineConfig(num_stages=4, num_microbatches=4), L)
        m2 = self._build(1, None, L)
        # copy stacked pipeline params into the flat model
        p2 = {k: dict(v) for k, v in m2.params.items()}
        for tname, ws in m.params.items():
            mm = re.match(r"^layer0_(.*)", tname)
            if mm:
                for l in range(L):
                    for wn, w in ws.items():
                        p2[f"layer{l}_" + mm.group(1)][wn] = jnp.asarray(
                            np.asarray(w)[l]
                        )
            else:
                for wn, w in ws.items():
                    p2[tname][wn] = jnp.asarray(np.asarray(w))
        rng = np.random.default_rng(1)
        x = rng.normal(size=(16, 4, 16)).astype(np.float32)
        y1 = np.asarray(
            jax.jit(m.compiled.forward_fn())(m.params, m.state, [jnp.asarray(x)])
        )
        y2 = np.asarray(
            jax.jit(m2.compiled.forward_fn())(p2, m2.state, [jnp.asarray(x)])
        )
        np.testing.assert_allclose(y1, y2, atol=1e-5)

    def test_pipelined_train_step_runs_and_learns(self):
        m = self._build(4, PipelineConfig(num_stages=2, num_microbatches=4))
        rng = np.random.default_rng(2)
        x = rng.normal(size=(16, 4, 16)).astype(np.float32)
        y = rng.normal(size=(16, 4, 16)).astype(np.float32) * 0.1
        params, opt_state, state = m.params, m.opt_state, m.state
        losses = []
        for i in range(5):
            params, opt_state, state, loss, _ = m.compiled.train_step(
                params, opt_state, state, jax.random.key(i),
                [jnp.asarray(x)], jnp.asarray(y),
            )
            losses.append(float(loss))
        assert losses[-1] < losses[0]

    def test_rejects_bad_stage_count(self):
        cfg = ff.FFConfig(batch_size=8, num_devices=2, compute_dtype="float32")
        from flexflow_tpu.models import build_transformer

        m = build_transformer(cfg, num_layers=4, hidden=16, num_heads=2,
                              ff_dim=32, seq_len=4)
        with pytest.raises(ValueError, match="must divide"):
            m.compile(
                pipeline=PipelineConfig(num_stages=4, num_microbatches=4),
                loss_type="mean_squared_error",
            )

    def test_rejects_non_isomorphic_blocks(self):
        cfg = ff.FFConfig(batch_size=8, num_devices=2, compute_dtype="float32")
        model = ff.FFModel(cfg)
        x = model.create_tensor([8, 16], name="x")
        t = model.dense(x, 16, name="layer0_fc")
        t = model.dense(t, 16, activation="relu", name="layer1_fc")  # differs
        model.dense(t, 4, name="head")
        with pytest.raises(ValueError, match="isomorphic"):
            model.compile(
                pipeline=PipelineConfig(num_stages=2, num_microbatches=4),
                loss_type="sparse_categorical_crossentropy",
            )


# ---------------------------------------------------------------------------
# search-integrated pipeline (round 4): compile() proposes pp itself
# ---------------------------------------------------------------------------


def test_search_proposes_pipeline_on_memory_bound_model():
    """The GPipe case, search-discovered: hidden dim 1021 is PRIME (no
    tensor-parallel divisor <= 8) and the weights + optimizer state of
    the full stack exceed the per-device HBM cap, so EVERY flat
    strategy is memory-infeasible — only pipelining (each stage holds
    1/S of the weights) fits.  compile() must find and lower it with
    no pipeline= argument (reference gap: OP_PIPELINE is an enum stub,
    ffconst.h:148; Unity approximates inter-op splits,
    graph.cc:161-295)."""
    import numpy as np

    from flexflow_tpu.compiler.pipeline_lowering import PipelinedCompiledModel
    from flexflow_tpu.core.machine import MachineSpec

    n = 8
    spec = MachineSpec(num_devices=n, devices_per_host=4, platform="cpu",
                       hbm_capacity=48e6)
    cfg = ff.FFConfig(batch_size=16, num_devices=n, compute_dtype="float32",
                      machine_spec=spec)
    m = ff.FFModel(cfg)
    t = m.create_tensor([16, 1021])
    for i in range(4):
        t = m.dense(t, 1021, activation="relu", name=f"layer{i}_fc")
    t = m.dense(t, 1021, name="head")  # epilogue: blocks need an external consumer
    m.compile(loss_type="mean_squared_error", metrics=[])
    assert isinstance(m.compiled, PipelinedCompiledModel)
    assert m.compiled.pipeline.num_stages in (2, 4)

    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 1021)).astype(np.float32)
    y = rng.normal(size=(64, 1021)).astype(np.float32) * 0.1
    hist = m.fit(x=x, y=y, epochs=2, verbose=False)
    assert hist[-1]["loss"] < hist[0]["loss"]


def test_search_keeps_flat_lowering_on_single_host():
    """Same model on a single-ICI-domain machine: DP sync rides ICI,
    the pipeline bubble cannot pay for itself, compile stays flat."""
    from flexflow_tpu.compiler.pipeline_lowering import PipelinedCompiledModel
    from flexflow_tpu.core.machine import MachineSpec

    n = 8
    spec = MachineSpec.host_cpu(n)  # one host, serialized collectives
    cfg = ff.FFConfig(batch_size=16, num_devices=n, compute_dtype="float32",
                      machine_spec=spec)
    m = ff.FFModel(cfg)
    t = m.create_tensor([16, 128])
    for i in range(4):
        t = m.dense(t, 128, activation="relu", name=f"layer{i}_fc")
    t = m.dense(t, 128, name="head")
    m.compile(loss_type="mean_squared_error", metrics=[])
    assert not isinstance(m.compiled, PipelinedCompiledModel)


def test_general_pipeline_costs_non_stacked_graph():
    """Pipeline costing over an ARBITRARY graph cut (reference:
    graph.cc:161-295 splits any graph): a heterogeneous MLP whose
    layer widths all differ fails the stacked-block gates, but
    propose_pipeline_general still produces a balanced staged
    partition with a finite modeled cost — the memory-bound prime-width
    regime where every flat strategy is infeasible."""
    from flexflow_tpu.core.machine import MachineSpec
    from flexflow_tpu.search.driver import optimize_strategy
    from flexflow_tpu.search.pipeline_search import (
        _applicable,
        propose_pipeline_general,
    )
    from flexflow_tpu.search.simulator import Simulator

    n = 8
    spec = MachineSpec(num_devices=n, devices_per_host=4, platform="cpu",
                       hbm_capacity=40e6)
    cfg = ff.FFConfig(batch_size=16, num_devices=n, compute_dtype="float32",
                      machine_spec=spec)
    m = ff.FFModel(cfg)
    t = m.create_tensor([16, 1021])
    # widths 1021, 1019, 1013, 1009: all prime (no TP divisor), all
    # DIFFERENT (no stacked-block isomorphism)
    for i, w in enumerate((1019, 1013, 1009, 1021)):
        t = m.dense(t, w, activation="relu", name=f"layer{i}_fc")
    t = m.dense(t, 1021, name="head")

    for stages in (2, 4):
        assert _applicable(m.graph, stages) is None  # truly non-stacked

    g, strat = optimize_strategy(m.graph, cfg, return_graph=True)
    sim = Simulator.for_config(cfg)
    baseline = sim.simulate(g, strat)
    prop = propose_pipeline_general(g, cfg, sim, baseline)
    assert prop is not None, "no staged proposal for the pp-only regime"
    assert prop.num_stages in (2, 4, 8)
    assert not prop.executable
    # the stages partition the whole graph, in topo order
    seen = [gg for stage in prop.stage_guids for gg in stage]
    assert sorted(seen) == sorted(g.nodes)
    order = {node.guid: i for i, node in enumerate(g.topo_order())}
    assert [order[gg] for gg in seen] == sorted(order[gg] for gg in seen)
    assert np.isfinite(prop.cost)
    # each stage holds 1/S of the weights; the modeled cost must beat
    # the (infeasible) flat baseline by construction
    assert prop.cost < baseline or not np.isfinite(baseline)
