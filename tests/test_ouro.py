"""``build_ouro`` and what it brought, at tiny widths on the CPU against
the plain reference (benchmarks/reference/ouro.py): rotary in
``MultiHeadAttentionOp``, ``weights_of`` on every weighted op of a block
(the tie), the exit objective and its counters, recomputation that
covers tied ops (per op and by block; tests/test_chip_lowering.py holds
that by block the step compiled for the chip needs fewer temporaries),
one view for the ops of one ``weights_key``, a checkpoint that holds
each tied leaf once.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flexflow_tpu as ff
from benchmarks.reference import ouro as ref
from flexflow_tpu.core.ptensor import ParallelTensorShape
from flexflow_tpu.model import FFModel
from flexflow_tpu.models import build_ouro
from flexflow_tpu.models import ouro as ouro_module
from flexflow_tpu.obs.metrics import METRICS
from flexflow_tpu.ops import ExitLossOp, LoweringContext, MultiHeadAttentionOp
from flexflow_tpu.ops.attention import half_split_rotary
from flexflow_tpu.ops.exit_loss import exit_distribution

TINY = dict(vocab=96, num_layers=2, hidden=32, num_heads=2, head_dim=16,
            ff_dim=48, loop_steps=4, seq_len=32)
T, L = TINY["loop_steps"], TINY["num_layers"]
LAYER_OPS = ("attn_norm", "attn", "attn_out_norm", "ffn_norm", "ffn_gate",
             "ffn_up", "ffn_down", "ffn_out_norm")


def shape(*sizes, dtype="float32"):
    return ParallelTensorShape.make(sizes, dtype)


def normal(seed, *sizes, scale=1.0):
    return scale * jax.random.normal(jax.random.key(seed), sizes, jnp.float32)


def batch_of(n, seq, vocab, seed=0):
    x = np.random.default_rng(seed).integers(0, vocab, (n, seq)).astype(np.int32)
    return x, np.roll(x, -1, axis=1)


def tiny_model(batch=2, num_devices=1, dtype="float32", builder=build_ouro,
               optimizer=None, **cfg):
    config = ff.FFConfig(batch_size=batch, seed=7, compute_dtype=dtype,
                         num_devices=num_devices, cost_cache_file="", **cfg)
    model = builder(config, **TINY)
    model.compile(optimizer=optimizer or ff.AdamOptimizer(alpha=1e-3),
                  loss_type="sparse_categorical_crossentropy", metrics=[])
    return model


def loss_of(model, x, y):
    """params -> the loss ``fit`` differentiates."""
    def loss(params):
        logits, state = model.compiled.apply(
            params, model.state, [jnp.asarray(x)], None, train=True)
        return model.compiled._loss_from(logits, jnp.asarray(y), state)

    return loss


@pytest.fixture(scope="module")
def tiny():
    return tiny_model()


# ---- (i) rotary in plain multi-head attention -----------------------------

def attention_op(seq, **kw):
    return MultiHeadAttentionOp("a", [shape(2, seq, 32)] * 3, embed_dim=32,
                                num_heads=2, causal=True, **kw)


def seeded(op, seed=0):
    keys = jax.random.split(jax.random.key(seed), len(op._weight_specs))
    return {w.name: w.initializer.init(k, w.shape, jnp.float32)
            for w, k in zip(op._weight_specs, keys)}


def test_half_split_rotary_is_the_references():
    x = normal(2, 2, 16, 3, 8)
    np.testing.assert_allclose(half_split_rotary(x, 1e6), ref.rotary(x),
                               rtol=1e-5, atol=1e-6)
    # position 0 is left alone; the pair (x_i, x_{i+D/2}) of position p
    # turns by p * theta^(-2i/D)
    np.testing.assert_allclose(half_split_rotary(x, 1e4)[:, 0], x[:, 0])
    one = half_split_rotary(jnp.zeros((1, 4, 1, 4)).at[..., 1].set(1.0), 100.0)
    angle = 3 * 100.0 ** (-2 / 4)
    np.testing.assert_allclose(one[0, 3, 0], [0, np.cos(angle), 0, np.sin(angle)],
                               atol=1e-6)


@pytest.mark.parametrize("seq", [48, 512], ids=["xla", "flash-interpreted"])
def test_rotary_attention_forward_and_gradients(seq):
    op = attention_op(seq, rope_theta=1e6)
    assert op.attrs["rope_theta"] == 1e6
    ws, x = seeded(op), normal(1, 2, seq, 32)
    ctx = LoweringContext(compute_dtype=jnp.float32, train=True)

    def system(x, ws):
        return op.forward(ctx, [x, x, x], ws)[0]

    def reference(x, ws):
        with jax.default_matmul_precision("highest"):
            return ref.attention(x, ws)

    np.testing.assert_allclose(system(x, ws), reference(x, ws),
                               rtol=1e-5, atol=1e-5)
    g = normal(3, 2, seq, 32)
    got = jax.grad(lambda x, w: jnp.sum(system(x, w) * g), (0, 1))(x, ws)
    want = jax.grad(lambda x, w: jnp.sum(reference(x, w) * g), (0, 1))(x, ws)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-4,
                                   atol=1e-4 * float(jnp.max(jnp.abs(b))))


def test_an_op_without_rotary_or_a_tie_is_the_op_it_was():
    """Neither key exists unless set: the signature (cost cache,
    calibration, compile-cache keys) and the program do not move."""
    op = attention_op(48)
    assert sorted(op.attrs) == ["causal", "dropout", "embed_dim", "kdim",
                                "num_heads", "sp_mode", "use_bias",
                                "use_flash", "vdim"]
    assert op.signature() == attention_op(48, rope_theta=None,
                                          weights_of=None).signature()
    assert op.signature() != attention_op(48, rope_theta=1e6).signature()
    ctx = LoweringContext(compute_dtype=jnp.float32, train=True)
    x, ws = normal(1, 2, 48, 32), seeded(op)
    program = str(jax.make_jaxpr(
        lambda x, w: op.forward(ctx, [x, x, x], w))(x, ws))
    assert " cos " not in program and " sin " not in program
    rotary = attention_op(48, rope_theta=1e6)
    assert " cos " in str(jax.make_jaxpr(
        lambda x, w: rotary.forward(ctx, [x, x, x], w))(x, ws))
    for cls, kw in ((ff.ops.RMSNormOp, {}), (ff.ops.LinearOp, {"out_dim": 4})):
        assert "weights_of" not in cls("n", [shape(2, 8)], **kw).attrs


def test_rotary_is_refused_on_cross_attention_and_on_a_sequence_shard():
    with pytest.raises(AssertionError, match="self-attention"):
        MultiHeadAttentionOp("a", [shape(2, 8, 32), shape(2, 16, 32),
                                   shape(2, 16, 32)], embed_dim=32,
                             num_heads=2, rope_theta=1e6)
    op = attention_op(48, rope_theta=1e6)
    # the search is not offered the sequence split ...
    assert op.splittable_output_dims() == (0,)
    assert attention_op(48).splittable_output_dims() == (0, 1)
    # ... and a caller's strategy that asks for it is refused
    ctx = LoweringContext(compute_dtype=jnp.float32, train=True)
    ctx.slot_axes = {1: ("x",)}   # the view shards the sequence
    x = normal(1, 2, 48, 32)
    with pytest.raises(NotImplementedError, match="sequence-sharded"):
        op.forward(ctx, [x, x, x], seeded(op))


# ---- (ii) the tie -----------------------------------------------------------

class Untied(FFModel):
    """``FFModel`` that ignores ``weights_of``: every op owns weights."""


for _layer in ("dense", "rms_norm", "multihead_attention", "embedding"):
    def _untied(self, *args, _layer=_layer, weights_of=None, **kw):
        return getattr(FFModel, _layer)(self, *args, **kw)

    setattr(Untied, _layer, _untied)


def test_one_copy_of_every_layer_is_held_and_optimised(tiny):
    names = {f"layer{l}_{part}" for l in range(L) for part in LAYER_OPS}
    assert set(tiny.params) == names | {"tok_embed", "final_norm", "lm_head",
                                       "exit_gate"}
    d, f, v = TINY["hidden"], TINY["ff_dim"], TINY["vocab"]
    layer = 4 * d * d + 3 * d * f + 4 * d
    count = sum(int(np.prod(w.shape)) for ws in tiny.params.values()
                for w in ws.values())
    assert count == 2 * v * d + d + (d + 1) + L * layer
    for slot in ("m", "v"):
        assert set(tiny.opt_state[slot]) == set(tiny.params)
    # the graph holds T x L blocks of which (T - 1) x L declare no weights
    sharers = [n.op for n in tiny.graph.nodes.values()
               if n.op.weights_key != n.op.name]
    assert len(sharers) == (T - 1) * (L * len(LAYER_OPS) + 3)
    assert all(not op._weight_specs for op in sharers)


def test_a_tied_gradient_is_the_sum_of_the_untied_twins(tiny, monkeypatch):
    """The gradient in layer l equals the SUM over t of the gradients of
    the T x L-layer twin that owns every block's weights and carries
    the same values."""
    monkeypatch.setattr(ouro_module, "FFModel", Untied)
    twin = tiny_model()
    monkeypatch.undo()
    assert len(twin.params) == len(tiny.params) + (T - 1) * (
        L * len(LAYER_OPS) + 3)
    carried = {name: tiny.params[name.split("_", 1)[1]
                                 if name.startswith("loop") else name]
               for name in twin.params}
    x, y = batch_of(2, 32, 96)
    want_loss, twin_grads = jax.value_and_grad(loss_of(twin, x, y))(carried)
    got_loss, got = jax.value_and_grad(loss_of(tiny, x, y))(tiny.params)
    assert abs(float(got_loss) - float(want_loss)) <= 1e-6 * float(want_loss)
    for name, leaves in got.items():
        uses = [name] + [f"loop{t}_{name}" for t in range(2, T + 1)
                         if f"loop{t}_{name}" in twin_grads]
        assert len(uses) == (1 if name == "tok_embed" else T), name
        for w, a in leaves.items():
            b = sum(twin_grads[u][w] for u in uses)
            if name == "exit_gate":
                # the last step's gate is computed and never read
                assert not np.any(np.asarray(twin_grads[uses[-1]][w]))
            scale = float(jnp.max(jnp.abs(b)))
            assert scale > 0, (name, w)
            np.testing.assert_allclose(a, b, rtol=0, atol=1e-5 * scale,
                                       err_msg=f"{name}/{w}")


def test_a_sharer_of_other_shapes_is_refused_at_graph_construction():
    model = FFModel(ff.FFConfig(batch_size=2))
    x = model.create_tensor([2, 8, 32], name="x")
    model.dense(x, 16, name="owner")
    model.rms_norm(x, name="norm")
    with pytest.raises(ValueError, match="cannot read the weights of owner"):
        model.dense(x, 24, name="wider", weights_of="owner")
    with pytest.raises(ValueError, match="cannot read the weights of owner"):
        model.dense(x, 16, use_bias=False, name="no_bias", weights_of="owner")
    with pytest.raises(ValueError, match="cannot read the weights of norm"):
        model.dense(x, 16, name="other_kind", weights_of="norm")
    with pytest.raises(ValueError, match="names no op"):
        model.rms_norm(x, name="orphan", weights_of="nobody")
    same = model.dense(x, 16, name="same", weights_of="owner")
    assert same.sizes == (2, 8, 16)


# ---- (iii) the objective ----------------------------------------------------

def exit_inputs(seed=0, batch=2, seq=12, vocab=20):
    logits = [normal(seed + t, batch, seq, vocab, scale=2.0) for t in range(T)]
    gates = [normal(seed + 10 + t, batch, seq, 1, scale=1.5) for t in range(T)]
    ids, labels = batch_of(batch, seq, vocab, seed)
    return logits, gates, jnp.asarray(ids), jnp.asarray(labels)


def exit_op(beta=0.1, batch=2, seq=12, vocab=20):
    return ExitLossOp("exit_loss", [shape(batch, seq, vocab)] * T
                      + [shape(batch, seq, 1)] * T
                      + [shape(batch, seq, dtype="int32")], beta=beta)


def run_exit_op(op, logits, gates, ids):
    state = {f"{op.name}/{name}": jnp.full(s, fill, dtype)
             for name, s, dtype, fill in op.state_specs()}
    ctx = LoweringContext(compute_dtype=jnp.float32, train=True,
                          state_in=state)
    out = op.forward(ctx, [*logits, *gates, ids], {})
    return out[0], ctx.state_out


def reference_objective(logits, gates, labels, beta=0.1):
    seq = labels.shape[1]
    counted = jnp.broadcast_to(jnp.arange(seq) < seq - 1, labels.shape)
    nll = jnp.stack([ref._token_nll(lg, labels) for lg in logits])
    lam = jax.nn.sigmoid(jnp.stack([z[..., 0] for z in gates]))
    return ref.objective(nll, lam, counted, beta)


def test_the_exit_objective_and_its_gradient_are_the_references():
    logits, gates, ids, labels = exit_inputs()
    op = exit_op()

    def system(logits, gates):
        return run_exit_op(op, logits, gates, ids)[1]["exit_loss/loss"]

    got, got_g = jax.value_and_grad(system, (0, 1))(logits, gates)
    want, want_g = jax.value_and_grad(
        lambda lg, z: reference_objective(lg, z, labels), (0, 1))(logits, gates)
    assert abs(float(got) - float(want)) <= 1e-5 * abs(float(want))
    for a, b in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
    # the gates learn: all but the last, which is computed and not read
    for t in range(T - 1):
        assert float(jnp.max(jnp.abs(got_g[1][t]))) > 0
    assert not np.any(np.asarray(got_g[1][T - 1]))
    # the last position has no target: no term reaches it
    for g in (*got_g[0], *got_g[1]):
        assert not np.any(np.asarray(g[:, -1]))
    out, _ = run_exit_op(op, logits, gates, ids)
    assert out is logits[-1]                       # handed through


def test_the_exit_distribution_sums_to_one_and_a_shut_gate_leaves_last():
    z = normal(4, T, 3, 7, scale=3.0)
    p, logp = exit_distribution(z)
    np.testing.assert_allclose(jnp.sum(p, axis=0), 1.0, rtol=1e-6)
    np.testing.assert_allclose(
        p, ref.exit_distribution(jax.nn.sigmoid(z)), rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(jnp.exp(logp), p, rtol=1e-6)
    # a gate bias of -30: all mass on step T, no NaN from 0 ln 0, and the
    # objective IS the last exit's cross-entropy
    logits, _, ids, labels = exit_inputs(seed=3)
    shut = [jnp.full((2, 12, 1), -30.0)] * T
    p, _ = exit_distribution(jnp.stack([z[..., 0] for z in shut]))
    np.testing.assert_allclose(p[-1], 1.0, atol=1e-6)
    _, state = run_exit_op(exit_op(), logits, shut, ids)
    last_ce = jnp.mean(ref._token_nll(logits[-1], labels)[:, :-1])
    assert abs(float(state["exit_loss/loss"]) - float(last_ce)) < 1e-5
    assert abs(float(state["exit_loss/obs/fit.exit_mass_last"]) - 1.0) < 1e-6
    assert np.isfinite(float(state["exit_loss/loss"]))


def test_beta_rewards_entropy():
    """objective(beta) = objective(0) - beta * mean H(p), H >= 0: the
    entropy term holds the exit distribution OPEN."""
    logits, gates, ids, _ = exit_inputs(seed=5)
    at = {beta: float(run_exit_op(exit_op(beta), logits, gates, ids)[1][
        "exit_loss/loss"]) for beta in (0.0, 0.1, 0.5)}
    p, logp = exit_distribution(jnp.stack([z[..., 0] for z in gates]))
    entropy = float(jnp.mean(-jnp.sum(p * logp, axis=0)[:, :-1]))
    assert 0 < entropy < np.log(T)
    assert at[0.1] == pytest.approx(at[0.0] - 0.1 * entropy, rel=1e-5)
    assert at[0.5] == pytest.approx(at[0.0] - 0.5 * entropy, rel=1e-5)


def test_whole_model_loss_and_every_gradient_equal_the_references(tiny):
    """float32 compute against the float32 reference: the loss within
    1e-5 relative, every gradient leaf within 1e-3 of its own largest
    entry (sums of float32 products in another order; a missing pass, a
    wrong mask or an unshared weight is of order 1)."""
    x, y = batch_of(2, 32, 96)
    got, got_grads = jax.value_and_grad(loss_of(tiny, x, y))(tiny.params)
    want, want_grads = jax.value_and_grad(
        lambda p: ref.loss(p, x, y))(tiny.params)
    assert abs(float(got) - float(want)) <= 1e-5 * abs(float(want))
    assert set(got_grads) == set(want_grads) == set(tiny.params)
    for op_name in sorted(want_grads):
        for w_name, b in want_grads[op_name].items():
            scale = float(jnp.max(jnp.abs(b)))
            assert scale > 0, (op_name, w_name)   # the gate's among them
            np.testing.assert_allclose(
                got_grads[op_name][w_name], b, rtol=0, atol=1e-3 * scale,
                err_msg=f"{op_name}/{w_name}")
    # the reported loss IS the objective, not the objective plus a
    # second cross-entropy of the last exit's logits
    logits, lam = ref.exits(tiny.params, x)
    np.testing.assert_allclose(
        tiny.compiled.forward_fn()(tiny.params, tiny.state, [jnp.asarray(x)]),
        logits[-1], rtol=1e-4, atol=1e-5)
    assert float(ref._token_nll(logits[-1], jnp.asarray(y)).mean()) > 1.0


# ---- (iv) bfloat16 against the float32 reference ----------------------------

def test_bfloat16_stays_inside_the_tolerances_and_int8_does_not():
    """tools/logits_check.py on a preset wide enough for the roundings to
    average (bfloat16 products against the float32 reference): the
    system reads under the tool's 0.025 and within the harness's 1e-4 of
    the step-0 loss; the reference with int8 weights reads past 0.025."""
    from benchmarks.harness import spec, train
    from tools import logits_check

    config = spec.load_json(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks", "configs", "tiny-ouro-train.json"))
    config["ffconfig"]["compute_dtype"] = "bfloat16"
    config["builder_kwargs"].update(hidden=128, num_heads=4, head_dim=32,
                                    ff_dim=256, seq_len=256, vocab=512)
    config["harness"].update(seq_len=256, vocab=512)
    out = logits_check.check(config, seed=2 ** 31 + 5,
                             controls=("bf16", "int8_per_tensor"))
    assert out["system_within_tolerance"] and out["int8_control_rejected"]
    assert 0 < out["system"]["rms_over_logit_std"] < logits_check.TOLERANCE
    assert out["controls"]["int8_per_tensor"]["rms_over_logit_std"] > (
        logits_check.TOLERANCE)
    model = train.build_model(config, 2, 2 ** 31 + 5)
    train.compile_for_training(model, config)
    x, y = train.lm_sequence_data(2, 256, 512, 2 ** 31 + 5)
    want = float(ref.loss(model.params, x, y))
    got = model.fit(x=x, y=y, epochs=1, shuffle=False, verbose=False)[0]["loss"]
    assert abs(got - want) <= train.STEP0_LOSS_RTOL * want


# ---- (v) recomputation ------------------------------------------------------

def strip_blocks(config, **kw):
    """``build_ouro`` whose ops say nothing of blocks: per-op ``remat``."""
    model = build_ouro(config, **kw)
    for node in model.graph.nodes.values():
        node.op.remat_block = None
    return model


def checkpoints_in(model, x, y) -> int:
    program = str(jax.make_jaxpr(jax.grad(loss_of(model, x, y)))(model.params))
    return program.count("checkpoint[") + program.count("remat2[")


def test_remat_gives_the_same_gradients_and_covers_tied_ops(tiny):
    x, y = batch_of(2, 32, 96)
    want_loss, want = jax.value_and_grad(loss_of(tiny, x, y))(tiny.params)
    # without remat only the objective checkpoints its cross-entropies
    assert not tiny.compiled._remat_blocks
    per_exit = checkpoints_in(tiny, x, y)
    assert per_exit == T
    weighted = sum(bool(tiny.params.get(n.op.weights_key))
                   for n in tiny.graph.nodes.values())
    assert weighted == 1 + T * (L * len(LAYER_OPS) + 3)
    for name, builder, expect in (
            # every op that reads weights, its own OR another op's
            ("op", strip_blocks, weighted),
            # one checkpoint a block (t, l); outside the blocks the
            # final norm, the head and the gate of every step, the table
            ("block", build_ouro, T * L + 1 + T * 3)):
        model = tiny_model(builder=builder, remat=True)
        assert len({id(b) for b in model.compiled._remat_blocks.values()}) == (
            T * L if name == "block" else 0)
        assert checkpoints_in(model, x, y) == per_exit + expect, name
        loss, got = jax.value_and_grad(loss_of(model, x, y))(tiny.params)
        assert abs(float(loss) - float(want_loss)) <= 1e-6 * float(want_loss)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(
                a, b, rtol=0, atol=1e-5 * float(jnp.max(jnp.abs(b))))


def test_a_block_that_cannot_run_as_one_checkpoint_falls_back_per_op():
    """A block whose value is read outside before its last op, or that
    holds a state-writing op, is not checkpointed as a whole."""
    config = ff.FFConfig(batch_size=2, seed=1, compute_dtype="float32",
                         num_devices=1, cost_cache_file="", remat=True)
    model = FFModel(config)
    x = model.create_tensor([2, 8, 16], name="x")
    with model.remat_block():
        a = model.dense(x, 16, name="a")
        b = model.dense(a, 16, name="b")
    with model.remat_block():
        c = model.dense(b, 16, name="c")
        early = model.dense(c, 16, name="early_reader_of_c_is_outside")
    with model.remat_block():
        d = model.batch_norm(model.reshape(c, [2, 8, 4, 4]), relu=False,
                             name="writes_state")
        d = model.reshape(d, [2, 8, 16])
    out = model.add(model.add(early, d), model.dense(c, 16, name="late"))
    model.dense(out, 4, name="head")
    model.compile(optimizer=ff.SGDOptimizer(lr=0.1),
                  loss_type="sparse_categorical_crossentropy", metrics=[])
    blocks = model.compiled._remat_blocks
    names = {model.graph.nodes[g].op.name for g in blocks}
    assert {"a", "b"} <= names and "writes_state" not in names
    xs = np.random.default_rng(0).normal(size=(2, 8, 16)).astype(np.float32)
    ys = np.zeros((2, 8), np.int32)
    hist = model.fit(x=xs, y=ys, epochs=2, shuffle=False, verbose=False)
    assert np.isfinite(hist[-1]["loss"])


# ---- (vi) one view for the ops of one weights_key ---------------------------

def test_on_four_devices_every_sharer_has_its_owners_view():
    model = tiny_model(batch=4, num_devices=4)
    assert model.strategy, "compile() searched"
    owners = {n.op.name: n for n in model.graph.nodes.values()}
    sharers = [n for n in model.graph.nodes.values()
               if n.op.weights_key != n.op.name]
    assert len(sharers) == (T - 1) * (L * len(LAYER_OPS) + 3)
    for node in sharers:
        owner = owners[node.op.weights_key]
        assert model.strategy[node.guid] == model.strategy[owner.guid], (
            node.op.name)
    assert model.plan.stats["tied_views_moved"] >= 0
    one = tiny_model(batch=4, num_devices=1)
    x, y = batch_of(16, 32, 96, seed=2)
    four = model.fit(x=x, y=y, epochs=1, shuffle=False, verbose=False)
    # fit reports the epoch's last step: four steps, the fourth's loss
    alone = one.fit(x=x, y=y, epochs=1, shuffle=False, verbose=False)
    assert abs(four[0]["loss"] - alone[0]["loss"]) <= 1e-5 * alone[0]["loss"]


def test_tie_views_moves_a_sharer_whose_view_differs():
    from flexflow_tpu.core.machine import MachineView
    from flexflow_tpu.search.plan import StrategyPlan

    model = build_ouro(ff.FFConfig(batch_size=4, num_devices=4), **TINY)
    graph = model.graph
    split = MachineView.data_parallel(3, 4)
    strategy = {}
    for node in graph.nodes.values():
        nd = node.op.output_shapes[0].ndim
        owned = node.op.weights_key == node.op.name
        strategy[node.guid] = (MachineView.data_parallel(nd, 4) if owned
                               else MachineView.trivial(nd))
    plan = StrategyPlan(graph, strategy, "caller")
    plan.tie_views()
    sharers = [n for n in graph.nodes.values()
               if n.op.weights_key != n.op.name]
    assert plan.stats["tied_views_moved"] == len(sharers) > 0
    assert all(plan.strategy[n.guid] == split for n in sharers
               if n.op.output_shapes[0].ndim == 3)


# ---- (vii) counters and gauges ----------------------------------------------

def test_fit_publishes_the_loop_counters_a_hand_count_gives():
    """With a learning rate of 0 the weights stay, so every epoch adds
    what the reference gives for the same batches."""
    model = tiny_model(optimizer=ff.SGDOptimizer(lr=0.0))
    x, y = batch_of(6, 32, 96, seed=4)
    METRICS.reset()
    model.fit(x=x, y=y, epochs=2, shuffle=False, verbose=False)
    snapshot = METRICS.snapshot()
    counters, gauges = snapshot["counters"], snapshot["gauges"]
    logits, lam = ref.exits(model.params, x)
    p = ref.exit_distribution(lam)[:, :, :-1]            # counted positions
    step = jnp.tensordot(jnp.arange(1.0, T + 1), p, axes=1)
    entropy = -jnp.sum(p * jnp.log(p), axis=0)
    positions = 6 * 31
    assert counters["loop.gated_tokens"] == 2 * positions
    # a position rounds to a thousandth: the sums agree to half of that each
    assert abs(counters["loop.exit_step_milli"]
               - 2 * 1000 * float(step.sum())) <= 2 * positions * 0.5 + 1
    assert abs(counters["loop.exit_entropy_milli"]
               - 2 * 1000 * float(entropy.sum())) <= 2 * positions * 0.5 + 1
    mean_step = counters["loop.exit_step_milli"] / counters[
        "loop.gated_tokens"] / 1000
    assert 1.0 < mean_step < T and abs(mean_step - float(step.mean())) < 1e-3
    # gauges: the last step's batch (rows 4..5)
    nll = jnp.stack([ref._token_nll(lg, jnp.asarray(y)) for lg in logits])
    for t in range(T):
        assert gauges[f"fit.exit_loss.{t + 1}"] == pytest.approx(
            float(nll[t, 4:, :-1].mean()), rel=1e-5)
    assert gauges["fit.exit_mass_last"] == pytest.approx(
        float(p[-1, 4:].mean()), rel=1e-5)
    # published where fit already reads the loss: a second publish of
    # the unchanged state adds nothing
    from flexflow_tpu.obs import device_counters

    device_counters.publish(model.state, model._obs_seen)
    assert METRICS.snapshot()["counters"]["loop.gated_tokens"] == 2 * positions


def test_the_loop_steps_and_the_exits_lower_under_their_name_scopes(tiny):
    x, y = batch_of(2, 32, 96)
    hlo = jax.jit(loss_of(tiny, x, y)).lower(tiny.params).as_text(
        debug_info=True)
    for scope in ("ff.loop1", "ff.loop4", "ff.exit"):
        assert scope in hlo, scope
    scopes = {n.op.name: n.op.block_scope for n in tiny.graph.nodes.values()}
    assert scopes["layer0_attn"] == "ff.loop1"
    assert scopes["loop3_layer1_ffn_down"] == "ff.loop3"
    assert scopes["loop2_final_norm"] == "ff.loop2"
    assert scopes["loop2_lm_head"] == scopes["exit_loss"] == "ff.exit"


# ---- (viii) checkpoints -----------------------------------------------------

def test_a_checkpoint_holds_each_tied_leaf_once(tiny, tmp_path):
    from flexflow_tpu.runtime.checkpoint import CheckpointManager

    x, y = batch_of(4, 32, 96, seed=9)
    model = tiny_model()
    model.fit(x=x, y=y, epochs=1, shuffle=False, verbose=False)
    manager = CheckpointManager(str(tmp_path), use_orbax=False)
    manager.save(2, model)
    stored = [f for _, _, files in os.walk(tmp_path) for f in files]
    assert stored and not any("loop2" in f for f in stored)
    saved = {k: {w: np.asarray(v) for w, v in ws.items()}
             for k, ws in model.params.items()}
    fresh = tiny_model()
    manager.restore(fresh)
    assert set(fresh.params) == set(saved) == set(tiny.params)
    for name, ws in saved.items():
        for w, v in ws.items():
            np.testing.assert_array_equal(np.asarray(fresh.params[name][w]), v)
    again = fresh.fit(x=x, y=y, epochs=1, shuffle=False, verbose=False)
    assert np.isfinite(again[0]["loss"])
