"""``build_joyai_flash`` and every op it brought, at tiny widths in
float32 on the CPU, against the plain reference's own functions
(benchmarks/reference/joyai_flash.py): RMS norm, interleaved rotary,
latent attention through the XLA path and through the flash kernels
(interpreter mode, value heads narrower than query/key heads), the
sigmoid router, an expert layer told which experts it holds, the MTP
loss; the whole model's loss and gradients; the share test; the search.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flexflow_tpu as ff
from benchmarks.reference import joyai_flash as ref
from flexflow_tpu.core.ptensor import ParallelTensorShape
from flexflow_tpu.models import build_joyai_flash
from flexflow_tpu.obs.metrics import METRICS
from flexflow_tpu.ops import (
    ExpertCombineOp,
    ExpertDispatchOp,
    ExpertLinearOp,
    LatentAttentionOp,
    LoweringContext,
    MoERouterOp,
    NextTokenLossOp,
    RMSNormOp,
    ShiftOp,
)
from flexflow_tpu.ops.latent_attention import interleaved_rotary

TINY = dict(vocab=96, num_layers=3, hidden=32, num_heads=2, q_lora_rank=24,
            kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, dense_ff_dim=64, expert_ff_dim=16,
            n_routed_experts=32, experts_held=8, experts_per_token=8,
            seq_len=32)


def ctx32(state=None):
    return LoweringContext(compute_dtype=jnp.float32, train=True,
                           state_in=state or {})


def shape(*sizes, dtype="float32"):
    return ParallelTensorShape.make(sizes, dtype)


def seeded(op, seed=0, **override):
    """An op's weights from its own initializers, some overridden."""
    keys = jax.random.split(jax.random.key(seed), len(op._weight_specs) or 1)
    ws = {w.name: w.initializer.init(k, w.shape, jnp.float32)
          for w, k in zip(op._weight_specs, keys)}
    ws.update(override)
    return ws


def normal(seed, *sizes, scale=1.0):
    return scale * jax.random.normal(jax.random.key(seed), sizes, jnp.float32)


def test_rms_norm_is_the_references():
    x, gamma = normal(0, 2, 5, 32, scale=3.0), 1.0 + normal(1, 32, scale=0.1)
    op = RMSNormOp("n", [shape(2, 5, 32)], eps=1e-6)
    got = op.forward(ctx32(), [x], {"gamma": gamma})[0]
    np.testing.assert_allclose(got, ref.rms(x, gamma), rtol=1e-6, atol=1e-6)
    # no mean is subtracted: a constant row keeps its sign and size
    flat = op.forward(ctx32(), [jnp.full((1, 1, 32), 2.0)],
                      {"gamma": jnp.ones(32)})[0]
    np.testing.assert_allclose(flat, 1.0, rtol=1e-5)


def test_interleaved_rotary_is_the_references():
    x = normal(2, 2, 16, 3, 8)
    np.testing.assert_allclose(interleaved_rotary(x, 32e6), ref.rotary(x),
                               rtol=1e-5, atol=1e-6)
    # position 0 is left alone; pair i of position p turns by p * theta^(-2i/R)
    np.testing.assert_allclose(interleaved_rotary(x, 1e4)[:, 0], x[:, 0])
    one = interleaved_rotary(jnp.zeros((1, 4, 1, 4)).at[..., 2].set(1.0), 100.0)
    angle = 3 * 100.0 ** (-2 / 4)
    np.testing.assert_allclose(one[0, 3, 0], [0, 0, np.cos(angle), np.sin(angle)],
                               atol=1e-6)


@pytest.mark.parametrize("seq", [48, 512], ids=["xla", "flash-interpreted"])
def test_latent_attention_forward_and_gradients(seq):
    """Query/key heads 16 + 8 = 24 wide beside value heads 40 wide (wider,
    here; narrower in the next test): W_kvb splits [k_nope 16 | v 40].  At
    48 positions the op takes the XLA path, at 512 the three flash kernels
    (``flash_profitable``), interpreted on the CPU."""
    op = LatentAttentionOp(
        "mla", [shape(1, seq, 32)], num_heads=2, q_lora_rank=24,
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=40, rope_theta=32e6)
    ws = seeded(op, 3, q_norm=1.0 + normal(4, 24, scale=0.1),
                kv_norm=1.0 + normal(5, 16, scale=0.1))
    x, g = normal(6, 1, seq, 32), normal(7, 1, seq, 32)

    def system(x, ws):
        return jnp.sum(op.forward(ctx32(), [x], ws)[0] * g)

    def reference(x, ws):
        with jax.default_matmul_precision("highest"):
            return jnp.sum(ref.mla(x, ws) * g)

    np.testing.assert_allclose(op.forward(ctx32(), [x], ws)[0], ref.mla(x, ws),
                               rtol=2e-4, atol=2e-5)
    got = jax.grad(system, argnums=(0, 1))(x, ws)
    want = jax.grad(reference, argnums=(0, 1))(x, ws)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4)


def test_latent_attention_value_width_differs_from_query_width():
    """192 beside 128 in small: q/k heads 16 + 8 = 24 wide, v heads 16."""
    op = LatentAttentionOp(
        "mla", [shape(1, 512, 32)], num_heads=2, q_lora_rank=24,
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, rope_theta=32e6)
    ws = seeded(op, 8)
    assert ws["w_kvb"].shape == (16, 2, 32) and ws["w_o"].shape == (2, 16, 32)
    x = normal(9, 1, 512, 32)
    np.testing.assert_allclose(op.forward(ctx32(), [x], ws)[0], ref.mla(x, ws),
                               rtol=2e-4, atol=2e-5)


def test_router_chooses_on_score_plus_bias_and_weighs_by_score():
    op = MoERouterOp("r", [shape(2, 6, 32)], n_experts=16, k=4, scale=2.5)
    ws, bias = seeded(op, 10), normal(11, 16, scale=0.5)
    assert set(ws) == {"kernel"}          # the bias is state, no parameter
    x = normal(12, 2, 6, 32)
    ctx = ctx32({"r/bias": bias})
    w, chosen = op.forward(ctx, [x], ws)
    assert "r/bias" not in ctx.state_out       # read, never written
    scores = np.asarray(jax.nn.sigmoid(x @ ws["kernel"]))
    by_biased = np.argsort(-(scores + np.asarray(bias)), axis=-1)[..., :4]
    by_score = np.argsort(-scores, axis=-1)[..., :4]
    assert np.array_equal(np.sort(chosen, -1), np.sort(by_biased, -1))
    assert not np.array_equal(np.sort(by_biased, -1), np.sort(by_score, -1))
    picked = np.take_along_axis(scores, np.asarray(chosen), -1)
    np.testing.assert_allclose(
        w, picked / picked.sum(-1, keepdims=True) * 2.5, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.5, rtol=1e-6)
    # ... which is the reference's dense weight matrix
    dense = np.zeros((2, 6, 16), np.float32)
    np.put_along_axis(dense, np.asarray(chosen), np.asarray(w), -1)
    np.testing.assert_allclose(
        dense, ref.routing_weights(x, dict(ws, bias=bias), 4, 2.5),
        rtol=1e-6, atol=1e-7)
    grads = jax.grad(lambda ws: jnp.sum(
        op.forward(ctx32({"r/bias": bias}), [x], ws)[0] ** 2))(ws)
    assert np.any(np.asarray(grads["kernel"]) != 0)


def expert_layer(tokens, d, ff, n_experts, held, offset, rows, k=4):
    """The system's routed part as the zoo wires it, on flat tokens."""
    router = MoERouterOp("r", [shape(tokens, d)], n_experts=n_experts, k=k,
                         scale=2.5, experts_held=held)
    dispatch = ExpertDispatchOp(
        "d", [shape(tokens, d), shape(tokens, k, dtype="int32")],
        n_experts=n_experts, experts_held=held, rows=rows,
        expert_offset=offset)
    sizes = shape(held, dtype="int32")
    gate = ExpertLinearOp("g", [shape(rows, d), sizes], out_dim=ff,
                          activation="silu")
    up = ExpertLinearOp("u", [shape(rows, d), sizes], out_dim=ff)
    down = ExpertLinearOp("w", [shape(rows, ff), sizes], out_dim=d)
    combine = ExpertCombineOp("c", [shape(tokens, k),
                                    shape(rows, dtype="int32"),
                                    shape(rows, d)])

    def run(x, p):
        state = {f"d/{n}": jnp.int32(0) for n, *_ in dispatch.state_specs()}
        ctx = ctx32(dict(state, **{"r/bias": p["router"]["bias"]}))
        w, chosen = router.forward(ctx, [x], {"kernel": p["router"]["kernel"]})
        sorted_rows, source, sizes = dispatch.forward(ctx, [x, chosen], {})
        h = (gate.forward(ctx, [sorted_rows, sizes], {"kernel": p["gate"]})[0]
             * up.forward(ctx, [sorted_rows, sizes], {"kernel": p["up"]})[0])
        out = down.forward(ctx, [h, sizes], {"kernel": p["down"]})[0]
        return combine.forward(ctx, [w, source, out], {})[0], ctx.state_out

    return router, run


def expert_weights(router, n_experts, d, ff, seed, skew=0.0):
    kernel = normal(seed, d, n_experts, scale=0.3)
    # experts 0 and 1 draw far more tokens than the rest
    kernel = kernel.at[:, :2].add(skew)
    return {"router": {"kernel": kernel, "bias": normal(seed + 1, n_experts,
                                                        scale=0.2)},
            "gate": normal(seed + 2, n_experts, d, ff, scale=0.2),
            "up": normal(seed + 3, n_experts, d, ff, scale=0.2),
            "down": normal(seed + 4, n_experts, ff, d, scale=0.2)}


def held(p, offset, n):
    return dict(p, **{k: p[k][offset:offset + n] for k in ("gate", "up", "down")})


def test_expert_layer_with_uneven_routing_drops_nothing():
    tokens, d, ff, n_experts = 64, 16, 8, 16
    router, run = expert_layer(tokens, d, ff, n_experts, held=4, offset=0,
                               rows=160)
    p = expert_weights(router, n_experts, d, ff, 20, skew=0.4)
    x = jnp.abs(normal(25, tokens, d))
    y, counted = run(x, held(p, 0, 4))
    load = np.asarray(ref.routing_weights(x, p["router"], 4, 2.5) > 0)[
        :, :4].sum(0)
    # uneven, on purpose
    assert load.max() > 1.5 * load.mean() and load.min() < 0.5 * load.mean()
    assert int(counted["d/obs/moe.assignments"]) == load.sum()
    assert int(counted["d/obs/moe.expert_load_max"]) == load.max()
    assert int(counted["d/obs/moe.assignments_dropped"]) == 0
    # the bound is the chip's: the fullest expert takes more than a
    # quarter of it and nothing is cut
    assert load.max() > 160 // 4 and load.sum() <= 160
    assert int(counted["d/obs/moe.row_slots"]) == 160
    assert int(counted["d/obs/moe.rows_filled"]) == load.sum()
    assert int(counted["d/obs/moe.rows_at_fullest_load"]) == 4 * load.max()
    want = ref.routed_part(x, p["router"], p["gate"][:4], p["up"][:4],
                           p["down"][:4], 0, 4, 2.5)
    np.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-6)
    # gradients flow to the held experts, the router and the tokens
    gsys = jax.grad(lambda x, p: jnp.sum(run(x, p)[0] ** 2), (0, 1))(
        x, held(p, 0, 4))
    gref = jax.grad(lambda x, p: jnp.sum(ref.routed_part(
        x, p["router"], p["gate"], p["up"], p["down"], 0, 4, 2.5) ** 2),
        (0, 1))(x, held(p, 0, 4))
    for a, b in zip(jax.tree.leaves(gsys), jax.tree.leaves(gref)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)
    # a share's partial sum teaches its router nothing, in both
    assert not np.any(np.asarray(gsys[1]["router"]["kernel"]))
    assert not np.any(np.asarray(gref[1]["router"]["kernel"]))


def test_a_router_whose_layer_holds_every_expert_learns():
    tokens, d, ff, n_experts = 32, 16, 8, 8
    router, run = expert_layer(tokens, d, ff, n_experts, held=n_experts,
                               offset=0, rows=tokens * 4)
    p = expert_weights(router, n_experts, d, ff, 50)
    x = normal(55, tokens, d)
    gsys = jax.grad(lambda p: jnp.sum(run(x, p)[0] ** 2))(p)
    gref = jax.grad(lambda p: jnp.sum(ref.routed_part(
        x, p["router"], p["gate"], p["up"], p["down"], 0, 4, 2.5) ** 2))(p)
    assert np.any(np.asarray(gref["router"]["kernel"]))
    for a, b in zip(jax.tree.leaves(gsys), jax.tree.leaves(gref)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


def test_an_assignment_past_the_row_bound_is_counted_not_lost_silently():
    """The chip's bound cuts the TAIL of the sorted rows — the last held
    experts' last arrivals — and every cut assignment is counted; what
    the kept rows give is the reference's part with those left out."""
    tokens, d, ff, n_experts = 64, 16, 8, 16
    router, run = expert_layer(tokens, d, ff, n_experts, held=4, offset=0,
                               rows=40)
    p = expert_weights(router, n_experts, d, ff, 20, skew=0.4)
    x = jnp.abs(normal(25, tokens, d))
    y, counted = run(x, held(p, 0, 4))
    w = np.asarray(ref.routing_weights(x, p["router"], 4, 2.5))[:, :4]
    load = (w > 0).sum(0)
    assert load.sum() > 40
    assert int(counted["d/obs/moe.assignments"]) == load.sum()
    assert int(counted["d/obs/moe.assignments_dropped"]) == load.sum() - 40
    assert int(counted["d/obs/moe.row_slots"]) == 40
    assert int(counted["d/obs/moe.rows_filled"]) == 40
    # expert by expert, token by token: the first 40 are kept
    kept = np.zeros_like(w)
    order = [(e, t) for e in range(4) for t in range(tokens) if w[t, e] > 0]
    for e, t in order[:40]:
        kept[t, e] = w[t, e]
    want = sum(kept[:, e:e + 1] * ref.gated(x, p["gate"][e], p["up"][e],
                                            p["down"][e]) for e in range(4))
    np.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-6)


def test_the_shares_add_up_to_the_uncut_layer():
    """THE share test: four chips hold four experts each of a 16-expert
    layer; the routed parts they compute, plus the shared expert counted
    once, are what the uncut reference layer gives for every token."""
    tokens, d, ff, n_experts, per_chip = 48, 16, 8, 16, 4
    router, _ = expert_layer(tokens, d, ff, n_experts, per_chip, 0,
                             tokens * 4)
    p = expert_weights(router, n_experts, d, ff, 30)
    shared = [normal(35, d, ff, scale=0.2), normal(36, d, ff, scale=0.2),
              normal(37, ff, d, scale=0.2)]
    x = normal(38, tokens, d)
    total = ref.gated(x, *shared)
    for chip in range(n_experts // per_chip):
        _, run = expert_layer(tokens, d, ff, n_experts, per_chip,
                              chip * per_chip, tokens * 4)
        part, counted = run(x, held(p, chip * per_chip, per_chip))
        assert int(counted["d/obs/moe.assignments_dropped"]) == 0
        # and each share is the reference's part at that offset
        np.testing.assert_allclose(part, ref.routed_part(
            x, p["router"], *(held(p, chip * per_chip, per_chip)[k]
                              for k in ("gate", "up", "down")),
            chip * per_chip, 4, 2.5), rtol=2e-5, atol=2e-6)
        total = total + part
    uncut = ref.routed_part(x, p["router"], p["gate"], p["up"], p["down"],
                            0, 4, 2.5) + ref.gated(x, *shared)
    np.testing.assert_allclose(total, uncut, rtol=2e-5, atol=2e-6)
    # every token's k assignments landed on exactly one chip each
    w = np.asarray(ref.routing_weights(x, p["router"], 4, 2.5))
    assert np.all((w > 0).sum(-1) == 4)


def test_mtp_targets_are_the_ids_two_ahead_and_the_tail_is_masked():
    b, s, v = 2, 6, 5
    ids = jnp.asarray(np.random.default_rng(1).integers(0, v, (b, s)), jnp.int32)
    shift = ShiftOp("s", [shape(b, s, dtype="int32")], by=1)
    ahead = np.asarray(shift.forward(ctx32(), [ids], {})[0])
    assert np.array_equal(ahead[:, :-1], np.asarray(ids)[:, 1:])
    assert np.all(ahead[:, -1] == 0)
    op = NextTokenLossOp("m", [shape(b, s, v), shape(b, s, v),
                               shape(b, s, dtype="int32")], shift=2, weight=0.3)
    logits, ahead_logits = normal(2, b, s, v), normal(3, b, s, v)
    ctx = ctx32()
    out = op.forward(ctx, [logits, ahead_logits, ids], {})[0]
    assert out is logits                      # handed through: the sink
    logp = np.asarray(jax.nn.log_softmax(ahead_logits, -1))
    want = -np.mean([logp[i, j, int(ids[i, j + 2])]
                     for i in range(b) for j in range(s - 2)])
    np.testing.assert_allclose(ctx.state_out["m/obs/fit.mtp_loss"], want,
                               rtol=1e-6)
    np.testing.assert_allclose(ctx.state_out["m/aux_loss"], 0.3 * want,
                               rtol=1e-6)
    # the two masked positions do not move it
    moved = ahead_logits.at[:, -2:].add(7.0)
    ctx2 = ctx32()
    op.forward(ctx2, [logits, moved, ids], {})
    np.testing.assert_allclose(ctx2.state_out["m/aux_loss"],
                               ctx.state_out["m/aux_loss"], rtol=1e-6)


def tiny_model(num_devices=1, batch=2, **kw):
    """Compiled, with every router's correction bias seeded non-zero, so
    that choosing on score + bias differs from weighing by score."""
    cfg = ff.FFConfig(batch_size=batch, seed=3, compute_dtype="float32",
                      num_devices=num_devices, cost_cache_file="")
    model = build_joyai_flash(cfg, **dict(TINY, **kw))
    model.compile(optimizer=ff.AdamOptimizer(alpha=1e-3),
                  loss_type="sparse_categorical_crossentropy", metrics=[])
    for i, key in enumerate(sorted(k for k in model.state
                                   if k.endswith("_router/bias"))):
        model.set_state_var(key, np.asarray(normal(40 + i, 32, scale=0.1)))
    return model


def with_biases(model):
    """The parameters as the reference wants them: each router's bias,
    which the system keeps in its state, beside its kernel."""
    params = dict(model.params)
    for key, bias in model.state.items():
        if key.endswith("_router/bias"):
            name = key[:-len("/bias")]
            params[name] = dict(params[name], bias=bias)
    return params


def batch_of(batch, seq, vocab, seed=0):
    x = np.random.default_rng(seed).integers(0, vocab, (batch, seq)).astype(
        np.int32)
    return x, np.roll(x, -1, axis=1)


@pytest.fixture(scope="module")
def tiny():
    return tiny_model()


def test_whole_model_loss_and_every_gradient_equal_the_references(tiny):
    """float32 compute against the float32 reference: the loss within
    1e-5 relative, every gradient leaf within 1e-3 of its own largest
    entry (sums of a few thousand float32 products in another order;
    a missing term, a wrong mask or a wrong shift is of order 1)."""
    x, y = batch_of(2, 32, 96)
    compiled = tiny.compiled

    def system(params):
        logits, state = compiled.apply(params, tiny.state, [jnp.asarray(x)],
                                       None, train=True)
        return compiled._loss_from(logits, jnp.asarray(y), state)

    got, got_grads = jax.value_and_grad(system)(tiny.params)
    want, want_grads = jax.value_and_grad(
        lambda p: ref.loss(p, x, y))(with_biases(tiny))
    assert abs(float(got) - float(want)) <= 1e-5 * abs(float(want))
    # the seeded biases matter: without them the reference reads another loss
    assert abs(float(ref.loss(tiny.params, x, y)) - float(want)) > 1e-4
    main, mtp = ref.losses(with_biases(tiny), x, y)
    assert float(mtp) > 1.0 and abs(float(main) + 0.3 * float(mtp)
                                    - float(want)) < 1e-5
    assert set(got_grads) == set(want_grads) == set(tiny.params)
    for op_name in sorted(want_grads):
        for w_name, b in want_grads[op_name].items():
            scale = float(jnp.max(jnp.abs(b)))
            if w_name == "bias" and "router" in op_name:
                assert scale == 0.0          # chosen on it, no gradient
                continue
            a = got_grads[op_name][w_name]
            if op_name.endswith("_router"):
                # 8 of 32 experts held: the share teaches the router nothing
                assert scale == 0.0 and not np.any(np.asarray(a))
                continue
            assert scale > 0, (op_name, w_name)
            np.testing.assert_allclose(a, b, rtol=0, atol=1e-3 * scale,
                                       err_msg=f"{op_name}/{w_name}")


def test_embedding_and_head_are_one_copy_read_twice(tiny):
    assert "mtp_embed" not in tiny.params and "mtp_head" not in tiny.params
    assert tiny.params["tok_embed"]["table"].shape == (96, 32)
    assert tiny.params["lm_head"]["kernel"].shape == (32, 96)
    # the MTP module's gradient reaches the shared table: with the main
    # loss switched off, the table still moves
    x, y = batch_of(2, 32, 96)

    def aux_only(params):
        _, state = tiny.compiled.apply(params, tiny.state, [jnp.asarray(x)],
                                       None, train=True)
        return state["mtp_loss/aux_loss"]

    grads = jax.grad(aux_only)(tiny.params)
    assert float(jnp.max(jnp.abs(grads["tok_embed"]["table"]))) > 0
    assert float(jnp.max(jnp.abs(grads["lm_head"]["kernel"]))) > 0


def test_fit_trains_and_publishes_the_device_counters(tiny):
    METRICS.reset()
    x, y = batch_of(8, 32, 96, seed=1)
    first = tiny.fit(x=x, y=y, epochs=1, shuffle=False, verbose=False)
    again = tiny.fit(x=x, y=y, epochs=3, shuffle=False, verbose=False)
    assert again[-1]["loss"] < first[0]["loss"]
    counters = METRICS.snapshot()["counters"]
    steps = 4 * 4                                  # 4 epochs x 4 batches
    # three expert layers (two in the trunk, the MTP module's), 8 of 32
    # experts held, never-drop rows = the batch's 64 tokens x 8 choices
    assert counters["moe.row_slots"] == steps * 3 * 64 * 8
    assert 0 < counters["moe.assignments"] < steps * 3 * 64 * 8
    assert counters["moe.assignments_dropped"] == 0
    assert counters["moe.expert_load_max"] * 8 == counters[
        "moe.rows_at_fullest_load"] >= counters["moe.assignments"]
    assert METRICS.snapshot()["gauges"]["fit.mtp_loss"] > 0
    # a second publish of an unchanged state adds nothing
    from flexflow_tpu.obs import device_counters

    device_counters.publish(tiny.state, tiny._obs_seen)
    assert METRICS.snapshot()["counters"]["moe.row_slots"] == steps * 3 * 64 * 8


def test_the_correction_bias_is_state_that_training_leaves_as_it_was(tiny):
    """No gradient reaches it and no rule moves it (its update rule is a
    training-loop heuristic the config does not give): after optimizer
    steps every router still holds the bias it was seeded with."""
    seeded_biases = {k: np.asarray(v) for k, v in tiny.state.items()
                     if k.endswith("_router/bias")}
    assert len(seeded_biases) == 3 and all(
        np.any(b != 0) for b in seeded_biases.values())
    x, y = batch_of(4, 32, 96, seed=5)
    tiny.fit(x=x, y=y, epochs=1, shuffle=False, verbose=False)
    for key, bias in seeded_biases.items():
        assert np.array_equal(np.asarray(tiny.state[key]), bias)


def test_the_search_returns_a_strategy_that_runs_on_four_devices():
    model = tiny_model(num_devices=4, batch=4)
    assert model.strategy, "compile() searched"
    x, y = batch_of(8, 32, 96, seed=2)
    want = float(ref.loss(with_biases(model), x[:4], y[:4]))
    history = model.fit(x=x[:4], y=y[:4], epochs=1, shuffle=False,
                        verbose=False)
    assert abs(history[0]["loss"] - want) <= 1e-4 * want
    # every op the model brought prices itself and says how it splits
    for node in model.graph.nodes.values():
        op = node.op
        assert op.flops() >= 0 and isinstance(op.splittable_output_dims(), tuple)


def test_the_logits_check_tells_the_roundings_apart():
    """tools/logits_check.py on the tiny preset (float32 compute, so the
    system sits on the reference): the controls order themselves bf16 <
    int8 < fp8, and the tolerance lies above bf16 and below fp8 — on the
    chip, at the configuration's own widths, below int8 too (PERF.md)."""
    import os

    from benchmarks.harness import spec
    from tools import logits_check

    config = spec.load_json(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks", "configs", "tiny-joyai-flash-train.json"))
    out = logits_check.check(config, seed=2 ** 31 + 5)
    assert out["system_within_tolerance"]
    assert out["system"]["rms_over_logit_std"] < 1e-5
    bf16, int8, fp8 = (out["controls"][k]["rms_over_logit_std"]
                       for k in ("bf16", "int8_per_tensor", "fp8_e4m3"))
    assert 0 < bf16 < int8 < fp8
    assert bf16 < logits_check.TOLERANCE < fp8
