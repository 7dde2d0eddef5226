"""Plain reference of the Trinity (``afmoe``) language model as the zoo's
``build_afmoe_decode`` serves it on one chip of an expert-parallel
group: float32 throughout, ``default_matmul_precision("highest")``, a
full causal forward over the whole sequence — no cache, no pages, no
kernel, no dispatch, no batching of requests — the published
mathematics.

    h0 = Emb[ids] * sqrt(hidden)                               (mup_enabled)
    layer:  h <- h + N2(Attn(N1 h));  h <- h + N4(FFN(N3 h))   (sandwich norms)
    N(x) = x * rsqrt(mean(x^2) + 1e-5) * gamma; no bias anywhere
    Attn(x): q = Nq(x Wq) as Hq heads of D, k = Nk(x Wk), v = x Wv as Hkv
        heads of D (query head h reads key/value head h // (Hq / Hkv));
        Nq, Nk over the D of a head; in a SLIDING layer q and k turn by
        their positions (half-split: pair (x_i, x_{i+D/2}) by
        pos * theta^(-2i/D)), in a FULL layer nothing carries a position;
        causal softmax(q.k / sqrt(D)) — in a sliding layer position j is
        seen from i iff i - window < j <= i; output
        (sigmoid(x Wg) * o) Wo
    FFN of the leading dense layers: Wdown(silu(Wgate x) * Wup x)
    FFN of the expert layers: s = sigmoid(x Wr) over ALL experts of the
        router's width; the top k of s + b are chosen (b a stored bias,
        used for the choice only) and weighed s_i / (sum of the chosen s
        + 1e-20) * route_scale;
        y = sum over experts CHOSEN AND HELD of w_i E_i(x) + E_shared(x)
    logits = N(h_L) W_head

The share: the experts' stacked kernels hold ``E_held`` experts, which
are experts ``expert_offset .. expert_offset + E_held - 1`` of the
router's width; what the others would add is left out, here as in the
program.  Held experts are computed DENSELY for every token and weighed
by the routing weight, one expert at a time; attention a block of
queries at a time; every weight is upcast where it is used, one leaf at
a time — so a 4,808-token probe fits beside the server that holds the
same weights in bfloat16.

It reads the system's parameters by op name: ``layer{i}_attn_window``
is a sliding layer, ``layer{i}_attn_global`` a full one;
``layer{i}_ffn_gate`` a dense layer, ``layer{i}_moe_router`` an expert
layer.  Heads and widths are read off the shapes.  What neither tells is
in ``Spec``, at the published values by default.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp


class Spec(NamedTuple):
    window: int = 4096          # sliding_window
    top_k: int = 4              # num_experts_per_tok
    route_scale: float = 2.448  # route_scale
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    expert_offset: int = 0      # the first expert held (the share)


PUBLISHED = Spec()
Q_BLOCK = 512  # queries a block of the reference attention


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def rms(x, gamma, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _f32(gamma)


def rotary(x, theta):
    """x [B, S, H, D] at positions 0..S-1, half-split pairs."""
    s, d = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("window",))
def _attend_block(qb, k, v, start, window):
    """qb [B, Q, Hkv, G, D] at positions start.., k, v [B, S, Hkv, D] ->
    [B, Q, Hkv, G, D]."""
    d = qb.shape[-1]
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qb, k) / math.sqrt(d)
    rows = start + jnp.arange(qb.shape[1])[:, None]
    cols = jnp.arange(k.shape[1])[None, :]
    seen = cols <= rows
    if window:
        seen &= cols > rows - window
    scores = jnp.where(seen, scores, -jnp.inf)
    return jnp.einsum("bhgqk,bkhd->bqhgd", jax.nn.softmax(scores, axis=-1), v)


def causal_attention(q, k, v, window):
    """q [B, S, Hq, D], k, v [B, S, Hkv, D] -> [B, S, Hq, D]; a block of
    queries at a time (the last block padded)."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    qg = q.reshape(b, s, hkv, hq // hkv, d)
    pad = -s % Q_BLOCK if s > Q_BLOCK else 0
    qg = jnp.pad(qg, ((0, 0), (0, pad)) + ((0, 0),) * 3)
    block = min(Q_BLOCK, s)
    out = [_attend_block(qg[:, i:i + block], k, v, i, window)
           for i in range(0, s + pad, block)]
    return jnp.concatenate(out, axis=1)[:, :s].reshape(b, s, hq, d)


@functools.partial(jax.jit, static_argnames=("sliding", "spec"))
def _attention(x, p, sliding, spec):
    d = p["q_norm"].shape[0]
    lead = x.shape[:2]
    q = (x @ _f32(p["wq"])).reshape(*lead, -1, d)
    k = (x @ _f32(p["wk"])).reshape(*lead, -1, d)
    v = (x @ _f32(p["wv"])).reshape(*lead, -1, d)
    q, k = rms(q, p["q_norm"], spec.rms_eps), rms(k, p["k_norm"], spec.rms_eps)
    if sliding:
        q, k = rotary(q, spec.rope_theta), rotary(k, spec.rope_theta)
    return q, k, v, jax.nn.sigmoid(x @ _f32(p["wg"]))


@jax.jit
def _gated(x, gate, up, down):
    return (jax.nn.silu(x @ _f32(gate)) * (x @ _f32(up))) @ _f32(down)


@functools.partial(jax.jit, static_argnames=("spec",))
def routing_weights(x, router, spec):
    """[..., E]: each token's weight for every expert of the router's
    width, 0 where the expert is not among its ``top_k``."""
    s = jax.nn.sigmoid(x @ _f32(router["kernel"]))
    # the bias is the system's STATE (zeros until a balancing rule moves
    # it); a caller that has one hands it in beside the kernel
    _, chosen = jax.lax.top_k(s + router.get("bias", 0.0), spec.top_k)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    w = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    w = w * spec.route_scale
    return jnp.zeros_like(s).at[
        tuple(jnp.indices(chosen.shape)[:-1]) + (chosen,)].set(w)


@jax.jit
def _one_expert(x, w, gate, up, down, e):
    return w[..., None] * _gated(x, gate[e], up[e], down[e])


def routed_experts(x, params, name, spec):
    """What the experts held here add: sum over held experts of
    weight * E(x), one expert at a time."""
    weights = routing_weights(x, params[f"{name}_moe_router"], spec)
    gate, up, down = (params[f"{name}_moe_experts_{part}"]["kernel"]
                      for part in ("gate", "up", "down"))
    y = jnp.zeros_like(x)
    for e in range(gate.shape[0]):
        y = y + _one_expert(x, weights[..., spec.expert_offset + e],
                            gate, up, down, e)
    return y


def shared_expert(x, params, name):
    return _gated(x, *(params[f"{name}_shared_{part}"]["kernel"]
                       for part in ("gate", "up", "down")))


def attention(x, params, name, spec):
    sliding = f"{name}_attn_window" in params
    p = params[f"{name}_attn_window" if sliding else f"{name}_attn_global"]
    q, k, v, gate = _attention(x, p, sliding, spec)
    o = causal_attention(q, k, v, spec.window if sliding else 0)
    return (gate * o.reshape(gate.shape)) @ _f32(p["wo"])


def ffn(x, params, name, spec):
    if f"{name}_ffn_gate" in params:
        return _gated(x, *(params[f"{name}_ffn_{part}"]["kernel"]
                           for part in ("gate", "up", "down")))
    return (routed_experts(x, params, name, spec)
            + shared_expert(x, params, name))


def layer(h, params, name, spec):
    def norm(x, part):
        return rms(x, params[f"{name}_{part}"]["gamma"], spec.rms_eps)

    h = h + norm(attention(norm(h, "attn_norm"), params, name, spec),
                 "attn_out_norm")
    return h + norm(ffn(norm(h, "ffn_norm"), params, name, spec),
                    "ffn_out_norm")


def forward(params, ids, spec: Spec = PUBLISHED):
    """ids [B, S] int32 -> logits [B, S, vocab] float32."""
    with jax.default_matmul_precision("highest"):
        table = params["tok_embed"]["table"]
        h = _f32(table[jnp.asarray(ids, jnp.int32)]) * math.sqrt(
            table.shape[1])
        layers = sum(k.startswith("layer") and k.endswith("_attn_norm")
                     for k in params)
        for i in range(layers):
            h = layer(h, params, f"layer{i}", spec)
        h = rms(h, params["final_norm"]["gamma"], spec.rms_eps)
        return h @ _f32(params["lm_head"]["kernel"])
