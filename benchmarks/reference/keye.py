"""Plain reference of Keye-VL-2.0's language model as the zoo's
``build_keye_vl_decode`` serves it on one chip of an expert-parallel
group: float32 throughout, ``default_matmul_precision("highest")``, a
full causal forward over the whole sequence — no cache, no pages, no
kernel, no dispatch, no batching of requests — the published
mathematics.

    h0 = Emb[ids]
    layer:  h <- h + Attn(N1 h);  h <- h + MoE(N2 h)
    N(x) = x * rsqrt(mean(x^2) + 1e-6) * gamma; no bias anywhere
    Attn(x): q = Nq(x Wq) as Hq heads of D, k = Nk(x Wk), v = x Wv as Hkv
        heads of D (query head h reads key/value head h // (Hq / Hkv));
        q and k turn by their positions (half-split: pair (x_i, x_{i+D/2})
        by pos * theta^(-2i/D));
        the indexer: qI = x WqI as Hi heads of Di, kI = LN(x WkI) (mean
        and variance over Di, gain and bias), the leading R of each turn
        as q and k do over R; wI = (x Ww) / sqrt(Hi);
        I[t, s] = sum_j wI[t, j] max(qI[t, j] . kI[s], 0) / sqrt(Di);
        S_t = the top_k s <= t of largest I[t, s] (lax.top_k over the
        causal scores, the rest at -inf; all s <= t where fewer);
        o_t = softmax over s in S_t of q_t . k_s / sqrt(D), times v_s;
        Attn = o Wo
    MoE(x): p = softmax(x Wr) over ALL experts of the router's width;
        the top k of p chosen, g = p / sum of the chosen;
        y = sum over experts CHOSEN AND HELD of g_e E_e(x),
        E(x) = Wdown(silu(Wgate x) * Wup x)
    logits = N(h_L) W_head

The share: the experts' stacked kernels hold ``E_held`` experts, which
are experts ``expert_offset .. expert_offset + E_held - 1`` of the
router's width; what the others would add is left out, here as in the
program.  Held experts are computed DENSELY for every token and weighed
by the routing weight, one expert at a time; attention a block of
``Q_BLOCK`` queries at a time against every key, the choice made per
query inside the block; every weight is upcast where it is used — so an
8,192-token probe fits beside the server that holds the same weights in
bfloat16.

It reads the system's parameters by op name (``layer{i}_attn``,
``layer{i}_attn_norm``, ``layer{i}_ffn_norm``, ``layer{i}_moe_router``,
``layer{i}_moe_experts_{gate,up,down}``); heads and widths are read off
the shapes.  What neither tells is in ``Spec``, at the published values
by default.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp


class Spec(NamedTuple):
    top_k: int = 8               # num_experts_per_tok
    index_topk: int = 2048       # sa_config.topk
    index_rope_dim: int = 32     # rotary on the leading half of a 64 head
    rope_theta: float = 10_000_000.0
    rms_eps: float = 1e-6
    expert_offset: int = 0       # the first expert held (the share)


PUBLISHED = Spec()
Q_BLOCK = 512  # queries a block of the reference attention


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def rms(x, gamma, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _f32(gamma)


def layer_norm(x, gain, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * _f32(gain) + _f32(bias)


def rotary(x, theta):
    """x [B, S, H, D] at positions 0..S-1, half-split pairs."""
    s, d = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def chosen_positions(scores, start, top_k):
    """scores [B, Q, S] of queries at positions start.. -> [B, Q, S] bool:
    each query's ``top_k`` causal positions of largest score (lax.top_k
    over the scores with the unseen at -inf)."""
    q, s = scores.shape[1:]
    rows = start + jnp.arange(q)[:, None]
    seen = jnp.arange(s)[None, :] <= rows
    masked = jnp.where(seen, scores, -jnp.inf)
    k = min(top_k, s)
    _, idx = jax.lax.top_k(masked, k)
    mask = jnp.zeros(masked.shape, bool).at[
        tuple(jnp.indices(idx.shape)[:-1]) + (idx,)].set(True)
    return mask & seen


@functools.partial(jax.jit, static_argnames=("top_k",))
def _attend_block(qb, qib, wib, k, v, ki, start, top_k):
    """One block of queries at positions start..: qb [B, Q, Hkv, G, D],
    qib [B, Q, Hi, Di], wib [B, Q, Hi]; k, v [B, S, Hkv, D], ki [B, S, Di]
    -> ([B, Q, Hkv, G, D], the chosen positions [B, Q, S])."""
    index = jnp.einsum("bqhd,bsd->bqhs", qib, ki)
    index = jnp.einsum("bqhs,bqh->bqs", jnp.maximum(index, 0.0),
                       wib) / math.sqrt(qib.shape[-1])
    chosen = chosen_positions(index, start, top_k)
    d = qb.shape[-1]
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qb, k) / math.sqrt(d)
    scores = jnp.where(chosen[:, None, None], scores, -jnp.inf)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", jax.nn.softmax(scores, axis=-1), v)
    return out, chosen


def sparse_attention(q, k, v, qi, ki, wi, top_k, with_choice=False):
    """q [B, S, Hq, D], k, v [B, S, Hkv, D], qi [B, S, Hi, Di], ki [B, S, Di],
    wi [B, S, Hi] -> [B, S, Hq, D] (and, ``with_choice``, each query's
    chosen positions [B, S, S]); a block of queries at a time (the last
    block padded)."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    qg = q.reshape(b, s, hkv, hq // hkv, d)
    block = min(Q_BLOCK, s)
    pad = -s % block

    def padded(x):
        return jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))

    qg, qi, wi = padded(qg), padded(qi), padded(wi)
    outs, choices = [], []
    for i in range(0, s + pad, block):
        out, chosen = _attend_block(qg[:, i:i + block], qi[:, i:i + block],
                                    wi[:, i:i + block], k, v, ki, i, top_k)
        outs.append(out)
        if with_choice:
            choices.append(chosen)
    out = jnp.concatenate(outs, axis=1)[:, :s].reshape(b, s, hq, d)
    if with_choice:
        return out, jnp.concatenate(choices, axis=1)[:, :s]
    return out


def _turn_leading(x, r, theta):
    return jnp.concatenate([rotary(x[..., :r], theta), x[..., r:]], axis=-1)


@functools.partial(jax.jit, static_argnames=("spec",))
def _projections(x, p, spec):
    d = p["q_norm"].shape[0]
    di = p["wk_index"].shape[1]
    lead = x.shape[:2]
    q = (x @ _f32(p["wq"])).reshape(*lead, -1, d)
    k = (x @ _f32(p["wk"])).reshape(*lead, -1, d)
    v = (x @ _f32(p["wv"])).reshape(*lead, -1, d)
    q, k = rms(q, p["q_norm"], spec.rms_eps), rms(k, p["k_norm"], spec.rms_eps)
    q, k = rotary(q, spec.rope_theta), rotary(k, spec.rope_theta)
    qi = (x @ _f32(p["wq_index"])).reshape(*lead, -1, di)
    ki = layer_norm(x @ _f32(p["wk_index"]), p["k_index_norm"],
                    p["k_index_bias"], spec.rms_eps)[:, :, None, :]
    r = spec.index_rope_dim
    qi = _turn_leading(qi, r, spec.rope_theta)
    ki = _turn_leading(ki, r, spec.rope_theta)[:, :, 0, :]
    wi = (x @ _f32(p["w_index"])) / math.sqrt(qi.shape[2])
    return q, k, v, qi, ki, wi


def attention(x, params, name, spec, with_choice=False):
    p = params[f"{name}_attn"]
    q, k, v, qi, ki, wi = _projections(x, p, spec)
    got = sparse_attention(q, k, v, qi, ki, wi, spec.index_topk, with_choice)
    o, choice = got if with_choice else (got, None)
    y = o.reshape(*x.shape[:2], -1) @ _f32(p["wo"])
    return (y, choice) if with_choice else y


@functools.partial(jax.jit, static_argnames=("top_k",))
def routing_weights(x, router, top_k):
    """[..., E]: each token's weight for every expert of the router's
    width, 0 where the expert is not among its ``top_k``."""
    p = jax.nn.softmax(x @ _f32(router["kernel"]), axis=-1)
    _, chosen = jax.lax.top_k(p, top_k)
    picked = jnp.take_along_axis(p, chosen, axis=-1)
    w = picked / jnp.sum(picked, axis=-1, keepdims=True)
    return jnp.zeros_like(p).at[
        tuple(jnp.indices(chosen.shape)[:-1]) + (chosen,)].set(w)


@jax.jit
def _one_expert(x, w, gate, up, down, e):
    y = (jax.nn.silu(x @ _f32(gate[e])) * (x @ _f32(up[e]))) @ _f32(down[e])
    return w[..., None] * y


def routed_experts(x, params, name, spec):
    """What the experts held here add: sum over held experts of
    weight * E(x), one expert at a time."""
    weights = routing_weights(x, params[f"{name}_moe_router"], spec.top_k)
    gate, up, down = (params[f"{name}_moe_experts_{part}"]["kernel"]
                      for part in ("gate", "up", "down"))
    y = jnp.zeros_like(x)
    for e in range(gate.shape[0]):
        y = y + _one_expert(x, weights[..., spec.expert_offset + e],
                            gate, up, down, e)
    return y


def layer(h, params, name, spec):
    def norm(x, part):
        return rms(x, params[f"{name}_{part}"]["gamma"], spec.rms_eps)

    h = h + attention(norm(h, "attn_norm"), params, name, spec)
    return h + routed_experts(norm(h, "ffn_norm"), params, name, spec)


def forward(params, ids, spec: Spec = PUBLISHED):
    """ids [B, S] int32 -> logits [B, S, vocab] float32."""
    with jax.default_matmul_precision("highest"):
        table = params["tok_embed"]["table"]
        h = _f32(table[jnp.asarray(ids, jnp.int32)])
        layers = sum(k.startswith("layer") and k.endswith("_attn_norm")
                     for k in params)
        for i in range(layers):
            h = layer(h, params, f"layer{i}", spec)
        h = rms(h, params["final_norm"]["gamma"], spec.rms_eps)
        return h @ _f32(params["lm_head"]["kernel"])
