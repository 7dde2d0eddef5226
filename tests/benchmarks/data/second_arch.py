"""A second architecture, added to the benchmark by files alone
(test_bench_second_arch.py): everything here is what a later PR would
bring as new files — two builders with argument names of their own, the
``work`` modules' four functions and a plain reference.  Nothing under
``benchmarks/`` knows any of these names.

* ``build_gated_lm``: a language model that is not ``build_gpt`` —
  embedding, one gated feed-forward block with a residual, head.  No
  attention op, no Pallas kernel, so no Mosaic call.
* ``build_renamed_decode``: the zoo's decode model behind other argument
  names, so that the serving driver cannot lean on ``build_gpt_decode``'s.
"""

import jax
import jax.numpy as jnp


def build_gated_lm(config, n_tokens: int, width: int, inner: int,
                   context: int):
    from flexflow_tpu import FFModel

    model = FFModel(config)
    ids = model.create_tensor([config.batch_size, context], dtype="int32",
                              name="ids")
    x = model.embedding(ids, n_tokens, width, aggr="none", name="embed")
    gate = model.dense(x, inner, activation="sigmoid", use_bias=False,
                       name="gate")
    up = model.dense(x, inner, use_bias=False, name="up")
    down = model.dense(model.multiply(gate, up), width, use_bias=False,
                       name="down")
    model.dense(model.add(x, down), n_tokens, use_bias=False, name="head")
    return model


def build_renamed_decode(config, n_tokens: int, depth: int, width: int,
                         heads: int, inner: int, page: int, pages: int):
    from flexflow_tpu.models import build_gpt_decode

    return build_gpt_decode(config, vocab=n_tokens, num_layers=depth,
                            hidden=width, num_heads=heads, ff_dim=inner,
                            page_size=page, pages_per_seq=pages)


# ---- the plain reference of the gated model: float32, "highest" --------

def forward(params, ids):
    p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        x = p["embed"]["table"][jnp.asarray(ids, jnp.int32)]
        hidden = (jax.nn.sigmoid(x @ p["gate"]["kernel"])
                  * (x @ p["up"]["kernel"]))
        return (x + hidden @ p["down"]["kernel"]) @ p["head"]["kernel"]


def loss(params, ids, labels):
    logp = jax.nn.log_softmax(forward(params, ids), axis=-1)
    picked = jnp.take_along_axis(
        logp, jnp.asarray(labels, jnp.int32)[..., None], axis=-1)
    return -jnp.mean(picked)


# ---- the ``work`` contract of the gated model ---------------------------

def _gated_weights(config: dict, logits: bool = True) -> int:
    kw = config["builder_kwargs"]
    return (3 * kw["width"] * kw["inner"]
            + (kw["width"] * kw["n_tokens"] if logits else 0))


def trained_token_flops(config: dict, seq_len: int) -> float:
    return 6.0 * _gated_weights(config)


def attention_kernel_flops(config: dict, batch: int, seq_len: int) -> float:
    return 0.0  # the model has no attention


def served_token_flops(config: dict, context, logits: bool = True):
    return 2.0 * _gated_weights(config, logits) + 0.0 * context


def cached_token_bytes(config: dict, itemsize: int) -> int:
    return 0  # and no cache


# ---- a per-layer metric's reader of its own -----------------------------

def window_gflop(ctx):
    """GFLOP the window's tokens needed, as the driver priced them."""
    flops = ctx["facts"].get("window_flops")
    return None if not flops else flops / 1e9
