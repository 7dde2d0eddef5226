"""The flash kernels with value heads narrower than the query/key heads
(latent attention: 192 beside 128): forward, dq, dk and dv against the
XLA path, in interpreter mode on the CPU.  (Mosaic's compile of the
published widths is in tests/test_chip_lowering.py, with the other
compiles for a described chip.)"""

import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

fa = importlib.import_module("flexflow_tpu.kernels.flash_attention")


def _qkv(b, s, h, d, dv, dtype=jnp.float32, seed=0):
    kq, kk, kv, kg = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(kq, (b, s, h, d), dtype),
            jax.random.normal(kk, (b, s, h, d), dtype),
            jax.random.normal(kv, (b, s, h, dv), dtype),
            jax.random.normal(kg, (b, s, h, dv), dtype))


@pytest.mark.parametrize("d,dv", [(24, 16), (16, 24), (16, 16)])
def test_flash_forward_and_three_gradients_agree_with_xla(d, dv):
    q, k, v, g = _qkv(2, 64, 2, d, dv)
    scale = 1.0 / math.sqrt(d)

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, scale=scale,
                                  block_q=16, block_k=32)

    def plain(q, k, v):
        return fa._xla_attention(q, k, v, True, scale)

    out, vjp = jax.vjp(flash, q, k, v)
    ref, ref_vjp = jax.vjp(plain, q, k, v)
    assert out.shape == (2, 64, 2, dv)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)
    for got, want, name in zip(vjp(g), ref_vjp(g), ("dq", "dk", "dv")):
        assert got.shape == want.shape, name
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5,
                                   err_msg=name)
