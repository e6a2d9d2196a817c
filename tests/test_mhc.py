"""Manifold-constrained hyper-connections (``models/hyper_connections.py``)
at a tiny size on the CPU: ``H_res`` comes out doubly stochastic, a
sublayer is the reference's, and the streams mix and stay apart."""

import types

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import sparse_hybrid_decoder as ref
from tensorflowonspark_tpu.models import hyper_connections as hc

CFG = types.SimpleNamespace(hc_mult=4, dim=32, hc_sinkhorn_iters=20,
                            hc_eps=1e-6, norm_eps=1e-5)


def _params(seed=0, alpha=1.0):
    p = hc.init(jax.random.PRNGKey(seed), CFG, jnp.float32)
    return dict(p, alpha=jnp.full((3,), alpha, jnp.float32))


def test_h_res_is_doubly_stochastic_after_20_iterations():
    """At the maps' drawn scale (alpha 0.1, ``b_res`` 2 on the diagonal)
    and twice it, over 4,096 tokens: rows and columns sum to 1 within
    1e-5 (2.3e-6 measured at twice the scale; at ten times it the rows
    reach only 1.4e-2 after 20 iterations)."""
    x = 3.0 * jax.random.normal(jax.random.PRNGKey(1), (4096, 4, 32))
    pre, post, res = hc.coefficients(_params(alpha=0.2), x, CFG)
    rows, cols = np.asarray(res.sum(-1)), np.asarray(res.sum(-2))
    np.testing.assert_allclose(cols, 1.0, atol=1e-5)
    np.testing.assert_allclose(rows, 1.0, atol=1e-5)
    assert float(jnp.min(res)) > 0
    assert 0 < float(jnp.min(pre)) and float(jnp.max(pre)) < 1
    assert 0 < float(jnp.min(post)) and float(jnp.max(post)) < 2
    # one iteration does not get there: the twenty are needed
    one = hc.sinkhorn(jnp.exp(3.0 * jax.random.normal(
        jax.random.PRNGKey(2), (64, 4, 4))), 1, 1e-6)
    assert float(jnp.max(jnp.abs(one.sum(-1) - 1))) > 1e-3


def test_a_sublayer_is_the_references():
    p = _params(3, alpha=0.5)
    x = jax.random.normal(jax.random.PRNGKey(4), (16, 4, 32))
    w = jax.random.normal(jax.random.PRNGKey(5), (32, 32)) / 6

    def f(h):
        return jnp.tanh(h @ w), None

    got, _ = hc.sublayer(p, x, CFG, f)
    d = ref.Dims(*([0] * len(ref.Dims._fields)))._replace(
        eps=1e-5, hc=4, hc_iters=20, hc_eps=1e-6)
    with jax.default_matmul_precision("highest"):
        h, post, res = ref.hc_in(p, x, d, None)
        want = ref.hc_out(x, f(h)[0], post, res)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_the_streams_mix_and_stay_apart():
    """From the embedding copied into four streams, one sublayer leaves
    four different streams (``H_post`` differs by stream); their sum is
    what the final norm reads."""
    x = hc.widen(jax.random.normal(jax.random.PRNGKey(6), (8, 32)), 4)
    assert x.shape == (8, 4, 32)
    np.testing.assert_allclose(np.asarray(hc.narrow(x)),
                               4 * np.asarray(x[:, 0]), rtol=1e-6)
    out, _ = hc.sublayer(_params(7), x, CFG, lambda h: (jnp.sin(h), None))
    spread = jnp.max(jnp.abs(out - out[:, :1]), axis=(0, 2))
    assert float(jnp.min(spread[1:])) > 1e-2
    # with every map at its zero (alpha and biases 0): H_pre 1/2 each,
    # H_post 1 each, H_res uniform: identical streams stay identical
    flat = dict(_params(7), alpha=jnp.zeros((3,)), bias=jnp.zeros((24,)))
    out, _ = hc.sublayer(flat, x, CFG, lambda h: (jnp.sin(h), None))
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(x + jnp.sin(2 * x)), atol=1e-5)
