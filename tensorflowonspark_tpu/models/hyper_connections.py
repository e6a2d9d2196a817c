"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880): the
residual stream widened to ``n = cfg.hc_mult`` streams that every sublayer
reads and writes through three small maps computed from the streams
themselves.

No reference counterpart (pre-LLM design).  For the streams ``x`` [n, C]
of one token before a sublayer ``f`` (attention or FFN, its own pre-norm
inside):

    x~     = RMSNorm(vec x)                       [n C], no gain
    H_pre  = sigmoid(a_pre  x~ phi_pre  + b_pre)       [n]
    H_post = 2 sigmoid(a_post x~ phi_post + b_post)    [n]
    H_res  = Sinkhorn(exp(a_res mat(x~ phi_res) + b_res))   [n, n]
    x'     = H_res x + H_post^T f(H_pre x)

``Sinkhorn`` normalises the rows and then the columns, ``hc_sinkhorn_iters``
times, each sum offset by ``hc_eps``: ``H_res`` comes out (close to)
doubly stochastic, so mixing the streams neither grows nor shrinks them.
The model copies its embedding into every stream and sums the streams in
front of the final norm.  ``phi`` is one ``[n C, 2n + n^2]`` matrix per
sublayer, ``alpha`` the three ``a`` and ``bias`` the ``b`` of all three
maps; the coefficients are float32, the streams stay in the compute type.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def init(key, cfg, dtype):
    """``phi`` drawn at unit scale over ``n C`` inputs (so ``x~ phi`` is
    about N(0, 1)), ``alpha`` 0.1, and the biases around a mapping that
    keeps each stream mostly its own (``b_res`` 2 on the diagonal) while
    the streams stay apart (``b_pre`` / ``b_post`` drawn N(0, 0.5²))."""
    n, c = cfg.hc_mult, cfg.dim
    kp, kb = jax.random.split(key)
    width = 2 * n + n * n
    bias = jnp.concatenate([
        0.5 * jax.random.normal(kb, (2 * n,), jnp.float32),
        2.0 * jnp.eye(n, dtype=jnp.float32).reshape(-1)])
    return {"phi": (jax.random.normal(kp, (n * c, width), jnp.float32)
                    / jnp.sqrt(n * c)).astype(dtype),
            "alpha": jnp.full((3,), 0.1, jnp.float32),
            "bias": bias}


def widen(x, n):
    """The embedding ``[..., C]`` copied into ``n`` streams ``[..., n, C]``."""
    return jnp.broadcast_to(x[..., None, :], x.shape[:-1] + (n, x.shape[-1]))


def narrow(x):
    """The streams ``[..., n, C]`` summed, in front of the final norm."""
    return jnp.sum(x.astype(jnp.float32), axis=-2).astype(x.dtype)


def sinkhorn(m, iters, eps):
    """``m`` [..., n, n] > 0 with its rows, then its columns, normalised
    ``iters`` times."""
    with jax.named_scope("mhc/sinkhorn"):
        for _ in range(iters):
            m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
            m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
        return m


def coefficients(p, x, cfg):
    """``(H_pre [..., n], H_post [..., n], H_res [..., n, n])`` float32
    of the streams ``x`` [..., n, C]."""
    n = cfg.hc_mult
    with jax.named_scope("mhc/coeffs"):
        flat = x.reshape(x.shape[:-2] + (-1,)).astype(jnp.float32)
        flat = flat * lax.rsqrt(jnp.mean(flat * flat, axis=-1, keepdims=True)
                                + cfg.norm_eps)
        proj = jnp.dot(flat.astype(x.dtype), p["phi"].astype(x.dtype),
                       preferred_element_type=jnp.float32)
        a, b = p["alpha"], p["bias"]
        pre = jax.nn.sigmoid(a[0] * proj[..., :n] + b[:n])
        post = 2.0 * jax.nn.sigmoid(a[1] * proj[..., n:2 * n] + b[n:2 * n])
        res = jnp.exp(a[2] * proj[..., 2 * n:] + b[2 * n:])
    res = sinkhorn(res.reshape(res.shape[:-1] + (n, n)),
                   cfg.hc_sinkhorn_iters, cfg.hc_eps)
    return pre, post, res


def sublayer(p, x, cfg, f):
    """``x`` [..., n, C] through ``f(h [..., C]) -> (y [..., C], aux)``:
    ``(H_res x + H_post^T y, aux)`` with ``h = H_pre x``."""
    n = cfg.hc_mult
    pre, post, res = coefficients(p, x, cfg)
    # sums of n terms written out: elementwise, in float32, fused
    stream = lambda j: x[..., j, :].astype(jnp.float32)
    with jax.named_scope("mhc/mix"):
        h = sum(pre[..., j:j + 1] * stream(j) for j in range(n))
    y, aux = f(h.astype(x.dtype))
    with jax.named_scope("mhc/mix"):
        y = y.astype(jnp.float32)
        out = jnp.stack(
            [sum(res[..., i, j:j + 1] * stream(j) for j in range(n))
             + post[..., i:i + 1] * y for i in range(n)], axis=-2)
        return out.astype(x.dtype), aux
