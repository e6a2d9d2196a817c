"""Gated delta rule with a per-channel decay (Kimi Delta Attention,
arXiv:2510.26692; the delta rule of arXiv:2406.06484 with the decay of
gated linear attention): the recurrence of a linear-attention layer,
one token at a time for decode and in chunks for a whole sequence.

No reference counterpart (pre-LLM design).  Per head, with a state
``S`` [d_k, d_v], a log-decay ``g_t <= 0`` per KEY channel (``alpha_t =
exp(g_t)``) and a write strength ``beta_t`` in (0, 1):

    S~  = Diag(alpha_t) S_{t-1}                 key row i scaled by alpha_t[i]
    S_t = S~ + beta_t k_t (v_t - S~^T k_t)^T     the delta-rule correction
    o_t = S_t^T q_t

:func:`gated_delta_step` is that, for one token of every row, written
so that the old state is read twice and the new one written once
(``S~^T k = S^T (alpha k)`` and ``S_t^T q = S^T (alpha q) + u (k . q)``
with ``u = beta (v - S~^T k)``: both products are over the OLD state).

:func:`gated_delta_chunked` solves the same recurrence ``chunk`` tokens
at a time.  With ``G_r = sum_{j <= r} g_j`` inside a chunk and ``S_0``
the state the chunk starts from, the corrections ``u_r`` satisfy

    u_r = beta_r (v_r - S_0^T (e^{G_r} k_r) - sum_{i < r} A_ri u_i)
    A_ri = sum_c k_r[c] k_i[c] e^{G_r[c] - G_i[c]}

a unit-lower-triangular system whose solution is linear in ``S_0``:
``U = W_v - W_k S_0`` with ``[W_v | W_k] = (I + Diag(beta) A_<)^{-1}
Diag(beta) [V | K e^G]``, computed for all chunks at once.  A scan over
the chunks then carries the state: ``O = (Q e^G) S_0 + B U`` (``B`` as
``A`` with q for k_r, diagonal included) and ``S_C = Diag(e^{G_C}) S_0 +
(K e^{G_C - G})^T U``.  Every decay that appears is ``e^x`` of a
DIFFERENCE ``x <= 0`` of cumulative log-decays, in float32: nothing is
divided by a cumulative decay, so a channel that forgets everything
inside a chunk underflows to the zero it stands for.  A position with
``beta = 0`` and ``g = 0`` (padding) leaves the state as it was.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

CHUNK = 64
_HIGH = lax.Precision.HIGHEST


def gated_delta_step(state, q, k, v, g, beta):
    """One token of every row: ``state`` [B, H, dk, dv] float32, ``q``,
    ``k``, ``g`` [B, H, dk], ``v`` [B, H, dv], ``beta`` [B, H] ->
    ``(o [B, H, dv] float32, new state)``.  Sums over the state are
    elementwise products and reductions in float32 (a matrix product at
    the default precision would round the state to bfloat16 on its way
    in)."""
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    alpha = jnp.exp(g)
    both = jnp.stack([alpha * k, alpha * q], axis=-2)        # [B, H, 2, dk]
    read = jnp.sum(state[:, :, None] * both[..., None], axis=-2)
    u = beta[..., None] * (v - read[:, :, 0])                # [B, H, dv]
    o = read[:, :, 1] + u * jnp.sum(k * q, axis=-1, keepdims=True)
    new = alpha[..., None] * state + k[..., None] * u[..., None, :]
    return o, new


def gated_delta_chunked(q, k, v, g, beta, state=None, chunk=CHUNK):
    """A whole sequence: ``q``, ``k``, ``g`` [B, T, H, dk], ``v``
    [B, T, H, dv], ``beta`` [B, T, H], ``state`` [B, H, dk, dv] (default
    zeros) -> ``(o [B, T, H, dv] float32, final state float32)``.  ``T``
    is padded to a multiple of ``chunk`` with positions that leave the
    state alone."""
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    pad = (-t) % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    n = (t + pad) // chunk

    def chunks(a):                      # [B, T, H, ...] -> [N, B, H, C, ...]
        a = a.reshape((b, n, chunk) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 1, 0), 2, 3)

    q, k, v, g = (chunks(a) for a in (q, k, v, g))
    beta = chunks(beta[..., None])                      # [N, B, H, C, 1]
    big_g = jnp.cumsum(g, axis=-2)                      # inclusive
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    # e^{G_r - G_i} for i <= r, per channel: [N, B, H, C, C, dk], consumed
    # by the two reductions below (never a product of e^{G_r} and e^{-G_i})
    decay = jnp.exp(jnp.where(
        lower[..., None],
        big_g[..., :, None, :] - big_g[..., None, :, :], -jnp.inf))
    kd = k[..., None, :, :] * decay
    a_mat = jnp.sum(k[..., :, None, :] * kd, axis=-1)   # [N, B, H, C, C]
    b_mat = jnp.sum(q[..., :, None, :] * kd, axis=-1)   # diagonal included
    system = jnp.eye(chunk, dtype=f32) + beta * jnp.where(
        jnp.tril(lower, -1), a_mat, 0.0)
    w = lax.linalg.triangular_solve(
        system, beta * jnp.concatenate([v, k * jnp.exp(big_g)], axis=-1),
        left_side=True, lower=True, unit_diagonal=True)
    w_v, w_k = w[..., :dv], w[..., dv:]
    q_in = q * jnp.exp(big_g)                           # reads S_0
    total = big_g[..., -1:, :]                          # [N, B, H, 1, dk]
    k_out = k * jnp.exp(total - big_g)                  # decays to the end
    keep = jnp.swapaxes(jnp.exp(total), -1, -2)         # [N, B, H, dk, 1]

    def step(s, inp):
        w_v, w_k, q_in, b_mat, k_out, keep = inp
        u = w_v - jnp.matmul(w_k, s, precision=_HIGH)
        o = jnp.matmul(q_in, s, precision=_HIGH) \
            + jnp.matmul(b_mat, u, precision=_HIGH)
        s = keep * s + jnp.matmul(jnp.swapaxes(k_out, -1, -2), u,
                                  precision=_HIGH)
        return s, o

    if state is None:
        state = jnp.zeros((b, h, dk, dv), f32)
    state, o = lax.scan(step, state.astype(f32),
                        (w_v, w_k, q_in, b_mat, k_out, keep))
    # [N, B, H, C, dv] -> [B, T, H, dv]
    o = jnp.moveaxis(jnp.moveaxis(o, 3, 2), 0, 1).reshape(b, n * chunk, h, dv)
    return o[:, :t], state
