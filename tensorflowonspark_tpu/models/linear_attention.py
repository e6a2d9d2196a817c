"""Kimi Delta Attention (KDA, arXiv:2510.26692): a linear-attention
mixer whose cache is a fixed-size recurrent state per session instead of
rows per token.  The recurrence itself is ``ops/delta_rule.py``.

No reference counterpart (pre-LLM design).  For a normed input ``h_t``
of one sequence, H heads of width d:

    [q~; k~; v~]_t = h_t W_qkv                       3 x H*d wide
    [q; k; v]_t    = SiLU(sum_{j<K} w[j] . [q~; k~; v~]_{t-K+1+j})
                     a causal depthwise convolution of length K per
                     channel, inputs before the sequence's start zero
    q_t <- q_t / |q_t| d^-1/2,   k_t <- k_t / |k_t|      per head
    g_t    = -exp(A_log[head]) softplus((h_t W_fa) W_fb + dt_bias)
             the log-decay, per head AND key channel: alpha_t = e^{g_t}
    beta_t = sigmoid(h_t W_beta)                      per head
    o_t    = the gated delta rule over (q, k, v, g, beta)
    y_t    = RMSNorm_d(o_t; gain) * sigmoid((h_t W_ga) W_gb)   per head
    out_t  = concat_h(y_t) W_o

What a session keeps between tokens: the state ``S`` [H, d, d] in
``cfg.state_dtype`` (float32: it is a product of thousands of decays and
rank-one corrections) and the convolution's history, the last ``K - 1``
rows of ``[q~; k~; v~]``, in the compute type.  :func:`mix_prefill` runs
a whole (right-padded) sequence in chunks and hands back both as they
stand at each row's TRUE length; :func:`mix_step` advances them by one
token.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from tensorflowonspark_tpu import ops
from tensorflowonspark_tpu.models import layers as L
from tensorflowonspark_tpu.models.moe import _matmul
from tensorflowonspark_tpu.ops import delta_rule


def init(key, cfg, dtype):
    """``a_log`` and ``dt_bias`` are float32 whatever ``dtype`` and are
    drawn as a trained layer leaves them (the state-space convention:
    ``exp(a_log)`` uniform in [1, 16], ``softplus(dt_bias)`` log-uniform
    in [0.001, 0.1]), so that most channels keep 0.9-0.999 of the state
    a token and some forget half of it (tests/test_linear_attention.py
    states the range)."""
    h, d, r = cfg.linear_heads, cfg.linear_head_dim, cfg.linear_rank
    wide = h * d
    ks = jax.random.split(key, 10)
    dense = lambda k, i, o: L._he_init(k, (i, o), i, dtype)
    dt = jnp.exp(jax.random.uniform(ks[8], (wide,), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    return {
        "wqkv": dense(ks[0], cfg.dim, 3 * wide),
        "conv": L._he_init(ks[1], (cfg.linear_conv, 3 * wide),
                           cfg.linear_conv, dtype),
        # the decay's map at a quarter of the other maps' scale: the
        # token moves a channel's decay around what ``dt_bias`` gives it
        "wfa": dense(ks[2], cfg.dim, r), "wfb": dense(ks[3], r, wide) / 4,
        "wga": dense(ks[4], cfg.dim, r), "wgb": dense(ks[5], r, wide),
        "wbeta": dense(ks[6], cfg.dim, h),
        "a_log": jnp.log(jax.random.uniform(ks[7], (h,), jnp.float32,
                                            1.0, 16.0)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),   # softplus^-1(dt)
        "o_norm": jnp.ones((d,), jnp.float32),
        "wo": dense(ks[9], wide, cfg.dim),
    }


def state_shapes(cfg):
    """``(S, convolution history)`` of one session and one layer."""
    h, d = cfg.linear_heads, cfg.linear_head_dim
    return (h, d, d), (cfg.linear_conv - 1, 3 * h * d)


def _heads(x, cfg):
    return x.reshape(x.shape[:-1] + (cfg.linear_heads, cfg.linear_head_dim))


def _inputs(p, y, window, cfg):
    """``y`` [B, T, dim] (normed) and ``window`` [B, K - 1 + T, 3*H*d],
    the projections of these tokens behind the K - 1 before them ->
    ``q, k, v, g`` [B, T, H, d] and ``beta`` [B, T, H], float32."""
    t = y.shape[1]
    f32 = jnp.float32
    with jax.named_scope("attn/kda_conv"):
        w = p["conv"].astype(f32)
        x = sum(w[j] * window[:, j:j + t].astype(f32)
                for j in range(cfg.linear_conv))
        q, k, v = (_heads(a, cfg)
                   for a in jnp.split(jax.nn.silu(x), 3, axis=-1))
        q = q * lax.rsqrt(jnp.sum(q * q, -1, keepdims=True)
                          * cfg.linear_head_dim + 1e-12)
        k = k * lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-12)
    with jax.named_scope("attn/kda_gate"):
        a = _matmul(_matmul(y, p["wfa"]), p["wfb"]).astype(f32)
        g = -jnp.exp(p["a_log"])[:, None] * _heads(
            jax.nn.softplus(a + p["dt_bias"]), cfg)
        beta = jax.nn.sigmoid(_matmul(y, p["wbeta"]).astype(f32))
    return q, k, v, g, beta


def _output(p, y, o, cfg):
    """The gated output norm and ``W_o``: ``o`` [B, T, H, d] float32."""
    with jax.named_scope("attn/kda_out"):
        z = _heads(_matmul(_matmul(y, p["wga"]), p["wgb"]), cfg)
        o = ops.rmsnorm_reference(o, p["o_norm"], cfg.norm_eps) \
            * jax.nn.sigmoid(z.astype(jnp.float32))
        return _matmul(o.astype(y.dtype).reshape(y.shape[:2] + (-1,)),
                       p["wo"])


def mix_prefill(p, y, cfg, lengths=None):
    """A whole sequence from an empty state: ``y`` [B, T, dim] (normed),
    ``lengths`` [B] true lengths (default: all T) -> ``(out [B, T, dim],
    S [B, H, d, d] in ``cfg.state_dtype``, history [B, K - 1, 3*H*d])``,
    the state and the history as they stand after ``lengths`` tokens: a
    padded position neither decays nor writes (``beta = 0``, ``g = 0``)
    and is not part of the history."""
    b, t, _ = y.shape
    hist = cfg.linear_conv - 1
    with jax.named_scope("attn/kda_proj"):
        window = jnp.pad(_matmul(y, p["wqkv"]), ((0, 0), (hist, 0), (0, 0)))
    q, k, v, g, beta = _inputs(p, y, window, cfg)
    if lengths is None:
        lengths = jnp.full((b,), t, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    real = jnp.arange(t)[None, :] < lengths[:, None]            # [B, T]
    g = jnp.where(real[..., None, None], g, 0.0)
    beta = jnp.where(real[..., None], beta, 0.0)
    with jax.named_scope("attn/kda_chunk"):
        o, state = delta_rule.gated_delta_chunked(q, k, v, g, beta)
        # rows lengths - hist .. lengths - 1 sit ``hist`` further in
        conv = jax.vmap(lambda w, n: lax.dynamic_slice_in_dim(w, n, hist))(
            window, lengths)
    return (_output(p, y, o, cfg), state.astype(jnp.dtype(cfg.state_dtype)),
            conv)


def mix_step(p, y, cfg, state, conv):
    """One token of every row against its session's ``state`` [B, H, d,
    d] and ``conv`` [B, K - 1, 3*H*d]: ``y`` [B, 1, dim] -> ``(out
    [B, 1, dim], new state, new history)``."""
    with jax.named_scope("attn/kda_proj"):
        window = jnp.concatenate(
            [conv, _matmul(y, p["wqkv"]).astype(conv.dtype)], axis=1)
    q, k, v, g, beta = _inputs(p, y, window, cfg)
    with jax.named_scope("attn/kda_step"):
        o, new = delta_rule.gated_delta_step(
            state.astype(jnp.float32), q[:, 0], k[:, 0], v[:, 0], g[:, 0],
            beta[:, 0])
    return (_output(p, y, o[:, None], cfg), new.astype(state.dtype),
            window[:, 1:])
