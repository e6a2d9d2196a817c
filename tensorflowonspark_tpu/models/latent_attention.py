"""Latent attention (multi-head latent attention, DeepSeek-V2,
arXiv:2405.04434): keys and values are up-projections of ONE low-rank
latent per token, so the cache holds that latent and a shared rotary key
— ``kv_lora_rank + qk_rope_dim`` numbers a token a layer, for all heads —
instead of per-head keys and values.

No reference counterpart (pre-LLM design).  For a normed input ``h``:

    q            = h W_q                    -> [H, nope + rope] per head
                   (RMSNorm per head where ``cfg.qk_norm``), rope part rotated
    [c_raw; kr]  = h W_kva                  -> [rank], [rope]
    c            = RMSNorm(c_raw);  k_rope = RoPE(kr)   (one for all heads)
    row          = [c; k_rope]              what the cache keeps
    [k_nope_h; v_h] = c W_kvb[h]
    score_h      = (q_nope_h . k_nope_h + q_rope_h . k_rope) * s
    s            = (nope + rope)^-0.5 * m^2  (``ops.yarn_softmax_scale``)

Without rotary (``cfg.qk_rotary`` False, the published ``mla_use_nope``)
nothing is rotated: the "rope" part of q and of the shared key enter the
score as they are projected, and no position enters the layer at all.

Two paths compute the same attention:

- :func:`attend_expanded` (prefill): expand ``k_nope_h`` / ``v_h`` for the
  tokens at hand and run ordinary causal attention with q/k width
  ``nope + rope`` against v width ``v_head_dim``;
- :func:`attend_absorbed` (decode, and a tail over cached rows): fold
  ``W_UK`` into the query and ``W_UV`` into the output, and attend over
  the cached rows directly — per-head keys and values of the cache are
  never materialised.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from tensorflowonspark_tpu import ops
from tensorflowonspark_tpu.models import layers as L
from tensorflowonspark_tpu.models.moe import _matmul

_NEG_INF = -1e30  # finite mask fill (ops.attention convention: never -inf)


def init(key, cfg, dtype):
    h, r = cfg.n_heads, cfg.kv_lora_rank
    qd, rope = cfg.q_head_dim, cfg.qk_rope_dim
    ks = jax.random.split(key, 4)
    dense = lambda k, i, o: L._he_init(k, (i, o), i, dtype)
    p = {
        "wq": dense(ks[0], cfg.dim, h * qd),
        "wkva": dense(ks[1], cfg.dim, r + rope),
        "kv_norm": jnp.ones((r,), dtype),
        "wkvb": dense(ks[2], r, h * (cfg.qk_nope_dim + cfg.v_head_dim)),
        "wo": dense(ks[3], h * cfg.v_head_dim, cfg.dim),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((qd,), dtype)
    return p


def rope_tables(cfg, length):
    """``(cos, sin)``, or ``(None, None)`` for a block without rotary
    (``cfg.qk_rotary`` False): :func:`project` then rotates nothing."""
    if not cfg.qk_rotary:
        return None, None
    return ops.rope_angles(length, cfg.qk_rope_dim, cfg.rope_base,
                           scaling=cfg.rope_scaling)


def project(p, y, cfg, cos, sin, positions=None):
    """``y`` [B, T, dim] (normed) -> ``(q [B, T, H, nope + rope], rows
    [B, T, rank + rope])``, rotary applied to both rope parts (to
    neither where the tables are None: the "rope" part is then one more
    slice of the key that all heads share)."""
    b, t, _ = y.shape
    nope, r = cfg.qk_nope_dim, cfg.kv_lora_rank
    with jax.named_scope("attn/latent_q"):
        q = _matmul(y, p["wq"]).reshape(b, t, cfg.n_heads, cfg.q_head_dim)
        if cfg.qk_norm:
            q = ops.rmsnorm_reference(q, p["q_norm"], cfg.norm_eps)
        if cos is not None:
            q = jnp.concatenate(
                [q[..., :nope],
                 ops.apply_rope(q[..., nope:], cos, sin,
                                positions=positions)], axis=-1)
    with jax.named_scope("attn/latent_kv"):
        kva = _matmul(y, p["wkva"])
        c = ops.rmsnorm_reference(kva[..., :r], p["kv_norm"], cfg.norm_eps)
        k_rope = kva[..., r:] if cos is None else ops.apply_rope(
            kva[..., None, r:], cos, sin, positions=positions)[:, :, 0]
        return q, jnp.concatenate([c, k_rope], axis=-1)


def _up(p, cfg, dtype):
    """``W_kvb`` as ``(W_UK [rank, H, nope], W_UV [rank, H, v])``."""
    w = p["wkvb"].astype(dtype).reshape(
        cfg.kv_lora_rank, cfg.n_heads, cfg.qk_nope_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_dim], w[..., cfg.qk_nope_dim:]


def attend_expanded(p, q, rows, cfg, attn_fn):
    """Causal attention of ``q`` over ITS OWN tokens' ``rows``: per-head
    keys and values are expanded for these tokens only.  ``attn_fn(q, k,
    v, scale=)`` on [B, T, H, D] with v narrower than q/k."""
    b, t, h, _ = q.shape
    r = cfg.kv_lora_rank
    with jax.named_scope("attn/expand"):
        kv = _matmul(rows[..., :r], p["wkvb"]).reshape(
            b, t, h, cfg.qk_nope_dim + cfg.v_head_dim)
        k = jnp.concatenate(
            [kv[..., :cfg.qk_nope_dim],
             jnp.broadcast_to(rows[:, :, None, r:],
                              (b, t, h, cfg.qk_rope_dim))], axis=-1)
        v = kv[..., cfg.qk_nope_dim:]
    with jax.named_scope("attn"):
        out = attn_fn(q, k, v, scale=softmax_scale(cfg))
        return out.reshape(b, t, h * cfg.v_head_dim)


def attend_absorbed(p, q, ctx, mask, cfg):
    """Attention of ``q`` [B, T, H, nope + rope] over cached ``ctx``
    [B, M, rank + rope] under ``mask`` [B, 1, T, M], with the
    up-projections absorbed: ``q~ = q_nope W_UK^T`` scores against the
    latent itself, the probabilities average the latent, and ``W_UV``
    maps that average to the head's output."""
    b, t, h, _ = q.shape
    r, nope = cfg.kv_lora_rank, cfg.qk_nope_dim
    dt = q.dtype
    w_uk, w_uv = _up(p, cfg, dt)
    with jax.named_scope("attn/absorb"):
        q_lat = jnp.einsum("bthn,rhn->bthr", q[..., :nope], w_uk,
                           preferred_element_type=jnp.float32).astype(dt)
        q_full = jnp.concatenate([q_lat, q[..., nope:]], axis=-1)
    with jax.named_scope("attn"):
        ctx = ctx.astype(dt)
        scores = jnp.einsum("bthc,bmc->bhtm", q_full, ctx,
                            preferred_element_type=jnp.float32)
        scores = jnp.where(mask, scores * softmax_scale(cfg), _NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(dt)
        o_lat = jnp.einsum("bhtm,bmr->bthr", probs, ctx[..., :r],
                           preferred_element_type=jnp.float32).astype(dt)
    with jax.named_scope("attn/absorb"):
        out = jnp.einsum("bthr,rhv->bthv", o_lat, w_uv,
                         preferred_element_type=jnp.float32).astype(dt)
        return out.reshape(b, t, h * cfg.v_head_dim)


def softmax_scale(cfg):
    return ops.yarn_softmax_scale(cfg.q_head_dim, cfg.rope_scaling)
