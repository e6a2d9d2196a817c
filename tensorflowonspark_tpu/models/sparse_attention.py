"""DeepSeek sparse attention (DSA, DeepSeek-V3.2-Exp): a lightning indexer
scores, for each query, the positions before it, and latent attention
(``models/latent_attention.py``) reads only the ``index_topk`` positions
it selects.

No reference counterpart (pre-LLM design).  For a token t with normed
input ``h_t`` and query latent ``c^Q_t`` (``latent.query_latent``):

    q^I_{t,j} = W^IQ_j c^Q_t               index heads j, ``index_head_dim``
    k^I_s     = LayerNorm(W^IK h_s)         one index key a position, for
                                            all index heads
    w_t       = W^w h_t                     one weight per index head
    the first ``index_rope_dim`` of each q^I and k^I rotated by position
    (interleaved pairs, base ``cfg.rope_base``)
    kbar_g    = the mean of k^I over positions [kpool g, kpool (g + 1))
    I_{t,g}   = sum_j w_{t,j} ReLU(q^I_{t,j} . kbar_g)
    read(t)   = the group holding t (always: its keys are not all
                there yet) and the ``index_topk / kpool - 1`` groups
                before it with the largest I, positions <= t of them

Constant positive factors on ``I`` (the published indexer scales the
scores and the weights) would not change which groups are largest and are
left out.  A query with at most ``index_topk / kpool`` groups up to its
own reads every position before it: the layer is then dense latent
attention.

What the cache keeps: ``k^I`` per token (``CacheEntry("index", ...)``,
``index_head_dim`` a token a layer, beside the latent row), pooled where
it is read.  The decode step (:func:`step_context`) scores every mapped
group, takes the top groups per slot and gathers ONLY their latent rows
for the absorbed attention; the prefill (:func:`prefill_attention`) does
the same per query, ``Q_BLOCK`` queries at a time, so that a long prompt's
``[T, T / kpool]`` scores and its gathered rows are never whole.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from tensorflowonspark_tpu.models import latent_attention as latent
from tensorflowonspark_tpu.models import layers as L
from tensorflowonspark_tpu.models.moe import _matmul

Q_BLOCK = 128         # prefill queries selected and attended at once
# the decode step's counters (:func:`step_context`), summed over the layers
COUNTERS = ("dsa_live_positions", "dsa_selected_positions",
            "dsa_scored_groups")
_LOW, _HIGH = -1e30, 1e30


def init(key, cfg, dtype):
    hi, di = cfg.index_heads, cfg.index_head_dim
    ks = jax.random.split(key, 3)
    dense = lambda k, i, o: L._he_init(k, (i, o), i, dtype)
    return {"wiq": dense(ks[0], cfg.q_lora_rank, hi * di),
            "wik": dense(ks[1], cfg.dim, di),
            "ik_norm": jnp.ones((di,), dtype),
            "ik_bias": jnp.zeros((di,), dtype),
            "wiw": dense(ks[2], cfg.dim, hi)}


def _rotate(x, positions, cfg):
    """``x`` [B, T, ..., d] with its first ``index_rope_dim`` rotated by
    ``positions`` [B, T], interleaved pairs ``(x[2i], x[2i + 1])``."""
    rope = cfg.index_rope_dim
    if not rope:
        return x
    half = rope // 2
    inv = 1.0 / cfg.rope_base ** (
        jnp.arange(half, dtype=jnp.float32) * 2 / rope)
    ang = positions.astype(jnp.float32)[..., None] * inv       # [B, T, half]
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    pairs = xf[..., :rope].reshape(xf.shape[:-1] + (half, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    rot = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return jnp.concatenate(
        [rot.reshape(xf.shape[:-1] + (rope,)), xf[..., rope:]],
        axis=-1).astype(x.dtype)


def index_keys(p, y, cfg, positions):
    """``y`` [B, T, dim] (normed) -> ``k^I`` [B, T, index_head_dim] in its
    type: what the cache keeps."""
    with jax.named_scope("attn/dsa_index"):
        k = _matmul(y, p["wik"]).astype(jnp.float32)
        k = k - jnp.mean(k, axis=-1, keepdims=True)
        k = k * lax.rsqrt(jnp.mean(k * k, axis=-1, keepdims=True)
                          + cfg.norm_eps)
        k = k * p["ik_norm"].astype(jnp.float32) \
            + p["ik_bias"].astype(jnp.float32)
        return _rotate(k, positions, cfg).astype(y.dtype)


def index_queries(p, cq, y, cfg, positions):
    """``(q^I [B, T, index_heads, index_head_dim], w [B, T, index_heads]
    float32)`` from the query latent ``cq`` and the normed input ``y``."""
    b, t, _ = cq.shape
    with jax.named_scope("attn/dsa_index"):
        q = _matmul(cq, p["wiq"]).reshape(b, t, cfg.index_heads,
                                          cfg.index_head_dim)
        w = jnp.dot(y, p["wiw"].astype(y.dtype),
                    preferred_element_type=jnp.float32)
        return _rotate(q, positions, cfg), w


def pool_keys(keys, cfg):
    """``k^I`` [B, M, d] (M a multiple of ``index_kpool``) -> the groups'
    mean keys ``kbar`` [B, M / kpool, d], summed in float32."""
    b, m, d = keys.shape
    kp = cfg.index_kpool
    with jax.named_scope("attn/dsa_index"):
        return keys.reshape(b, m // kp, kp, d).astype(jnp.float32).mean(
            axis=2).astype(keys.dtype)


def scores(q, w, kbar):
    """``I`` [B, T, G] float32 of queries ``q`` [B, T, J, d] weighted by
    ``w`` [B, T, J] against groups ``kbar`` [B, G, d]."""
    with jax.named_scope("attn/dsa_index"):
        s = jnp.einsum("btjd,bgd->btjg", q, kbar,
                       preferred_element_type=jnp.float32)
        return jnp.einsum("btjg,btj->btg", jax.nn.relu(s), w)


def select(score, tpos, cfg):
    """``(positions [B, T, n], read [B, T, n] bool)``: for query
    positions ``tpos`` [B, T] and their group scores ``score`` [B, T, G],
    the positions of the groups each reads (its own and the largest
    before it; ``n = min(index_topk, G * kpool)``) and which of them it
    reads (those of a chosen group, up to the query itself)."""
    kp = cfg.index_kpool
    groups = score.shape[-1]
    n = min(cfg.index_topk // kp, groups)
    own = (tpos // kp)[..., None]
    gi = jnp.arange(groups)
    with jax.named_scope("attn/dsa_topk"):
        s = jnp.where(gi < own, score, _LOW)
        s = jnp.where(gi == own, _HIGH, s)
        top, idx = lax.top_k(s, n)
        pos = (idx[..., None] * kp + jnp.arange(kp)).reshape(
            idx.shape[:-1] + (n * kp,))
        read = jnp.repeat(top > _LOW / 2, kp, axis=-1) \
            & (pos <= tpos[..., None])
    return pos, read


def step_context(p, cq, y, cfg, pool, ipool, layer, tables, positions,
                 live):
    """The decode step's selection, one token a slot: ``cq``, ``y`` [S, 1,
    ...]; ``pool`` / ``ipool`` the paged latent rows and index keys
    ``[num_blocks, layers, block_size, ...]`` (this token's already
    written), ``layer`` their layer, ``tables`` [S, blocks], ``positions``
    [S, 1], ``live`` [S] the slots a session holds.  Returns ``(ctx [S, n,
    latent_row]: the selected rows ONLY, mask [S, 1, 1, n], counters)``;
    the counters count live slots: positions up to the query, positions
    read, and the whole groups before the query's own that were scored."""
    s_slots = tables.shape[0]
    bs = pool.shape[2]
    qi, w = index_queries(p, cq, y, cfg, positions)
    with jax.named_scope("attn/dsa_index"):
        keys = ipool[tables, layer].reshape(s_slots, -1, ipool.shape[-1])
    sc = scores(qi, w, pool_keys(keys, cfg))                # [S, 1, G]
    pos, read = select(sc, positions, cfg)
    pos, read = pos[:, 0], read[:, 0]                       # [S, n]
    with jax.named_scope("attn/dsa_gather"):
        blk = jnp.take_along_axis(tables, pos // bs, axis=1)
        ctx = pool[blk, layer, pos % bs]                    # [S, n, row]
    t = positions[:, 0]
    lv = live.astype(jnp.int32)
    counters = {
        "dsa_live_positions": jnp.sum(lv * (t + 1)),
        "dsa_selected_positions": jnp.sum(
            lv * jnp.sum(read, axis=-1, dtype=jnp.int32)),
        "dsa_scored_groups": jnp.sum(lv * (t // cfg.index_kpool)),
    }
    return ctx, read[:, None, None, :], counters


def prefill_attention(pa, p, q, rows, cq, y, cfg):
    """A whole (right-padded) sequence: ``q`` [B, T, H, nope + rope],
    ``rows`` [B, T, latent_row] of these tokens, ``cq`` / ``y`` their
    query latent and normed input -> ``(attention [B, T, H * v], k^I [B,
    T, index_head_dim])``.  ``Q_BLOCK`` queries at a time: their scores
    against every group, their selection, their selected rows gathered,
    the absorbed attention over those (``latent.attend_absorbed``, one
    query a row).  A padded position past a row's length is never read
    by a real query: it lies past it."""
    b, t = q.shape[:2]
    kp = cfg.index_kpool
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    keys = index_keys(p, y, cfg, positions)
    qi, w = index_queries(p, cq, y, cfg, positions)
    kbar = pool_keys(jnp.pad(keys, ((0, 0), (0, (-t) % kp), (0, 0))), cfg)
    qb = min(Q_BLOCK, t)
    nblk = -(-t // qb)

    def pad(a):
        return jnp.pad(a, ((0, 0), (0, nblk * qb - t))
                       + ((0, 0),) * (a.ndim - 2))

    q, qi, w = pad(q), pad(qi), pad(w)

    def block(i):
        start = i * qb
        cut = lambda a: lax.dynamic_slice_in_dim(a, start, qb, axis=1)
        tpos = jnp.broadcast_to(start + jnp.arange(qb, dtype=jnp.int32),
                                (b, qb))
        pos, read = select(scores(cut(qi), cut(w), kbar), tpos, cfg)
        n = pos.shape[-1]
        with jax.named_scope("attn/dsa_gather"):
            # a padded query may name a group past the sequence: clamped,
            # and read by no real query
            ctx = jax.vmap(lambda r, i: r[i])(
                rows, jnp.minimum(pos, t - 1).reshape(b, qb * n))
        out = latent.attend_absorbed(
            pa, cut(q).reshape((b * qb, 1) + q.shape[2:]),
            ctx.reshape(b * qb, n, rows.shape[-1]),
            read.reshape(b * qb, 1, 1, n), cfg)
        return out.reshape(b, qb, -1)

    out = lax.map(block, jnp.arange(nblk))                 # [nblk, B, qb, .]
    out = jnp.moveaxis(out, 0, 1).reshape(b, nblk * qb, -1)[:, :t]
    return out, keys


def selected_positions(lengths, cfg):
    """Positions read by every real query of sequences of ``lengths``
    (host integers), as :func:`select` chooses them: a query reads every
    position up to itself while its groups number at most ``index_topk /
    kpool``, else the whole groups chosen before its own and its own up to
    itself."""
    kp, groups = cfg.index_kpool, cfg.index_topk // cfg.index_kpool
    total = 0
    for n in np.asarray(lengths).reshape(-1):
        t = np.arange(int(n))
        total += int(np.where(t // kp + 1 <= groups, t + 1,
                              (groups - 1) * kp + t % kp + 1).sum())
    return total
