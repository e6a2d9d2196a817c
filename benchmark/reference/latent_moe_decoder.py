"""Plain reference: a decoder with latent attention (MLA), one or more
leading dense SwiGLU layers and then expert layers (sigmoid router with
a selection bias, top-k, normalised and scaled gates, one or more shared
experts), as the ``sarvam_mla`` / DeepSeek-V2/V3 family publishes it.
Straightforward float32 ``jax.numpy``: the NON-absorbed attention (keys
and values expanded per head for every position), full-materialisation
causal softmax (in blocks of queries only so that 8.7 k positions x 64
heads fit: every query still sees all its keys at once), a Python loop
over experts, no cache, no kernels, no scan, nothing imported from the
program.  Callers set ``jax.default_matmul_precision("highest")``.

It reads the program's PARAMETER TREE (weights are data; every leaf is
upcast to float32 where it is used, one layer and one expert at a time,
so a float32 copy of all weights never exists): ``embed`` [V, d],
``dense_layers`` and ``layers`` (each leaf stacked on a leading layer
axis), ``ln_f``, ``head`` [d, V].  A layer has ``ln1``, ``ln2``,
``attn`` = {``wq`` [d, H*(nope+rope)], ``q_norm`` [nope+rope], ``wkva``
[d, rank+rope], ``kv_norm`` [rank], ``wkvb`` [rank, H*(nope+v)], ``wo``
[H*v, d]} and either ``wg``/``wu``/``wd`` (dense) or ``moe`` = {``router``
[d, E], ``router_bias`` [E], ``wg``/``wu`` [held, d, f], ``wd`` [held, f,
d], ``shared_wg``/``shared_wu``/``shared_wd``}.

``sizes`` is the configuration file's own dict (published key names).
``held = (first, count)`` says which experts the tree's expert weights
are: expert ``first + j`` is row ``j``; what the other experts would add
is left out, and that partial sum goes on to the next layer.

Departures from the published description, each on purpose:
1. ``use_qk_norm`` is read as RMSNorm (learned scale) on each head's
   whole q (nope+rope wide, before the split and the rotation) and on the
   latent c; the configuration file's ``assumed`` says why.
2. Rotary pairs are (i, i + rope/2) (rotate-half on the stored order);
   the published code de-interleaves (2i, 2i+1) first: for seeded random
   weights a fixed permutation of the rope columns of W_q and W_kva.
3. Only the experts in ``held`` contribute (the chip's share of an
   expert-parallel deployment); with ``held = (0, E)`` it is the whole
   layer.
4. ``round_to`` (default None) rounds every weight and every cached row
   ``[c; k_rope]`` to that dtype before use: NOT part of the model, it is
   how the benchmark reads what one precision lower would give.
"""

import math

import jax
import jax.numpy as jnp

EPS = 1e-6


def _f32(x, round_to=None):
    if round_to is not None:
        x = x.astype(round_to)
    return x.astype(jnp.float32)


def _rmsnorm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + EPS) * scale


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim, base, sc):
    """YaRN's frequencies for ``dim`` rotary dimensions (``sc``: the
    config's ``rope_scaling`` group)."""
    half = dim // 2
    plain = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    if not sc:
        return plain

    def correction(rotations):
        return dim * math.log(sc["original_max_position_embeddings"]
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction(sc["beta_fast"])), 0)
    high = min(math.ceil(correction(sc["beta_slow"])), dim - 1)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return plain / sc["factor"] * ramp + plain * (1 - ramp)


def _rope(x, inv_freq, mag):
    """x [T, ..., D]: rotate pairs (i, i + D/2) by position * inv_freq."""
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(ang) * mag, jnp.sin(ang) * mag
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


def attention(p, h, sizes, round_to=None, q_block=512):
    """One sequence ``h`` [T, d] (normed) -> [T, d]."""
    heads = sizes["num_attention_heads"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    rank, vd = sizes["kv_lora_rank"], sizes["v_head_dim"]
    sc = sizes.get("rope_scaling")
    t = h.shape[0]
    w = lambda k: _f32(p[k], round_to)
    inv_freq = yarn_inv_freq(rope, float(sizes["rope_theta"]), sc)
    m_all = yarn_mscale(sc["factor"], sc["mscale_all_dim"]) if sc else 1.0
    mag = yarn_mscale(sc["factor"], sc["mscale"]) / m_all if sc else 1.0
    scale = m_all * m_all / math.sqrt(nope + rope)

    q = (h @ w("wq")).reshape(t, heads, nope + rope)
    if "q_norm" in p:
        q = _rmsnorm(q, w("q_norm"))
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], inv_freq, mag)],
                        axis=-1)
    kva = h @ w("wkva")
    row = jnp.concatenate(
        [_rmsnorm(kva[:, :rank], w("kv_norm")),
         _rope(kva[:, rank:], inv_freq, mag)], axis=-1)
    row = _f32(row, round_to)           # what a cache would have kept
    kv = (row[:, :rank] @ w("wkvb")).reshape(t, heads, nope + vd)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(row[:, None, rank:], (t, heads, rope))], axis=-1)
    v = kv[..., nope:]
    out = []
    for s in range(0, t, q_block):      # blocks of queries, all keys each
        # (a whole block is cut at a start that is data, not a constant:
        # run operation by operation, one program then cuts them all)
        block = jax.lax.dynamic_slice_in_dim(q, s, q_block) \
            if s + q_block <= t else q[s:]
        scores = jnp.einsum("qhd,khd->hqk", block, k) * scale
        qpos = s + jnp.arange(scores.shape[1])
        scores = jnp.where(qpos[None, :, None] >= jnp.arange(t)[None, None],
                           scores, -jnp.inf)
        out.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v))
    return jnp.concatenate(out).reshape(t, heads * vd) @ w("wo")


def route(m, h, sizes):
    """``(idx [T, k], gates [T, k], margin [T])``: the choice is of
    ``score + bias``, the gates are of ``score``; ``margin`` is how far
    the last chosen expert is ahead of the first one left out."""
    k = sizes["num_experts_per_tok"]
    scores = jax.nn.sigmoid(h @ _f32(m["router"]))
    biased = scores + _f32(m["router_bias"])
    top, idx = jax.lax.top_k(biased, k + 1)
    idx = idx[:, :k]
    picked = jnp.take_along_axis(scores, idx, axis=-1)
    gates = sizes["routed_scaling_factor"] * picked \
        / jnp.sum(picked, axis=-1, keepdims=True)
    return idx, gates, top[:, k - 1] - top[:, k]


def expert_ffn(m, h, sizes, held, round_to=None, shared=True):
    """``(y [T, d], margin [T])``: the shared expert (counted where
    ``shared``) plus the held experts' part of the routed sum."""
    idx, gates, margin = route(m, h, sizes)
    first, count = held
    w = lambda k, j=None: _f32(m[k] if j is None else m[k][j], round_to)
    y = jnp.zeros_like(h)
    if shared and "shared_wg" in m:
        y = y + _swiglu(h, w("shared_wg"), w("shared_wu"), w("shared_wd"))
    for j in range(count):              # one expert at a time
        gate = jnp.sum(jnp.where(idx == first + j, gates, 0.0), axis=-1)
        y = y + gate[:, None] * _swiglu(h, w("wg", j), w("wu", j),
                                        w("wd", j))
    return y, margin


def _layer(p, x, sizes, held, round_to, q_block):
    w = lambda k: _f32(p[k], round_to)
    x = x + attention(p["attn"], _rmsnorm(x, w("ln1")), sizes, round_to,
                      q_block)
    h = _rmsnorm(x, w("ln2"))
    if "moe" in p:
        y, margin = expert_ffn(p["moe"], h, sizes, held, round_to)
        return x + y, margin
    return x + _swiglu(h, w("wg"), w("wu"), w("wd")), None


def forward(params, tokens, sizes, held=None, round_to=None, at=None,
            q_block=512):
    """One sequence ``tokens`` [T] -> ``(logits [T, V] float32, margins
    [expert layers, T])``: ``margins`` is each expert layer's routing
    margin per position (see :func:`route`), which tells a comparison
    where a rounding error may have picked another expert.  ``at``
    (positions) keeps only those rows of the logits: [T, V] does not fit
    beside the weights at 8.7 k positions x 65 k ids."""
    if held is None:
        held = (int(sizes.get("expert_offset", 0)), sizes["num_experts"])
    x = _f32(params["embed"], round_to)[jnp.asarray(tokens)]
    margins = []
    for stack in ("dense_layers", "layers"):
        if stack not in params:
            continue
        n = jax.tree_util.tree_leaves(params[stack])[0].shape[0]
        for i in range(n):
            p = jax.tree_util.tree_map(lambda a: a[i], params[stack])
            x, margin = _layer(p, x, sizes, held, round_to, q_block)
            if margin is not None:
                margins.append(margin)
    x = _rmsnorm(x, _f32(params["ln_f"], round_to))
    if at is not None:
        x = x[jnp.asarray(at)]
    logits = x @ _f32(params["head"], round_to)
    return logits, (jnp.stack(margins) if margins else None)
