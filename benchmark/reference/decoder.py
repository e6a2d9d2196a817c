"""Plain reference: the decoder-only transformer the repo's
``models/transformer.py`` implements, in straightforward float32
``jax.numpy``: full-materialization causal attention, no kernels, no
remat, no scan, no cache, no bf16, nothing imported from the program.
Callers set ``jax.default_matmul_precision("highest")``.

It reads the program's PARAMETER TREE (weights are data): ``embed``
[V, d], ``layers`` (each leaf stacked on a leading layer axis: ``ln1``,
``wqkv`` [d, 3d] laid out (3, heads, head_dim), ``wo``, ``ln2``, ``w1``,
``w2``), ``ln_f``, ``head`` [d, V].

The block, as written down from the program (and where it differs from
GPT-NeoX, whose sizes the benchmark's configurations borrow):
pre-norm RMSNorm without bias (eps 1e-6; NeoX: LayerNorm with bias);
rotary embedding over the WHOLE head, rotate-half pairing, base 10000
(NeoX: a quarter of the head); sequential residual, attention then MLP
(NeoX: parallel); tanh-approximated GELU; no biases anywhere; untied
output head.
"""

import math

import jax
import jax.numpy as jnp

EPS = 1e-6
ROPE_BASE = 10000.0


def _rmsnorm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + EPS) * scale


def _rope(x):
    """x [B, S, H, D]: rotate pairs (i, i + D/2) by position * base^(-i/(D/2))."""
    half = x.shape[-1] // 2
    freqs = ROPE_BASE ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _gelu_tanh(x):
    return 0.5 * x * (1 + jnp.tanh(
        math.sqrt(2 / math.pi) * (x + 0.044715 * x ** 3)))


def hidden(params, tokens, n_heads):
    """tokens [B, S] -> final normed hidden states [B, S, d], float32."""
    tokens = jnp.asarray(tokens)
    x = params["embed"].astype(jnp.float32)[tokens]
    b, s, d = x.shape
    hd = d // n_heads
    mask = jnp.tril(jnp.ones((s, s), bool))
    n_layers = params["layers"]["wqkv"].shape[0]
    for i in range(n_layers):
        p = {k: v[i].astype(jnp.float32) for k, v in params["layers"].items()}
        qkv = (_rmsnorm(x, p["ln1"]) @ p["wqkv"]).reshape(b, s, 3, n_heads, hd)
        q, k, v = _rope(qkv[:, :, 0]), _rope(qkv[:, :, 1]), qkv[:, :, 2]
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        attn = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
        x = x + attn.reshape(b, s, d) @ p["wo"]
        x = x + _gelu_tanh(_rmsnorm(x, p["ln2"]) @ p["w1"]) @ p["w2"]
    return _rmsnorm(x, params["ln_f"].astype(jnp.float32))


def logits(params, tokens, n_heads):
    return hidden(params, tokens, n_heads) @ params["head"].astype(
        jnp.float32)


def loss(params, tokens, n_heads):
    """Next-token cross entropy, mean over B x (S - 1)."""
    lg = logits(params, tokens, n_heads)[:, :-1]
    labels = jnp.asarray(tokens)[:, 1:]
    lg = lg - jnp.max(lg, axis=-1, keepdims=True)
    logp = lg - jnp.log(jnp.sum(jnp.exp(lg), axis=-1, keepdims=True))
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))
