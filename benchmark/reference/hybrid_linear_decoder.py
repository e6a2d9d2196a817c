"""Plain reference: a decoder whose layers mix either by Kimi Delta
Attention (KDA: a gated delta rule with a per-channel decay, behind a
short causal convolution) or by latent attention WITHOUT rotary, with one
leading dense SwiGLU layer and then expert layers (sigmoid router with a
selection bias, top-k, renormalised and scaled gates, a shared expert),
as ``kimi_linear`` publishes it (``config.json`` and the modelling file
beside it at huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct;
arXiv:2510.26692).  Straightforward float32 ``jax.numpy``: KDA by the
TOKEN-BY-TOKEN recurrence (a ``lax.scan`` over the tokens of the one
sequence: no chunks, no closed form), the NON-absorbed latent attention
with a full causal softmax (in blocks of queries only so that it fits), a
Python loop over experts (the expert layer of
``reference/latent_moe_decoder.py``: its router, its SwiGLU), no cache, no
kernels, nothing imported from the program.  Callers set
``jax.default_matmul_precision("highest")``.

It runs operation by operation, and on the chip every new shape of an
operation is a compile (a float32 ``highest`` product: seconds each).  So
products take their rows ``ROWS`` at a time: the two lengths that a run
pads its sequences to share one set of product programs (PERF.md, PR32).

It reads the program's PARAMETER TREE (weights are data; each leaf is
upcast to float32 where it is used): ``embed`` [V, d]; three stacks, each
leaf stacked on a leading axis over the stack's layers in model order —
``dense_layers`` (the leading dense layers), ``kda_layers`` (the expert
layers that mix by KDA), ``layers`` (the expert layers that mix by latent
attention); ``ln_f``; ``head`` [d, V].  A layer has ``ln1``, ``ln2``, a
mixer and an FFN.  Mixer ``kda``: ``wqkv`` [d, 3*H*dh] (q | k | v),
``conv`` [K, 3*H*dh], ``wfa`` [d, r] / ``wfb`` [r, H*dh] (decay), ``wga`` /
``wgb`` (output gate), ``wbeta`` [d, H], ``a_log`` [H], ``dt_bias``
[H*dh], ``o_norm`` [dh], ``wo`` [H*dh, d].  Mixer ``attn``: ``wq`` [d,
H*(nope+rope)], ``wkva`` [d, rank+rope], ``kv_norm`` [rank], ``wkvb``
[rank, H*(nope+v)], ``wo`` [H*v, d].  FFN: ``wg``/``wu``/``wd`` (dense) or
``moe`` as ``reference/latent_moe_decoder`` reads it.

``sizes`` is the configuration file's own dict (published key names);
which layer mixes how is ``sizes["linear_attn_config"]``'s two 1-based
lists.  ``held = (first, count)``: which experts the tree's expert
weights are; what the others would add is left out.

Departures from the published description, each on purpose (the
configuration file's ``assumed`` has the reasons):
1. The decay's and the output gate's low-rank maps are ``head_dim`` wide,
   the convolutions have no bias, ``a_log`` is per head and ``dt_bias``
   per channel, the output gate is a sigmoid and its RMSNorm is over each
   head's ``dh`` values, q and k are L2-normalised per head with
   ``dh^-1/2`` on q.
2. Only the experts in ``held`` contribute (the chip's share).
3. ``round_to`` (default None) rounds every weight, every latent row a
   cache would keep, and the recurrent state after every token to that
   dtype: NOT part of the model, it is how the benchmark reads what one
   precision lower would give.
"""

import math

import jax
import jax.numpy as jnp

from benchmark.reference import latent_moe_decoder as moe_ref

EPS = 1e-5
ROWS = 1024


def _rows(x, w):
    """``x @ w``, the rows of ``x`` at most ``ROWS`` at a time."""
    if x.shape[0] <= ROWS:
        return x @ w
    return jnp.concatenate([x[s:s + ROWS] @ w
                            for s in range(0, x.shape[0], ROWS)])


def _f32(x, round_to=None):
    if round_to is not None:
        x = x.astype(round_to)
    return x.astype(jnp.float32)


def _rmsnorm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + EPS) * scale


def kda_recurrence(q, k, v, g, beta, round_to=None):
    """One sequence, token by token: ``q``, ``k``, ``g`` [T, H, dk], ``v``
    [T, H, dv], ``beta`` [T, H] -> ``(o [T, H, dv], final state [H, dk,
    dv])``:

        S~ = Diag(e^{g_t}) S;  S = S~ + beta_t k_t (v_t - S~^T k_t)^T;
        o_t = S^T q_t
    """
    def token(s, inp):
        q, k, v, g, beta = inp
        s = jnp.exp(g)[:, :, None] * s
        u = beta[:, None] * (v - jnp.einsum("hkv,hk->hv", s, k))
        s = _f32(s + k[:, :, None] * u[:, None, :], round_to)
        return s, jnp.einsum("hkv,hk->hv", s, q)

    s0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
    s, o = jax.lax.scan(token, s0, (q, k, v, g, beta))
    return o, s


def kda_inputs(p, h, sizes, round_to=None):
    """``h`` [T, d] (normed) -> ``q, k, v, g`` [T, H, dh], ``beta`` [T, H]:
    projection, causal depthwise convolution + SiLU, the norms of q and k,
    the log-decay and the write strength."""
    lin = sizes["linear_attn_config"]
    heads, dh, kernel = (lin["num_heads"], lin["head_dim"],
                         lin["short_conv_kernel_size"])
    w = lambda name: _f32(p[name], round_to)
    t = h.shape[0]
    x = jnp.pad(_rows(h, w("wqkv")), ((kernel - 1, 0), (0, 0)))
    x = jax.nn.silu(sum(w("conv")[j] * x[j:j + t] for j in range(kernel)))
    q, k, v = (a.reshape(t, heads, dh) for a in jnp.split(x, 3, axis=-1))
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-12) \
        / math.sqrt(dh)
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-12)
    a = _rows(_rows(h, w("wfa")), w("wfb")) + w("dt_bias")
    g = -jnp.exp(w("a_log"))[:, None] * jax.nn.softplus(a).reshape(
        t, heads, dh)
    return q, k, v, g, jax.nn.sigmoid(_rows(h, w("wbeta")))


def kda(p, h, sizes, round_to=None):
    """One sequence ``h`` [T, d] (normed) -> [T, d]."""
    w = lambda name: _f32(p[name], round_to)
    q, k, v, g, beta = kda_inputs(p, h, sizes, round_to)
    o, _state = kda_recurrence(q, k, v, g, beta, round_to)
    z = _rows(_rows(h, w("wga")), w("wgb")).reshape(o.shape)
    y = _rmsnorm(o, w("o_norm")) * jax.nn.sigmoid(z)
    return _rows(y.reshape(h.shape[0], -1), w("wo"))


def attention(p, h, sizes, round_to=None, q_block=512):
    """Latent attention without rotary, one sequence ``h`` [T, d]
    (normed) -> [T, d]: the "rope" part of q and of the shared key is
    used as it is projected (``mla_use_nope``)."""
    heads = sizes["num_attention_heads"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    rank, vd = sizes["kv_lora_rank"], sizes["v_head_dim"]
    t = h.shape[0]
    w = lambda name: _f32(p[name], round_to)
    q = _rows(h, w("wq")).reshape(t, heads, nope + rope)
    kva = _rows(h, w("wkva"))
    row = jnp.concatenate(
        [_rmsnorm(kva[:, :rank], w("kv_norm")), kva[:, rank:]], axis=-1)
    row = _f32(row, round_to)           # what a cache would have kept
    kv = _rows(row[:, :rank], w("wkvb")).reshape(t, heads, nope + vd)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(row[:, None, rank:], (t, heads, rope))], axis=-1)
    v = kv[..., nope:]
    out = []
    for s in range(0, t, q_block):      # blocks of queries, all keys each
        block = jax.lax.dynamic_slice_in_dim(q, s, q_block) \
            if s + q_block <= t else q[s:]
        scores = jnp.einsum("qhd,khd->hqk", block, k) \
            / math.sqrt(nope + rope)
        qpos = s + jnp.arange(scores.shape[1])
        scores = jnp.where(qpos[None, :, None] >= jnp.arange(t)[None, None],
                           scores, -jnp.inf)
        out.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v))
    return _rows(jnp.concatenate(out).reshape(t, heads * vd), w("wo"))


def _swiglu(x, wg, wu, wd):
    return _rows(jax.nn.silu(_rows(x, wg)) * _rows(x, wu), wd)


def expert_ffn(m, h, sizes, held, round_to=None):
    """``(y [T, d], margin [T])``: the shared expert plus the held
    experts' part of the routed sum, one expert at a time; the router and
    ``margin`` are ``reference/latent_moe_decoder.route``'s."""
    idx, gates, margin = moe_ref.route(m, h, {
        "num_experts_per_tok": sizes["num_experts_per_token"],
        "routed_scaling_factor": sizes["routed_scaling_factor"]})
    first, count = held
    w = lambda k, j=None: _f32(m[k] if j is None else m[k][j],
                               round_to)
    y = _swiglu(h, w("shared_wg"), w("shared_wu"), w("shared_wd"))
    for j in range(count):
        gate = jnp.sum(jnp.where(idx == first + j, gates, 0.0), axis=-1)
        y = y + gate[:, None] * _swiglu(h, w("wg", j), w("wu", j),
                                        w("wd", j))
    return y, margin


def _layer(p, x, sizes, held, round_to, q_block):
    w = lambda name: _f32(p[name], round_to)
    h = _rmsnorm(x, w("ln1"))
    x = x + (kda(p["kda"], h, sizes, round_to) if "kda" in p
             else attention(p["attn"], h, sizes, round_to, q_block))
    h = _rmsnorm(x, w("ln2"))
    if "moe" in p:
        y, margin = expert_ffn(p["moe"], h, sizes, held, round_to)
        return x + y, margin
    return x + _swiglu(h, w("wg"), w("wu"), w("wd")), None


def forward(params, tokens, sizes, held=None, round_to=None, at=None,
            q_block=512):
    """One sequence ``tokens`` [T] -> ``(logits [T, V] float32, margins
    [expert layers, T])``; ``margins``, ``at`` and ``q_block`` as
    ``reference/latent_moe_decoder.forward`` has them."""
    if held is None:
        held = (int(sizes.get("expert_offset", 0)), sizes["num_experts"])
    kda_layers = set(sizes["linear_attn_config"]["kda_layers"])  # 1-based
    x = _f32(params["embed"], round_to)[jnp.asarray(tokens)]
    margins, taken = [], {}
    for number in range(1, sizes["num_hidden_layers"] + 1):
        stack = ("dense_layers" if number <= sizes["first_k_dense_replace"]
                 else "kda_layers" if number in kda_layers else "layers")
        i = taken[stack] = taken.get(stack, -1) + 1
        p = jax.tree_util.tree_map(lambda a: a[i], params[stack])
        if ("kda" in p) != (number in kda_layers):
            raise ValueError(f"layer {number}: the tree and "
                             "linear_attn_config disagree on its mixer")
        x, margin = _layer(p, x, sizes, held, round_to, q_block)
        if margin is not None:
            margins.append(margin)
    x = _rmsnorm(x, _f32(params["ln_f"], round_to))
    if at is not None:
        x = x[jnp.asarray(at)]
    logits = x @ _f32(params["head"], round_to)
    return logits, (jnp.stack(margins) if margins else None)
