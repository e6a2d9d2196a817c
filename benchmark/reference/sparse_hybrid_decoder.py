"""Plain reference: a decoder whose layers mix either by Kimi Delta
Attention with a bounded decay or by latent attention without rotary that
reads only the positions a DeepSeek sparse-attention (DSA) indexer selects,
whose residual is four hyper-connected streams (mHC, arXiv:2512.24880),
with one leading dense SwiGLU layer and then expert layers (sigmoid router
with a selection bias, top-k, renormalised and scaled gates, a shared
expert), every SwiGLU clamped: as ``glm5_next_text`` publishes it (the
catalog row of ``huggingface.co/zai-org/GLM-5.3-Flash`` ``config.json``;
DeepSeek-V3.2-Exp for the indexer).  Straightforward float32
``jax.numpy``: KDA by the TOKEN-BY-TOKEN recurrence, latent attention
with a full softmax over each query's selected positions (its OWN indexer
scores and selection, from float32 keys; per-head keys and values as
products with the latent rows, never held: ``dsa_attend``), a loop over
experts (a ``fori_loop``), no cache, no kernels, nothing imported from the
program.
Callers set ``jax.default_matmul_precision("highest")``.

Shapes.  Everything that acts on positions one at a time runs ``rows``
positions at a time, the recurrence carries its state from one such chunk
to the next, and attention takes its queries ``q_block`` at a time against
latent rows laid out over ``max_len`` positions: each small program is jitted
ONCE, whatever the sequence's length (a multiple of ``rows``), so the
reference costs compiles once and time in proportion to the length.

It reads the program's PARAMETER TREE (weights are data, upcast to float32
where they are used): ``embed`` [V, d]; ``dense_layers``, ``kda_layers``,
``layers`` (each leaf stacked on a leading axis over the stack's layers in
model order); ``ln_f``; ``head`` [d, V].  A layer has ``ln1``, ``ln2``,
``hc_attn`` / ``hc_ffn`` = {``phi`` [n d, 2n + n^2], ``alpha`` [3],
``bias`` [2n + n^2]}, a mixer and an FFN.  Mixer ``kda``: as
``reference/hybrid_linear_decoder.py`` reads it.  Mixer ``attn``: ``wqa``
[d, q_rank], ``q_a_norm``, ``wqb`` [q_rank, H*nope], ``wkva`` [d, rank],
``kv_norm``, ``wkvb`` [rank, H*(nope+v)], ``wo`` [H*v, d]; beside it
``index`` = {``wiq`` [q_rank, J*di], ``wik`` [d, di], ``ik_norm``,
``ik_bias`` [di], ``wiw`` [d, J]}.  FFN: ``wg``/``wu``/``wd`` or ``moe``.

``sizes`` is the configuration file's own dict (published key names, the
layer lists 0-based as that file has them).  ``held = (first, count)``:
which experts the tree's expert weights are; what the others would add is
left out.

Departures from the published description, each on purpose (the
configuration file's ``assumed`` has the reasons):
1. The indexer: queries from the normed query latent, one LayerNorm'd key
   a position, its first ``index_rope_dim`` rotated in interleaved pairs
   (base ``index_rope_theta``), keys pooled by the MEAN of ``index_kpool``
   positions, scores ``sum_j w_j ReLU(q_j . kbar)`` without the published
   constant scales (they change no choice) and without its fp8
   quantisation; ``index_topk`` counts positions, so ``index_topk /
   kpool`` groups are read: the query's own and the largest before it.
2. KDA's decay ``gate_lower_bound * sigmoid(exp(A_log) (h W_fa W_fb +
   dt_bias))``; the rest of KDA as ``hybrid_linear_decoder``.
3. mHC: ``RMSNorm`` of the flattened streams without a gain, Sinkhorn
   normalising rows then columns; the embedding copied into every stream,
   the streams summed before the final norm.
4. Only the experts in ``held`` contribute (the chip's share).
5. ``round_to`` (default None) rounds every weight, every latent row and
   index key a cache would keep, and the recurrent state after every
   token to that dtype: NOT part of the model, it is how the benchmark
   reads what one precision lower would give.
"""

import functools
import math
import typing

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import latent_moe_decoder as moe_ref

ROWS = 2048
_LOW, _HIGH = -1e30, 1e30


class Dims(typing.NamedTuple):
    """What the small programs need of ``sizes``: hashable, so that they
    are jitted on it."""
    eps: float
    heads: int
    nope: int
    vd: int
    rank: int
    kda_heads: int
    kda_dim: int
    kernel: int
    lower: float
    index_heads: int
    index_dim: int
    index_rope: int
    rope_theta: float
    topk: int
    kpool: int
    hc: int
    hc_iters: int
    hc_eps: float
    limit: float
    top_k: int
    scale: float


def dims_of(sizes):
    lin = sizes["linear_attn_config"]
    return Dims(
        float(sizes["rms_norm_eps"]), sizes["num_attention_heads"],
        sizes["qk_nope_head_dim"], sizes["v_head_dim"],
        sizes["kv_lora_rank"], lin["num_heads"], lin["head_dim"],
        lin["short_conv_kernel_size"], float(lin["gate_lower_bound"]),
        sizes["index_n_heads"], sizes["index_head_dim"],
        sizes["index_rope_dim"], float(sizes["index_rope_theta"]),
        sizes["index_topk"], sizes["index_kpool"], sizes["hc_mult"],
        sizes["hc_sinkhorn_iters"], float(sizes["hc_eps"]),
        float(sizes["swiglu_limit"]), sizes["num_experts_per_tok"],
        float(sizes["routed_scaling_factor"]))


def _f32(x, round_to=None):
    if round_to is not None:
        x = x.astype(round_to)
    return x.astype(jnp.float32)


def _tree(p, round_to):
    return jax.tree_util.tree_map(lambda a: _f32(a, round_to), p)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _swiglu(x, wg, wu, wd, limit):
    g, u = x @ wg, x @ wu
    return (jax.nn.silu(jnp.minimum(g, limit))
            * jnp.clip(u, -limit, limit)) @ wd


def _rotate(x, pos, d):
    """The first ``d.index_rope`` of ``x`` [T, ..., di] rotated by ``pos``
    [T], interleaved pairs."""
    half = d.index_rope // 2
    inv = d.rope_theta ** (-2.0 * np.arange(half) / d.index_rope)
    ang = pos[:, None].astype(jnp.float32) * inv
    ang = ang.reshape((ang.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    a, b = x[..., 0:d.index_rope:2], x[..., 1:d.index_rope:2]
    c, s = jnp.cos(ang), jnp.sin(ang)
    rot = jnp.stack([a * c - b * s, a * s + b * c], -1).reshape(
        x.shape[:-1] + (d.index_rope,))
    return jnp.concatenate([rot, x[..., d.index_rope:]], -1)


# -- the small programs, each jitted once -------------------------------------

@functools.partial(jax.jit, static_argnums=(2, 3))
def hc_in(p, x, d, round_to):
    """Streams ``x`` [C, n, dm] -> ``(h [C, dm], post [C, n], res [C, n,
    n])`` of one sublayer's hyper-connection."""
    p, n = _tree(p, round_to), d.hc
    flat = x.reshape(x.shape[0], -1)
    flat = flat * jax.lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True)
                                + d.eps)
    proj = flat @ p["phi"]
    a, b = p["alpha"], p["bias"]
    pre = jax.nn.sigmoid(a[0] * proj[:, :n] + b[:n])
    post = 2 * jax.nn.sigmoid(a[1] * proj[:, n:2 * n] + b[n:2 * n])
    res = jnp.exp(a[2] * proj[:, 2 * n:] + b[2 * n:]).reshape(-1, n, n)
    for _ in range(d.hc_iters):
        res = res / (res.sum(-1, keepdims=True) + d.hc_eps)
        res = res / (res.sum(-2, keepdims=True) + d.hc_eps)
    return jnp.einsum("ci,cid->cd", pre, x), post, res


@jax.jit
def hc_out(x, y, post, res):
    return jnp.einsum("cij,cjd->cid", res, x) + post[..., None] * y[:, None]


@functools.partial(jax.jit, static_argnums=(3, 4))
def kda_in(p, ln1, h, d, round_to):
    """``h`` [C, dm] -> the projections before the convolution [C, 3HD],
    the log-decay [C, H, D], beta [C, H], the output gate [C, H, D]."""
    p = _tree(p, round_to)
    y = _rmsnorm(h, _f32(ln1, round_to), d.eps)
    a = (y @ p["wfa"]) @ p["wfb"] + p["dt_bias"]
    g = d.lower * jax.nn.sigmoid(
        jnp.exp(p["a_log"])[:, None] * a.reshape(-1, d.kda_heads, d.kda_dim))
    z = ((y @ p["wga"]) @ p["wgb"]).reshape(g.shape)
    return y @ p["wqkv"], g, jax.nn.sigmoid(y @ p["wbeta"]), z


@functools.partial(jax.jit, static_argnums=(5, 6))
def kda_chunk(p, raw, g, beta, carry, d, round_to):
    """One chunk of the recurrence: ``raw`` [C, 3HD] behind the ``K - 1``
    rows before it, the state before it -> ``(o [C, H, D], (raw's last K
    - 1 rows, state))``."""
    conv = _f32(p["conv"], round_to)
    before, state = carry
    c = raw.shape[0]
    window = jnp.concatenate([before, raw])
    x = jax.nn.silu(sum(conv[j] * window[j:j + c] for j in range(d.kernel)))
    q, k, v = (a.reshape(c, d.kda_heads, d.kda_dim)
               for a in jnp.split(x, 3, axis=-1))
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-12) \
        / math.sqrt(d.kda_dim)
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-12)

    def token(s, inp):
        q, k, v, g, beta = inp
        s = jnp.exp(g)[:, :, None] * s
        u = beta[:, None] * (v - jnp.einsum("hkv,hk->hv", s, k))
        s = _f32(s + k[:, :, None] * u[:, None, :], round_to)
        return s, jnp.einsum("hkv,hk->hv", s, q)

    state, o = jax.lax.scan(token, state, (q, k, v, g, beta))
    return o, (window[-(d.kernel - 1):], state)


@functools.partial(jax.jit, static_argnums=(3, 4))
def kda_out(p, o, z, d, round_to):
    p = _tree(p, round_to)
    y = _rmsnorm(o, p["o_norm"], d.eps) * jax.nn.sigmoid(z)
    return y.reshape(o.shape[0], -1) @ p["wo"]


@functools.partial(jax.jit, static_argnums=(4, 5))
def dsa_in(pa, pi, ln1, h_pos, d, round_to):
    """``(h [C, dm], positions [C])`` -> ``(q [C, H, nope], the latent row
    [C, rank], the index key [C, di], index queries [C, J, di], index
    weights [C, J])``."""
    h, pos = h_pos
    pa, pi = _tree(pa, round_to), _tree(pi, round_to)
    y = _rmsnorm(h, _f32(ln1, round_to), d.eps)
    cq = _rmsnorm(y @ pa["wqa"], pa["q_a_norm"], d.eps)
    q = (cq @ pa["wqb"]).reshape(-1, d.heads, d.nope)
    row = _f32(_rmsnorm(y @ pa["wkva"], pa["kv_norm"], d.eps), round_to)
    ik = y @ pi["wik"]
    ik = ik - ik.mean(-1, keepdims=True)
    ik = ik * jax.lax.rsqrt(jnp.mean(ik * ik, -1, keepdims=True) + d.eps) \
        * pi["ik_norm"] + pi["ik_bias"]
    ik = _f32(_rotate(ik, pos, d), round_to)
    iq = _rotate((cq @ pi["wiq"]).reshape(-1, d.index_heads, d.index_dim),
                 pos, d)
    return q, row, ik, iq, y @ pi["wiw"]


@functools.partial(jax.jit, static_argnums=(7,))
def dsa_attend(q, iq, w, start, rows, ikbar, wkvb, d):
    """Queries ``q`` [B, H, nope] at positions ``start + [0, B)`` against
    the latent rows ``rows`` [positions, rank] of all positions and the
    groups' mean index keys ``ikbar`` [G, di]: the indexer's score of
    every group before each query, its selection (its own group and the
    largest before it), a softmax over the selected positions only.  Per
    head ``k = row W_UK`` and ``v = row W_UV`` (``wkvb``); the sums are
    taken as ``(q W_UK^T) row^T`` and ``(p row) W_UV``, the same sums in
    float32, so that no per-head key or value of a long context is ever
    held (17,408 positions x 64 heads x 512 would be 2.3 GB).  Returns
    ``(out [B, H, v], read [B, positions] bool)``."""
    b, groups = q.shape[0], ikbar.shape[0]
    t = start + jnp.arange(b)
    own = (t // d.kpool)[:, None]
    gi = jnp.arange(groups)[None]
    score = jnp.einsum("bjg,bj->bg", jax.nn.relu(
        jnp.einsum("bjd,gd->bjg", iq, ikbar)), w)
    score = jnp.where(gi < own, score, _LOW)
    score = jnp.where(gi == own, _HIGH, score)
    top, idx = jax.lax.top_k(score, min(d.topk // d.kpool, groups))
    chosen = jnp.zeros((b, groups), bool).at[
        jnp.arange(b)[:, None], idx].set(top > _LOW / 2)
    read = jnp.repeat(chosen, d.kpool, axis=1)[:, :rows.shape[0]] \
        & (jnp.arange(rows.shape[0])[None] <= t[:, None])
    up = wkvb.reshape(d.rank, d.heads, d.nope + d.vd)
    s = jnp.einsum("bhr,kr->bhk", jnp.einsum("bhn,rhn->bhr", q,
                                               up[..., :d.nope]), rows)
    s = jnp.where(read[:, None], s / math.sqrt(d.nope), -jnp.inf)
    o = jnp.einsum("bhk,kr->bhr", jax.nn.softmax(s, -1), rows)
    return jnp.einsum("bhr,rhv->bhv", o, up[..., d.nope:]), read


@functools.partial(jax.jit, static_argnums=(2, 3))
def dense_ffn(p, h, d, round_to):
    p = _tree(p, round_to)
    y = _rmsnorm(h, p["ln2"], d.eps)
    return _swiglu(y, p["wg"], p["wu"], p["wd"], d.limit)


@functools.partial(jax.jit, static_argnums=(3, 4))
def expert_ffn(m, ln2, h, d_held, round_to):
    """``(y [C, dm], margin [C])``: the shared expert plus the held
    experts' part of the routed sum, one expert at a time."""
    d, (first, count) = d_held
    y = _rmsnorm(h, _f32(ln2, round_to), d.eps)
    idx, gates, margin = moe_ref.route(
        {"router": m["router"], "router_bias": m["router_bias"]}, y,
        {"num_experts_per_tok": d.top_k, "routed_scaling_factor": d.scale})
    w = lambda k, j=None: _f32(m[k] if j is None else m[k][j], round_to)
    out = _swiglu(y, w("shared_wg"), w("shared_wu"), w("shared_wd"),
                  d.limit)

    def expert(j, out):     # a loop, not unrolled: one body to compile
        gate = jnp.sum(jnp.where(idx == first + j, gates, 0.0), axis=-1)
        return out + gate[:, None] * _swiglu(y, w("wg", j), w("wu", j),
                                             w("wd", j), d.limit)

    return jax.lax.fori_loop(0, count, expert, out), margin


# -- the forward pass ---------------------------------------------------------

def _chunks(n, rows):
    return [slice(s, s + rows) for s in range(0, n, rows)]


def _kda(p, ln1, h, d, round_to, rows):
    """``h`` [T, dm] -> [T, dm], a chunk of ``rows`` at a time: its
    projections, its part of the recurrence, its output."""
    carry = (jnp.zeros((d.kernel - 1, 3 * d.kda_heads * d.kda_dim)),
             jnp.zeros((d.kda_heads, d.kda_dim, d.kda_dim)))
    out = []
    for c in _chunks(len(h), rows):
        raw, g, beta, z = kda_in(p, ln1, h[c], d, round_to)
        o, carry = kda_chunk(p, raw, g, beta, carry, d, round_to)
        out.append(kda_out(p, o, z, d, round_to))
    return jnp.concatenate(out)


def _dsa(pa, pi, ln1, h, d, round_to, rows, q_block, max_len, keep_reads):
    """``(out [T, dm], read [T, positions] bool or None)``: T a multiple
    of ``rows``; latent rows laid out over ``max_len`` positions (one
    shape for every sequence)."""
    t = len(h)
    parts = [dsa_in(pa, pi, ln1, (h[c], jnp.arange(c.start, c.stop)), d,
                    round_to) for c in _chunks(t, rows)]
    q, row, ik, iq, w = (jnp.concatenate(a) for a in zip(*parts))
    del parts
    span = -(-max(max_len, t) // rows) * rows
    pad = lambda a: jnp.pad(a, ((0, span - t),) + ((0, 0),) * (a.ndim - 1))
    row, ik = pad(row), pad(ik)
    ikbar = ik.reshape(-1, d.kpool, ik.shape[-1]).mean(1)
    wkvb, wo = _f32(pa["wkvb"], round_to), _f32(pa["wo"], round_to)
    out, reads = [], []
    for s in range(0, t, q_block):
        o, r = dsa_attend(q[s:s + q_block], iq[s:s + q_block],
                          w[s:s + q_block], s, row, ikbar, wkvb, d)
        out.append(o.reshape(o.shape[0], -1) @ wo)
        if keep_reads:
            reads.append(r)
    return jnp.concatenate(out), jnp.concatenate(reads) if keep_reads \
        else None


def _sublayer(hp, xs, d, round_to, f):
    """The streams, a list of ``[rows, n, dm]`` chunks, through one
    hyper-connected sublayer ``f(h [T, dm]) -> (y, aux)``; chunks, so that
    the streams of a long sequence are never held twice."""
    parts = [hc_in(hp, x, d, round_to) for x in xs]
    y, aux = f(jnp.concatenate([h for h, _post, _res in parts]))
    out, start = [], 0
    for x, (_h, post, res) in zip(xs, parts):
        out.append(hc_out(x, y[start:start + len(x)], post, res))
        start += len(x)
    return out, aux


def forward(params, tokens, sizes, held=None, round_to=None, at=None,
            q_block=256, rows=ROWS, max_len=None, selections=False):
    """One sequence ``tokens`` [T] (T a multiple of ``rows``, or less than
    it) -> ``(logits [len(at) or T, V] float32, routing margins [expert
    layers, T])``, and with ``selections=True`` also, per DSA layer, which
    positions each query read ([T, positions] bool)."""
    d = dims_of(sizes)
    if held is None:
        held = (int(sizes.get("expert_offset", 0)),
                sizes["n_routed_experts"])
    held = tuple(int(v) for v in held)
    tokens = np.asarray(tokens)
    rows = min(rows, len(tokens))
    if len(tokens) % rows:
        raise ValueError(f"{len(tokens)} tokens are not chunks of {rows}")
    max_len = int(max_len or sizes["max_position_embeddings"])
    kda_layers = set(sizes["linear_attn_config"]["kda_layers"])  # 0-based
    embed = _f32(params["embed"], round_to)
    xs = [jnp.broadcast_to(embed[jnp.asarray(tokens[c])][:, None],
                           (c.stop - c.start, d.hc, embed.shape[1]))
          for c in _chunks(len(tokens), rows)]
    margins, reads, taken = [], [], {}
    for number in range(sizes["num_hidden_layers"]):
        stack = ("dense_layers" if number < sizes["first_k_dense_replace"]
                 else "kda_layers" if number in kda_layers else "layers")
        i = taken[stack] = taken.get(stack, -1) + 1
        p = jax.tree_util.tree_map(lambda a: a[i], params[stack])
        if ("kda" in p) != (number in kda_layers):
            raise ValueError(f"layer {number}: the tree and "
                             "linear_attn_config disagree on its mixer")

        def mixer(h):
            if "kda" in p:
                return _kda(p["kda"], p["ln1"], h, d, round_to, rows), None
            return _dsa(p["attn"], p["index"], p["ln1"], h, d, round_to,
                        rows, q_block, max_len, selections)

        def ffn(h):
            chunks = _chunks(len(h), rows)
            if "moe" in p:
                out = [expert_ffn(p["moe"], p["ln2"], h[c], (d, held),
                                  round_to) for c in chunks]
                return (jnp.concatenate([o[0] for o in out]),
                        jnp.concatenate([o[1] for o in out]))
            dense = {k: p[k] for k in ("ln2", "wg", "wu", "wd")}
            return jnp.concatenate([dense_ffn(dense, h[c], d, round_to)
                                    for c in chunks]), None

        xs, read = _sublayer(p["hc_attn"], xs, d, round_to, mixer)
        if selections and read is not None:
            reads.append(read)
        xs, margin = _sublayer(p["hc_ffn"], xs, d, round_to, ffn)
        if margin is not None:
            margins.append(margin)
    x = jnp.concatenate([jnp.sum(x, axis=1) for x in xs])
    if at is not None:
        x = x[jnp.asarray(at)]
    x = _rmsnorm(x, _f32(params["ln_f"], round_to), d.eps)
    logits = x @ _f32(params["head"], round_to)
    out = (logits, jnp.stack(margins) if margins else None)
    return out + (reads,) if selections else out
