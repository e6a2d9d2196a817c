"""Attention ops: flash attention (pallas, online softmax) + XLA reference.

Layout convention: ``[batch, seq, heads, head_dim]`` at the API boundary
(the natural layout for sequence-sharded meshes — the seq axis is axis 1
everywhere, so a NamedSharding P(None, 'sp', None, None) applies to q/k/v
alike).  The kernel internally flattens to ``[batch*heads, seq, head_dim]``
and tiles seq onto the MXU.

The pallas kernel computes softmax(q kᵀ·scale + mask) v blockwise with the
online-softmax recurrence (running max / running sum / rescaled
accumulator), so the [S, S] score matrix never materializes in HBM —
memory is O(block_q · seq) VMEM per program instead of O(seq²).  The
backward pass recomputes attention blockwise under ``jax.checkpoint``
semantics via a custom VJP over the reference implementation.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from tensorflowonspark_tpu.ops._pallas import resolve_interpret

_NEG_INF = -1e30


# -- rotary position embeddings ----------------------------------------------

@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """YaRN rotary scaling as ``deepseek_yarn`` configs state it
    (Peng et al., arXiv:2309.00071): dimensions that turn more than
    ``beta_fast`` times over the ORIGINAL context keep their frequency,
    those that turn fewer than ``beta_slow`` times are interpolated by
    ``factor``, a linear ramp joins the two."""
    factor: float
    original_max_seq: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


def yarn_mscale(factor, mscale=1.0):
    """``0.1 * mscale * ln(factor) + 1`` (1 where nothing is scaled)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_softmax_scale(width, scaling):
    """``width^-0.5 * m^2``, ``m = yarn_mscale(factor, mscale_all_dim)``:
    the factor a YaRN model multiplies its attention scores by."""
    m = 1.0 if scaling is None else yarn_mscale(
        scaling.factor, scaling.mscale_all_dim)
    return m * m / math.sqrt(width)


def yarn_frequencies(head_dim, base, scaling):
    """Inverse frequencies [head_dim // 2] under ``scaling``."""
    half = head_dim // 2
    plain = 1.0 / (base ** (jnp.arange(0, half, dtype=jnp.float32) / half))

    def correction_dim(rotations):
        return head_dim * math.log(
            scaling.original_max_seq / (rotations * 2 * math.pi)
        ) / (2 * math.log(base))

    low = max(math.floor(correction_dim(scaling.beta_fast)), 0)
    high = min(math.ceil(correction_dim(scaling.beta_slow)), head_dim - 1)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return plain / scaling.factor * ramp + plain * (1.0 - ramp)


def rope_angles(seq_len, head_dim, base=10000.0, dtype=jnp.float32,
                scaling=None):
    """(cos, sin) tables of shape [seq_len, head_dim//2].  With a
    :class:`YarnScaling` the frequencies are YaRN's and both tables carry
    ``yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)``."""
    half = head_dim // 2
    if scaling is None:
        freqs = 1.0 / (base ** (jnp.arange(0, half, dtype=jnp.float32)
                                / half))
        mag = 1.0
    else:
        freqs = yarn_frequencies(head_dim, base, scaling)
        mag = yarn_mscale(scaling.factor, scaling.mscale) / yarn_mscale(
            scaling.factor, scaling.mscale_all_dim)
    pos = jnp.arange(seq_len, dtype=jnp.float32)
    ang = jnp.outer(pos, freqs)
    if mag == 1.0:
        return jnp.cos(ang).astype(dtype), jnp.sin(ang).astype(dtype)
    return (jnp.cos(ang) * mag).astype(dtype), \
        (jnp.sin(ang) * mag).astype(dtype)


def apply_rope(x, cos, sin, positions=None):
    """Rotate [B, S, H, D] by the (cos, sin) tables.

    ``positions`` ([B, S] int) selects rows of the tables — used by
    sequence-parallel shards whose local positions are offset into the
    global sequence.
    """
    if positions is not None:
        cos = cos[positions]  # [B, S, half]
        sin = sin[positions]
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    else:
        cos = cos[None, : x.shape[1], None, :]
        sin = sin[None, : x.shape[1], None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# -- reference implementation (pure XLA) -------------------------------------

def mha_reference(q, k, v, *, causal=False, scale=None, q_offset=0, kv_offset=0):
    """Full-materialization attention; [B, S, H, D] in/out.

    ``q_offset``/``kv_offset`` shift the causal mask's global positions —
    the hook ring attention uses to attend a local q shard against a
    remote k/v shard (parallel/ring.py).
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    # [B, H, Sq, Skv]
    scores = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) * scale
    if causal:
        qpos = q_offset + jnp.arange(q.shape[1])
        kpos = kv_offset + jnp.arange(k.shape[1])
        mask = qpos[:, None] >= kpos[None, :]
        scores = jnp.where(mask[None, None], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, vf)
    return out.astype(q.dtype)


# -- pallas flash attention ---------------------------------------------------

def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, seq_q, seq_kv,
                  block_q, block_kv, scale, causal):
    """One program of grid (B*H, num_q_blocks): one [block_q, D] q tile
    against the whole (masked) kv range."""
    import jax.experimental.pallas as pl

    q_blk = q_ref[0].astype(jnp.float32) * scale  # [block_q, D]
    head_dim = v_ref.shape[-1]  # the output's width is v's, not q's
    q_start = pl.program_id(1) * block_q

    num_kv = pl.cdiv(seq_kv, block_kv)
    if causal:
        # blocks strictly above the diagonal contribute nothing; the
        # dynamic fori bound trims them (the loop body stays static).
        num_kv = lax.min(
            num_kv, lax.div(q_start + block_q + block_kv - 1, block_kv)
        )

    def body(j, carry):
        acc, m, l = carry
        kv_start = j * block_kv
        k_blk = k_ref[0, pl.ds(kv_start, block_kv), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(kv_start, block_kv), :].astype(jnp.float32)
        s = jnp.dot(q_blk, k_blk.T, preferred_element_type=jnp.float32)
        # tail masking (seq not a multiple of block) + causal masking, on
        # global positions
        qpos = q_start + lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
        kpos = kv_start + lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
        valid = (kpos < seq_kv) & (qpos < seq_q)
        if causal:
            valid &= qpos >= kpos
        s = jnp.where(valid, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[:, None] + jnp.dot(
            p, v_blk, preferred_element_type=jnp.float32
        )
        return acc_new, m_new, l_new

    acc0 = jnp.zeros((block_q, head_dim), jnp.float32)
    m0 = jnp.full((block_q,), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc, m, l = lax.fori_loop(0, num_kv, body, (acc0, m0, l0))
    # fully-masked rows (tail padding) have l == 0; avoid 0/0
    out = acc / jnp.maximum(l, 1e-30)[:, None]
    o_ref[0] = out.astype(o_ref.dtype)
    # log-sum-exp per row, saved for the O(S*block) backward; trailing
    # singleton keeps the block TPU-tileable (block_q x 1 vs the (8,128)
    # divisibility rule)
    lse_ref[0, :, 0] = m + jnp.log(jnp.maximum(l, 1e-30))


def _flash_forward(q, k, v, *, causal, scale, block_q, block_kv, interpret):
    import jax.experimental.pallas as pl

    b, sq, h, d = q.shape
    skv, dv = k.shape[1], v.shape[-1]
    block_q = min(block_q, max(sq, 8))
    block_kv = min(block_kv, max(skv, 8))

    def flat(x):  # [B, S, H, D] -> [B*H, S, D]
        return x.transpose(0, 2, 1, 3).reshape(
            b * h, x.shape[1], x.shape[-1])

    qf, kf, vf = flat(q), flat(k), flat(v)
    pad_q = (-sq) % block_q
    pad_kv = (-skv) % block_kv
    if pad_q:
        qf = jnp.pad(qf, ((0, 0), (0, pad_q), (0, 0)))
    if pad_kv:
        kf = jnp.pad(kf, ((0, 0), (0, pad_kv), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad_kv), (0, 0)))

    grid = (b * h, (sq + pad_q) // block_q)
    kernel = functools.partial(
        _flash_kernel,
        seq_q=sq,
        seq_kv=skv,
        block_q=block_q,
        block_kv=block_kv,
        scale=scale,
        causal=causal,
    )
    # the kernel keeps one head's whole K and V in VMEM (double-buffered)
    # beside its [block_q, block_kv] float32 temporaries; past the
    # compiler's default scoped limit (16 MiB: 8k positions of a 192-wide
    # key and a 128-wide value) say how much it needs
    resident = 2 * (skv + pad_kv) * (d + dv) * k.dtype.itemsize \
        + 8 * block_q * block_kv * 4 + 4 * block_q * (d + dv) * 4
    extra = {}
    if resident > 12 * 2 ** 20:
        from jax.experimental.pallas import tpu as pltpu

        extra["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=min(resident + 16 * 2 ** 20, 100 * 2 ** 20))
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i: (bh, i, 0)),
            pl.BlockSpec((1, skv + pad_kv, d), lambda bh, i: (bh, 0, 0)),
            pl.BlockSpec((1, skv + pad_kv, dv), lambda bh, i: (bh, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, block_q, dv), lambda bh, i: (bh, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, i: (bh, i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b * h, sq + pad_q, dv), q.dtype),
            jax.ShapeDtypeStruct((b * h, sq + pad_q, 1), jnp.float32),
        ),
        interpret=interpret,
        name="tfos_flash_fwd",
        **extra,
    )(qf, kf, vf)
    out = out[:, :sq].reshape(b, h, sq, dv).transpose(0, 2, 1, 3)
    lse = lse[:, :sq, 0].reshape(b, h, sq)  # [B, H, Sq]
    return out, lse


def _bwd_recompute(q_blk, k_blk, v_blk, g_blk, lse, delta, q_start,
                   kv_start, *, seq_q, seq_kv, scale, causal):
    """Shared backward recompute for one (q block, kv block) pair:
    probabilities from (q, k, lse) and the score gradient
        p  = exp(q kᵀ·scale − lse)        (masked)
        ds = p · (g vᵀ − delta) · scale
    Both kernels MUST use this — a masking/math fix applied to one of
    dq vs dk/dv only would silently desynchronize the gradients."""
    block_q, block_kv = q_blk.shape[0], k_blk.shape[0]
    s = jnp.dot(q_blk, k_blk.T, preferred_element_type=jnp.float32) * scale
    qpos = q_start + lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
    kpos = kv_start + lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
    valid = (kpos < seq_kv) & (qpos < seq_q)
    if causal:
        valid &= qpos >= kpos
    p = jnp.where(valid, jnp.exp(s - lse[:, None]), 0.0)
    dp = jnp.dot(g_blk, v_blk.T, preferred_element_type=jnp.float32)
    ds = p * (dp - delta[:, None]) * scale
    return p, ds


def _dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, dq_ref, *,
               seq_q, seq_kv, block_q, block_kv, scale, causal):
    """dq for one [block_q, D] q tile: loop kv blocks (causal-trimmed,
    like the forward), recomputing p from (q, k, lse):
        p = exp(q kᵀ·scale − lse);  ds = p·(g vᵀ − delta)·scale;
        dq = ds k
    """
    import jax.experimental.pallas as pl

    q_blk = q_ref[0].astype(jnp.float32)          # [bq, D]
    g_blk = g_ref[0].astype(jnp.float32)          # [bq, D]
    lse = lse_ref[0, :, 0]                        # [bq]
    delta = delta_ref[0, :, 0]                    # [bq]
    head_dim = q_blk.shape[-1]
    q_start = pl.program_id(1) * block_q

    num_kv = pl.cdiv(seq_kv, block_kv)
    if causal:
        num_kv = lax.min(
            num_kv, lax.div(q_start + block_q + block_kv - 1, block_kv)
        )

    def body(j, dq):
        kv_start = j * block_kv
        k_blk = k_ref[0, pl.ds(kv_start, block_kv), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(kv_start, block_kv), :].astype(jnp.float32)
        _, ds = _bwd_recompute(
            q_blk, k_blk, v_blk, g_blk, lse, delta, q_start, kv_start,
            seq_q=seq_q, seq_kv=seq_kv, scale=scale, causal=causal)
        return dq + jnp.dot(ds, k_blk, preferred_element_type=jnp.float32)

    dq = lax.fori_loop(
        0, num_kv, body, jnp.zeros((block_q, head_dim), jnp.float32)
    )
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, *, seq_q, seq_kv, block_q, block_kv, scale, causal):
    """dk/dv for one [block_kv, D] kv tile: loop q blocks starting at the
    diagonal (causal lower bound — above-diagonal q blocks see none of
    this kv tile):
        dv += pᵀ g;  dk += dsᵀ q
    """
    import jax.experimental.pallas as pl

    k_blk = k_ref[0].astype(jnp.float32)          # [bkv, D]
    v_blk = v_ref[0].astype(jnp.float32)          # [bkv, D]
    head_dim = k_blk.shape[-1]
    kv_start = pl.program_id(1) * block_kv

    num_q = pl.cdiv(seq_q, block_q)
    i0 = lax.div(kv_start, block_q) if causal else 0

    def body(i, carry):
        dk, dv = carry
        q_start = i * block_q
        q_blk = q_ref[0, pl.ds(q_start, block_q), :].astype(jnp.float32)
        g_blk = g_ref[0, pl.ds(q_start, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(q_start, block_q), 0]
        delta = delta_ref[0, pl.ds(q_start, block_q), 0]
        p, ds = _bwd_recompute(
            q_blk, k_blk, v_blk, g_blk, lse, delta, q_start, kv_start,
            seq_q=seq_q, seq_kv=seq_kv, scale=scale, causal=causal)
        dv = dv + jnp.dot(p.T, g_blk, preferred_element_type=jnp.float32)
        dk = dk + jnp.dot(ds.T, q_blk, preferred_element_type=jnp.float32)
        return dk, dv

    zeros = jnp.zeros((block_kv, head_dim), jnp.float32)
    dk, dv = lax.fori_loop(i0, num_q, body, (zeros, zeros))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_backward_pallas(q, k, v, out, lse, g, *, causal, scale, block_q,
                           block_kv, interpret):
    """Blockwise pallas backward: dq over q tiles (kv loop trimmed above
    the diagonal) + dk/dv over kv tiles (q loop started at the diagonal)
    — the causal triangle is never computed, unlike the XLA fallback
    which computes and masks it (~2x the attention-backward FLOPs at
    long seq)."""
    import jax.experimental.pallas as pl

    b, sq, h, d = q.shape
    skv = k.shape[1]
    block_q = min(block_q, max(sq, 8))
    block_kv = min(block_kv, max(skv, 8))

    def flat(x):  # [B, S, H, D] -> [B*H, S, D]
        return x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], d)

    qf, kf, vf, gf, of = flat(q), flat(k), flat(v), flat(g), flat(out)
    delta = jnp.sum(gf.astype(jnp.float32) * of.astype(jnp.float32),
                    axis=-1, keepdims=True)  # [B*H, Sq, 1]
    lsef = lse.reshape(b * h, sq, 1)

    pad_q = (-sq) % block_q
    pad_kv = (-skv) % block_kv
    if pad_q:
        zq = ((0, 0), (0, pad_q), (0, 0))
        qf, gf = jnp.pad(qf, zq), jnp.pad(gf, zq)
        lsef, delta = jnp.pad(lsef, zq), jnp.pad(delta, zq)
    if pad_kv:
        zkv = ((0, 0), (0, pad_kv), (0, 0))
        kf, vf = jnp.pad(kf, zkv), jnp.pad(vf, zkv)

    sq_p, skv_p = sq + pad_q, skv + pad_kv
    common = dict(seq_q=sq, seq_kv=skv, block_q=block_q,
                  block_kv=block_kv, scale=scale, causal=causal)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **common),
        grid=(b * h, sq_p // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i: (bh, i, 0)),
            pl.BlockSpec((1, skv_p, d), lambda bh, i: (bh, 0, 0)),
            pl.BlockSpec((1, skv_p, d), lambda bh, i: (bh, 0, 0)),
            pl.BlockSpec((1, block_q, d), lambda bh, i: (bh, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, i: (bh, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, i: (bh, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, i: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq_p, d), q.dtype),
        interpret=interpret,
        name="tfos_flash_bwd_dq",
    )(qf, kf, vf, gf, lsef, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **common),
        grid=(b * h, skv_p // block_kv),
        in_specs=[
            pl.BlockSpec((1, sq_p, d), lambda bh, j: (bh, 0, 0)),
            pl.BlockSpec((1, block_kv, d), lambda bh, j: (bh, j, 0)),
            pl.BlockSpec((1, block_kv, d), lambda bh, j: (bh, j, 0)),
            pl.BlockSpec((1, sq_p, d), lambda bh, j: (bh, 0, 0)),
            pl.BlockSpec((1, sq_p, 1), lambda bh, j: (bh, 0, 0)),
            pl.BlockSpec((1, sq_p, 1), lambda bh, j: (bh, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, block_kv, d), lambda bh, j: (bh, j, 0)),
            pl.BlockSpec((1, block_kv, d), lambda bh, j: (bh, j, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b * h, skv_p, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, skv_p, d), v.dtype),
        ),
        interpret=interpret,
        name="tfos_flash_bwd_dkv",
    )(qf, kf, vf, gf, lsef, delta)

    def unflat(x, s):  # [B*H, S, D] -> [B, S, H, D]
        return x[:, :s].reshape(b, h, s, d).transpose(0, 2, 1, 3)

    return unflat(dq, sq), unflat(dk, skv), unflat(dv, skv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, scale, block_q, block_kv, interpret, bwd_impl):
    out, _lse = _flash_forward(
        q, k, v, causal=causal, scale=scale, block_q=block_q,
        block_kv=block_kv, interpret=interpret,
    )
    return out


def _flash_fwd(q, k, v, causal, scale, block_q, block_kv, interpret,
               bwd_impl):
    out, lse = _flash_forward(
        q, k, v, causal=causal, scale=scale, block_q=block_q,
        block_kv=block_kv, interpret=interpret,
    )
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, scale, block_q, block_kv, interpret, bwd_impl, res, g):
    if res[2].shape[-1] != res[0].shape[-1]:
        raise NotImplementedError(
            "flash_attention's backward needs v as wide as q and k "
            f"(got q/k {res[0].shape[-1]}, v {res[2].shape[-1]}): only "
            "the forward takes a narrower v (latent-attention prefill)")
    if bwd_impl == "pallas":
        q, k, v, out, lse = res
        return _flash_backward_pallas(
            q, k, v, out, lse, g, causal=causal, scale=scale,
            block_q=block_q, block_kv=block_kv, interpret=interpret,
        )
    return _flash_bwd_xla(causal, scale, block_q, block_kv, res, g)


def _flash_bwd_xla(causal, scale, block_q, block_kv, res, g):
    """Blockwise flash backward (pure XLA, lax.scan over q blocks).

    Memory is O(block_q * S_kv) per step instead of the O(S^2) score
    matrix a naive softmax backward materializes — per-block scores are
    recomputed from (q, k) and renormalized with the saved logsumexp:
        p   = exp(s - lse)
        dv += p^T g
        ds  = p * (g v^T - rowsum(g * out))
        dq  = scale * ds k ;  dk += scale * ds^T q
    """
    q, k, v, out, lse = res
    b, sq, h, d = q.shape
    skv = k.shape[1]
    block = min(block_q, max(sq, 8))
    pad_q = (-sq) % block
    nb = (sq + pad_q) // block

    def heads(x):  # [B, S, H, D] -> [B, H, S, D] f32
        return x.transpose(0, 2, 1, 3).astype(jnp.float32)

    qt, gt, ot = heads(q), heads(g), heads(out)
    kt, vt = heads(k), heads(v)
    delta = jnp.sum(gt * ot, axis=-1)  # [B, H, Sq]

    def padq(x):
        return jnp.pad(x, ((0, 0), (0, 0), (0, pad_q)) + ((0, 0),) * (x.ndim - 3))

    # stack q blocks on a leading scan axis: [nb, B, H, block, ...]
    qb = padq(qt).reshape(b, h, nb, block, d).transpose(2, 0, 1, 3, 4)
    gb = padq(gt).reshape(b, h, nb, block, d).transpose(2, 0, 1, 3, 4)
    lseb = padq(lse).reshape(b, h, nb, block).transpose(2, 0, 1, 3)
    deltab = padq(delta).reshape(b, h, nb, block).transpose(2, 0, 1, 3)
    qpos = jnp.pad(jnp.arange(sq), (0, pad_q), constant_values=-1).reshape(
        nb, block
    )
    kpos = jnp.arange(skv)

    def body(carry, xs):
        dk_acc, dv_acc = carry
        q_i, g_i, lse_i, delta_i, qpos_i = xs
        s = jnp.einsum("bhqd,bhkd->bhqk", q_i, kt) * scale
        valid = (qpos_i[:, None] >= 0) & (kpos[None, :] < skv)
        if causal:
            valid &= qpos_i[:, None] >= kpos[None, :]
        p = jnp.where(valid[None, None], jnp.exp(s - lse_i[..., None]), 0.0)
        dv_acc = dv_acc + jnp.einsum("bhqk,bhqd->bhkd", p, g_i)
        dp = jnp.einsum("bhqd,bhkd->bhqk", g_i, vt)
        ds = p * (dp - delta_i[..., None]) * scale
        dq_i = jnp.einsum("bhqk,bhkd->bhqd", ds, kt)
        dk_acc = dk_acc + jnp.einsum("bhqk,bhqd->bhkd", ds, q_i)
        return (dk_acc, dv_acc), dq_i

    zeros = jnp.zeros((b, h, skv, d), jnp.float32)
    (dk, dv), dq_blocks = lax.scan(
        body, (zeros, zeros), (qb, gb, lseb, deltab, qpos)
    )
    dq = dq_blocks.transpose(1, 2, 0, 3, 4).reshape(b, h, nb * block, d)
    dq = dq[:, :, :sq]

    def unheads(x, like):  # [B, H, S, D] -> [B, S, H, D] in input dtype
        return x.transpose(0, 2, 1, 3).astype(like.dtype)

    return unheads(dq, q), unheads(dk, k), unheads(dv, v)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal=False, scale=None, block_q=512,
                    block_kv=512, interpret=None, bwd_impl="xla"):
    """Flash attention on [B, S, H, D]; differentiable.  ``v`` may be
    narrower or wider than ``q``/``k`` ([B, S, H, Dv] -> out [B, S, H,
    Dv]) in the forward pass (latent attention's prefill: 192 against
    128); the backward pass needs equal widths.

    ``interpret=None`` auto-selects: compiled pallas on TPU, interpreter
    mode on a CPU backend that was asked for (tests / virtual-device
    meshes), an error anywhere else (``ops._pallas.resolve_interpret``).

    ``bwd_impl``: "xla" (default — blockwise scan, computes-then-masks
    the causal triangle) or "pallas" (dq/dkv kernels whose block loops
    are trimmed at the diagonal, skipping ~half the causal backward
    FLOPs at long seq; numerics identical, see tests).

    Defaults tuned on v5e (B=4, S=2048, H=8, D=128: 512/512 is ~4x the
    128/128 throughput).  The kernel keeps the full k/v sequence of one
    head in VMEM, so S*D*4 bytes must stay well under the ~16MB budget —
    beyond ~32k tokens at D=128, shard the sequence (parallel/ring.py)
    or shrink block_kv.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    interpret = resolve_interpret(interpret)
    if bwd_impl not in ("xla", "pallas"):
        raise ValueError(f"bwd_impl must be 'xla' or 'pallas', "
                         f"got {bwd_impl!r}")
    return _flash(q, k, v, causal, scale, block_q, block_kv, interpret,
                  bwd_impl)
