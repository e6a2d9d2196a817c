"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

Long-context support is green-field relative to the reference (SURVEY.md
§5 "Long-context / sequence parallelism — absent"); here it is
first-class.  Two interchangeable schemes over a named sequence mesh
axis:

- **Ring attention** (``ring_attention``): every device keeps its local
  q shard and rotates the k/v shards around the ring with
  ``lax.ppermute`` (rides ICI neighbor links), accumulating blockwise
  online-softmax partials.  Peak memory is O(S_local²) per step and the
  k/v transfer overlaps the next block's compute under XLA's async
  collective scheduling.
- **Ulysses** (``ulysses_attention``): ``lax.all_to_all`` re-shards
  seq→heads so each device computes *full-sequence* attention for a
  subset of heads, then re-shards back.  One collective pair instead of
  ring steps; needs heads % axis_size == 0.

Both are meant to run inside ``shard_map`` (helpers below wrap that) and
are differentiable — ppermute/all_to_all have transposes, and the
blockwise softmax is plain traced math.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as _onp
from jax import lax
from jax.sharding import PartitionSpec as P

def shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the varying-manual-axes check off (the ring
    primitives produce unreplicated outputs from psum-free math, which
    the checker cannot prove)."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


_NEG_INF = -1e30


def _online_softmax_update(carry, q_blk, k_blk, v_blk, scale, causal,
                           q_offset, kv_offset):
    """One blockwise online-softmax accumulation step (shared by the
    contiguous and zigzag rings — the delicate running-max/rescale math
    must never diverge between them)."""
    acc, m, l = carry
    s = _block_scores(q_blk, k_blk, scale, causal,
                      q_offset=q_offset, kv_offset=kv_offset)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    l_new = l * alpha + jnp.sum(p, axis=-1)
    acc_new = acc * alpha[..., None] + jnp.einsum(
        "bhqk,bkhd->bhqd", p, v_blk.astype(jnp.float32)
    )
    return acc_new, m_new, l_new


def _block_scores(q, k, scale, causal, q_offset, kv_offset):
    """[B,Sq,H,D]x[B,Skv,H,D] -> masked f32 scores [B,H,Sq,Skv]."""
    s = jnp.einsum(
        "bqhd,bkhd->bhqk",
        q.astype(jnp.float32),
        k.astype(jnp.float32),
    ) * scale
    if causal:
        qpos = q_offset + jnp.arange(q.shape[1])
        kpos = kv_offset + jnp.arange(k.shape[1])
        mask = qpos[:, None] >= kpos[None, :]
        s = jnp.where(mask[None, None], s, _NEG_INF)
    return s


def ring_attention(q, k, v, axis_name, *, causal=False, scale=None):
    """Attention over a sequence-sharded ring; call inside shard_map.

    q/k/v: local shards [B, S_local, H, D]; the global sequence is the
    concatenation over the ``axis_name`` ring order.  Returns the local
    output shard [B, S_local, H, D].
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    axis_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    b, s_local, h, d = q.shape

    acc = jnp.zeros((b, h, s_local, d), jnp.float32)
    m = jnp.full((b, h, s_local), _NEG_INF, jnp.float32)
    l = jnp.zeros((b, h, s_local), jnp.float32)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    k_cur, v_cur = k, v
    for step in range(axis_size):
        # after `step` rotations each device holds the shard originally
        # at (my_idx - step); step 0 is the local diagonal block, so for
        # causal masking m is finite after step 0 for every valid row
        # and fully-masked later blocks contribute exp(-inf - m) = 0.
        kv_idx = (my_idx - step) % axis_size

        def do_block(carry, k_blk=k_cur, v_blk=v_cur, kv_i=kv_idx):
            return _online_softmax_update(
                carry, q, k_blk, v_blk, scale, causal,
                q_offset=my_idx * s_local, kv_offset=kv_i * s_local,
            )

        if causal:
            # a kv shard strictly after the q shard is fully masked —
            # skip its score/softmax compute entirely (the ring still
            # rotates it, but ~half the blocks cost nothing)
            acc, m, l = lax.cond(
                kv_idx > my_idx, lambda c: c, do_block, (acc, m, l)
            )
        else:
            acc, m, l = do_block((acc, m, l))
        if step + 1 < axis_size:
            k_cur = lax.ppermute(k_cur, axis_name, perm)
            v_cur = lax.ppermute(v_cur, axis_name, perm)

    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def zigzag_permutation(seq_len, axis_size):
    """Global-position permutation for zigzag sequence sharding.

    The sequence is cut into ``2*axis_size`` stripes; device i owns
    stripes (i, 2*axis_size-1-i), so under the causal mask every device
    holds one early and one late stripe and computes the SAME number of
    unmasked blocks — the plain contiguous ring's device n-1 computes n
    blocks while device 0 computes 1, so its latency never improves no
    matter how many masked blocks are skipped (the classic zigzag /
    striped-attention load balance).

    Returns int32 index array ``perm`` with ``x[:, perm]`` reordering a
    [B, S, ...] sequence into zigzag order; invert with
    ``inverse_permutation(perm)``.
    """
    if seq_len % (2 * axis_size):
        raise ValueError(
            f"seq_len {seq_len} must divide into 2*axis_size="
            f"{2 * axis_size} stripes")
    stripe = seq_len // (2 * axis_size)
    order = []
    for i in range(axis_size):
        order.append(i)
        order.append(2 * axis_size - 1 - i)
    idx = _onp.concatenate(
        [_onp.arange(s * stripe, (s + 1) * stripe) for s in order])
    return jnp.asarray(idx, jnp.int32)


def inverse_permutation(perm):
    """Index array inverting ``zigzag_permutation`` (x_perm[inv] == x)."""
    inv = jnp.zeros_like(perm)
    return inv.at[perm].set(jnp.arange(perm.shape[0], dtype=perm.dtype))


def zigzag_ring_attention(q, k, v, axis_name, *, causal=False, scale=None):
    """Load-balanced causal ring attention; call inside shard_map.

    Inputs are local shards in ZIGZAG order: the global sequence was
    reordered with ``zigzag_permutation`` so this device's
    [B, S_local, H, D] shard is the concatenation of global stripes
    (my_idx, 2n-1-my_idx), each S_local/2 long.  Rotating kv around the
    ring, each (q stripe, kv stripe) pair is computed only when the
    causal mask can reach it — every device does axis_size+1 of the
    2*axis_size stripe-pairs per rotation on average, so causal latency
    is ~halved vs the contiguous ring, not just FLOPs.

    Returns the local output shard, still in zigzag order.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    axis_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    b, s_local, h, d = q.shape
    if s_local % 2:
        raise ValueError("zigzag shards must have even local length")
    s_h = s_local // 2

    # global stripe ids + positions of the two local q halves
    q_stripes = (my_idx, 2 * axis_size - 1 - my_idx)

    acc = jnp.zeros((b, h, s_local, d), jnp.float32)
    m = jnp.full((b, h, s_local), _NEG_INF, jnp.float32)
    l = jnp.zeros((b, h, s_local), jnp.float32)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def half_update(carry, q_half_ix, q_stripe, k_half, v_half, kv_stripe):
        """Online-softmax update of q half ``q_half_ix`` against one kv
        stripe, skipped entirely when the stripe pair is fully masked."""
        acc, m, l = carry
        rows = slice(q_half_ix * s_h, (q_half_ix + 1) * s_h)

        def compute(sub):
            return _online_softmax_update(
                sub, q[:, rows], k_half, v_half, scale, causal,
                q_offset=q_stripe * s_h, kv_offset=kv_stripe * s_h,
            )

        sub = (acc[:, :, rows], m[:, :, rows], l[:, :, rows])
        if causal:
            sub = lax.cond(kv_stripe > q_stripe, lambda c: c, compute, sub)
        else:
            sub = compute(sub)
        return (
            acc.at[:, :, rows].set(sub[0]),
            m.at[:, :, rows].set(sub[1]),
            l.at[:, :, rows].set(sub[2]),
        )

    k_cur, v_cur = k, v
    for step in range(axis_size):
        kv_idx = (my_idx - step) % axis_size
        kv_stripes = (kv_idx, 2 * axis_size - 1 - kv_idx)
        carry = (acc, m, l)
        for qi, q_stripe in enumerate(q_stripes):
            for ki, kv_stripe in enumerate(kv_stripes):
                carry = half_update(
                    carry, qi, q_stripe,
                    k_cur[:, ki * s_h:(ki + 1) * s_h],
                    v_cur[:, ki * s_h:(ki + 1) * s_h],
                    kv_stripe,
                )
        acc, m, l = carry
        if step + 1 < axis_size:
            k_cur = lax.ppermute(k_cur, axis_name, perm)
            v_cur = lax.ppermute(v_cur, axis_name, perm)

    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def ulysses_attention(q, k, v, axis_name, *, causal=False, scale=None,
                      attn_fn=None):
    """All-to-all sequence parallelism; call inside shard_map.

    Re-shards [B, S/n, H, D] -> [B, S, H/n, D], runs full-sequence
    attention locally (``attn_fn``, default the XLA reference; pass
    ops.flash_attention on TPU), and re-shards back.
    """
    from tensorflowonspark_tpu.ops import mha_reference

    if attn_fn is None:
        attn_fn = mha_reference
    n = lax.psum(1, axis_name)
    assert q.shape[2] % n == 0, (
        f"ulysses needs heads ({q.shape[2]}) divisible by axis size ({n})"
    )
    # seq-shard -> head-shard: split heads axis, gather seq axis
    a2a = functools.partial(
        lax.all_to_all, axis_name=axis_name, split_axis=2, concat_axis=1,
        tiled=True,
    )
    qg, kg, vg = a2a(q), a2a(k), a2a(v)
    out = attn_fn(qg, kg, vg, causal=causal, scale=scale)
    # head-shard -> seq-shard
    return lax.all_to_all(
        out, axis_name=axis_name, split_axis=1, concat_axis=2, tiled=True
    )


def sequence_parallel_attention(mesh, impl="ring", *, seq_axis="seq",
                                batch_axes=("data", "fsdp"),
                                head_axis="model", causal=False, scale=None):
    """shard_map-wrapped attention over ``mesh``: [B, S, H, D] global
    arrays, batch sharded over ``batch_axes``, sequence over
    ``seq_axis``, heads over ``head_axis`` (tp); returns same sharding.

    This is the building block models call when a 'seq' axis is present
    (models/transformer.py) — dp/fsdp/tp stay GSPMD-managed, only the
    sequence dimension's cross-shard exchange is explicit.

    ``impl="zigzag"`` expects the caller to have reordered the global
    sequence with ``zigzag_permutation(seq_len, mesh.shape[seq_axis])``
    (and to inverse-permute outputs / permute labels identically): the
    reorder is what balances causal work across the ring.
    """
    fns = {"ring": ring_attention, "zigzag": zigzag_ring_attention,
           "ulysses": ulysses_attention}
    inner = functools.partial(
        fns[impl], axis_name=seq_axis, causal=causal, scale=scale
    )
    axes = dict(mesh.shape)
    batch_axes = tuple(a for a in batch_axes if a in axes)
    head = head_axis if head_axis in axes else None
    spec = P(batch_axes if batch_axes else None, seq_axis, head, None)

    def call(q, k, v):
        return shard_map(
            lambda q, k, v: inner(q, k, v),
            mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
        )(q, k, v)

    return call
