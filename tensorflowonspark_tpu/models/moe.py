"""Expert feed-forward layer: bias-corrected sigmoid top-k routing, a
shared expert, dropless sort-by-expert dispatch, and a layer that is
told which experts it holds (expert parallelism's share of a layer).

No reference counterpart (pre-MoE era).  The layer, for one token ``h``
(after the block's pre-norm):

    s     = sigmoid(h W_r)                    over ALL ``n_experts`` outputs
    pick  = the ``top_k`` largest of s + b    (b: per-expert bias, used
                                               for the choice only)
    g_i   = routed_scale * s_i / sum_{picked} s_j
    y     = SwiGLU_shared(h) + sum_{i picked, i held} g_i * SwiGLU_i(h)

``held`` is ``[expert_offset, expert_offset + n_held)`` where ``n_held``
is the leading axis of the expert weights: a layer that holds all
experts computes the whole sum, a layer that holds a share computes its
part and leaves the rest to the chips that hold the others (on one chip
that part is simply absent: nothing here stands in for them).

Dispatch is dropless: every (token, pick) pair whose expert is held is
computed, whatever the imbalance — the pairs are sorted by expert and
the three matrix products are grouped products over the experts held
(``jax.lax.ragged_dot``, a native grouped-matmul kernel on TPU that
visits only the row tiles that hold work).  There is no capacity factor
and nothing to tune; pairs routed elsewhere sort behind the last group
and are never touched.  Long inputs are dispatched ``DISPATCH_CHUNK``
tokens at a time so the sorted copies stay bounded.

Sharding: the expert axis on a mesh axis (``param_specs(ep_axis=...)``)
with everything else replicated is plain GSPMD.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from tensorflowonspark_tpu.models import layers as L

# tokens dispatched at once: 4096 x top-8 = 32768 sorted rows
DISPATCH_CHUNK = 4096


def init(key, dim, hidden, num_experts, *, num_held=None, num_shared=0,
         dtype=jnp.float32, bias_scale=0.01):
    """``router`` [dim, num_experts] spans ALL experts; the expert
    weights hold ``num_held`` of them (default: all).  ``router_bias`` is
    drawn small and non-zero (+-``bias_scale``), as a trained
    aux-loss-free balancer leaves it, so that it changes some choices."""
    held = num_experts if num_held is None else num_held
    kr, kb, kg, ku, kd, ks = jax.random.split(key, 6)
    params = {
        "router": L._he_init(kr, (dim, num_experts), dim, dtype),
        "router_bias": jax.random.uniform(
            kb, (num_experts,), jnp.float32, -bias_scale, bias_scale),
        "wg": L._he_init(kg, (held, dim, hidden), dim, dtype),
        "wu": L._he_init(ku, (held, dim, hidden), dim, dtype),
        "wd": L._he_init(kd, (held, hidden, dim), hidden, dtype),
    }
    if num_shared:
        width = num_shared * hidden
        k1, k2, k3 = jax.random.split(ks, 3)
        params["shared_wg"] = L._he_init(k1, (dim, width), dim, dtype)
        params["shared_wu"] = L._he_init(k2, (dim, width), dim, dtype)
        params["shared_wd"] = L._he_init(k3, (width, dim), width, dtype)
    return params


def param_specs(*, ep_axis="model", fsdp_axis=None, shared=False):
    """Expert axis sharded over ``ep_axis``: each device holds E/n
    experts; router and shared expert are replicated."""
    specs = {
        "router": P(None, None),
        "router_bias": P(None),
        "wg": P(ep_axis, fsdp_axis, None),
        "wu": P(ep_axis, fsdp_axis, None),
        "wd": P(ep_axis, None, fsdp_axis),
    }
    if shared:
        specs.update(shared_wg=P(None, None), shared_wu=P(None, None),
                     shared_wd=P(None, None))
    return specs


def _matmul(x, w):
    return jnp.dot(x, w.astype(x.dtype),
                   preferred_element_type=jnp.float32).astype(x.dtype)


def swiglu(x, wg, wu, wd):
    """``(silu(x wg) * x wu) wd``: the gated MLP of dense layers, shared
    experts and (grouped) routed experts alike."""
    return _matmul(jax.nn.silu(_matmul(x, wg)) * _matmul(x, wu), wd)


def route(params, h, top_k, routed_scale=1.0):
    """``(idx [N, k] int32, gates [N, k] f32, scores [N, E] f32)``: the
    choice uses ``scores + router_bias``, the gates use ``scores``."""
    scores = jax.nn.sigmoid(jnp.dot(
        h, params["router"].astype(h.dtype),
        preferred_element_type=jnp.float32))
    _, idx = lax.top_k(scores + params["router_bias"], top_k)
    picked = jnp.take_along_axis(scores, idx, axis=-1)
    gates = routed_scale * picked / jnp.sum(picked, axis=-1, keepdims=True)
    return idx.astype(jnp.int32), gates, scores


def _routed(params, h, idx, gates, expert_offset, held_n, bank_index):
    """The held experts' part for tokens ``h`` [N, D]: sort the (token,
    pick) pairs by expert, three grouped products, weight, un-sort, sum
    each token's picks."""
    n, k = idx.shape
    with jax.named_scope("moe/dispatch"):
        local = idx - expert_offset
        held = (local >= 0) & (local < held_n)
        # pairs for experts that live elsewhere sort behind the last group
        flat = jnp.where(held, local, held_n).reshape(-1)
        order = jnp.argsort(flat, stable=True)
        sizes = jnp.bincount(flat, length=held_n + 1)[:held_n].astype(
            jnp.int32)
        n_live = jnp.sum(sizes)
        if bank_index is not None:
            # the weights are a BANK of several layers' experts: this
            # layer's are groups [bank_index * held, ...), all others empty
            sizes = lax.dynamic_update_slice(
                jnp.zeros((params["wg"].shape[0],), jnp.int32), sizes,
                (bank_index * held_n,))
        xs = h[order // k]
    with jax.named_scope("moe/experts"):
        dt = h.dtype
        act = jax.nn.silu(lax.ragged_dot(xs, params["wg"].astype(dt), sizes)) \
            * lax.ragged_dot(xs, params["wu"].astype(dt), sizes)
        ys = lax.ragged_dot(act, params["wd"].astype(dt), sizes)
    with jax.named_scope("moe/combine"):
        w = jnp.where(held, gates, 0.0).reshape(-1)[order]
        live = jnp.arange(n * k) < n_live
        ys = jnp.where(live[:, None], ys.astype(jnp.float32) * w[:, None], 0.0)
        back = jnp.zeros_like(order).at[order].set(jnp.arange(n * k))
        return ys[back].reshape(n, k, -1).sum(axis=1).astype(dt)


def apply(params, x, *, top_k, routed_scale=1.0, expert_offset=0,
          chunk=DISPATCH_CHUNK, live=None, bank=None):
    """``x`` [..., D] -> ``(y [..., D], stats)``.

    ``bank=(index, held)``: ``wg``/``wu``/``wd`` are the experts of
    SEVERAL layers stacked on the group axis, ``held`` a layer, and this
    call is layer ``index`` of them.  A scan over layers passes the whole
    stack and its counter instead of slicing a layer out, because a slice
    handed to the grouped-product kernel is first COPIED, all experts of
    it, touched or not (PERF.md, PR27).

    ``stats`` (all on the device, a few scalars and two [E] vectors):
    what the device did — ``rows_held`` ((token, pick) pairs computed
    here), ``experts_touched`` (held experts with at least one token),
    ``tokens_per_expert_max``, ``dropped`` (held pairs not computed: 0,
    dispatch is dropless) — and how the router chose, over the tokens
    ``live`` marks (``x.shape[:-1]`` bools, default all: a decode step's
    free slots carry padding, which routes but says nothing):
    ``picks`` / ``picks_held`` (pair counts), ``load`` (share of the
    picks per expert, sums to 1) and ``importance`` (mean score per
    expert)."""
    shape = x.shape
    h = x.reshape(-1, shape[-1])
    n = h.shape[0]
    n_experts = params["router"].shape[1]
    bank_index, held_n = (None, params["wg"].shape[0]) if bank is None \
        else bank
    with jax.named_scope("moe/route"):
        idx, gates, scores = route(params, h, top_k, routed_scale)
    if n <= chunk:
        y = _routed(params, h, idx, gates, expert_offset, held_n, bank_index)
    else:
        pad = (-n) % chunk

        def chunks(a, fill=0):
            a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1),
                        constant_values=fill)
            return a.reshape((-1, chunk) + a.shape[1:])

        # a padded token picks expert -1, held nowhere: it sorts last
        y = lax.map(
            lambda c: _routed(params, *c, expert_offset, held_n, bank_index),
            (chunks(h), chunks(idx, -1), chunks(gates)))
        y = y.reshape(-1, shape[-1])[:n]
    if "shared_wg" in params:
        with jax.named_scope("moe/shared"):
            y = y + swiglu(h, params["shared_wg"], params["shared_wu"],
                           params["shared_wd"])
    local = idx - expert_offset
    held = (local >= 0) & (local < held_n)
    counts = jnp.bincount(jnp.where(held, local, held_n).reshape(-1),
                          length=held_n + 1)[:held_n]
    rows_held = jnp.sum(held)
    lv = (jnp.ones((n,), bool) if live is None
          else live.reshape(-1)).astype(jnp.float32)
    n_live = jnp.maximum(jnp.sum(lv), 1.0)
    stats = {
        "rows_held": rows_held.astype(jnp.int32),
        "experts_touched": jnp.sum(counts > 0).astype(jnp.int32),
        "tokens_per_expert_max": jnp.max(counts).astype(jnp.int32),
        "dropped": (rows_held - jnp.sum(counts)).astype(jnp.int32),
        "picks": (jnp.sum(lv) * top_k).astype(jnp.int32),
        "picks_held": jnp.sum(held * lv[:, None]).astype(jnp.int32),
        "load": jnp.zeros((n_experts,), jnp.float32).at[idx].add(
            jnp.broadcast_to(lv[:, None], idx.shape)) / (n_live * top_k),
        "importance": jnp.sum(scores * lv[:, None], axis=0) / n_live,
    }
    return y.reshape(shape), stats
