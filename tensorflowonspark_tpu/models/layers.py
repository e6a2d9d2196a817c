"""Minimal functional layer library: pytree params + pure apply functions.

Design: every layer is an ``init(key, ...) -> params`` plus a pure
``apply(params, x, ...)``; models are compositions.  No module classes,
no tracing magic — everything is jit/grad/shard_map friendly, params are
plain nested dicts that shard naturally with NamedSharding trees.

Convolutions use NHWC with HWIO kernels — the layout XLA:TPU maps best
onto the MXU; matmuls accumulate in float32 while activations/weights
may be bfloat16 (``compute_dtype``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax


def _he_init(key, shape, fan_in, dtype):
    return jax.random.normal(key, shape, dtype) * jnp.asarray(
        math.sqrt(2.0 / fan_in), dtype
    )


# -- dense -------------------------------------------------------------------

def dense_init(key, in_dim, out_dim, dtype=jnp.float32):
    wkey, _ = jax.random.split(key)
    return {
        "w": _he_init(wkey, (in_dim, out_dim), in_dim, dtype),
        "b": jnp.zeros((out_dim,), dtype),
    }


def dense(params, x, precision=None):
    w = params["w"].astype(x.dtype)  # params live in fp32; compute dtype follows x
    return (
        jnp.dot(x, w, precision=precision,
                preferred_element_type=jnp.float32).astype(x.dtype)
        + params["b"].astype(x.dtype)
    )


# -- conv --------------------------------------------------------------------

def conv_init(key, h, w, in_ch, out_ch, dtype=jnp.float32, use_bias=True):
    wkey, _ = jax.random.split(key)
    p = {"w": _he_init(wkey, (h, w, in_ch, out_ch), h * w * in_ch, dtype)}
    if use_bias:
        p["b"] = jnp.zeros((out_ch,), dtype)
    return p


def conv(params, x, stride=1, padding="SAME"):
    strides = (stride, stride) if isinstance(stride, int) else stride
    # No explicit preferred_element_type: the TPU MXU already accumulates
    # bf16 convs in f32, and an f32 result dtype breaks the conv transpose
    # (bf16 operands meet an f32 cotangent in the backward pass).
    y = lax.conv_general_dilated(
        x,
        params["w"].astype(x.dtype),  # fp32 master weights, bf16 compute
        window_strides=strides,
        padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    if "b" in params:
        y = y + params["b"].astype(x.dtype)
    return y


# -- norm --------------------------------------------------------------------

def batchnorm_init(ch, dtype=jnp.float32):
    """Returns (params, state): trainable scale/bias vs running stats.

    Keeping running statistics in a separate state tree keeps the
    optimizer and grad transform off them (they receive no gradient)."""
    params = {"scale": jnp.ones((ch,), dtype), "bias": jnp.zeros((ch,), dtype)}
    state = {"mean": jnp.zeros((ch,), jnp.float32), "var": jnp.ones((ch,), jnp.float32)}
    return params, state


def _bn_stats(x, eps):
    """One-pass E[x]/E[x^2] (f32 accumulation over one bf16 read) →
    (mean, var, inv)."""
    reduce_axes = tuple(range(x.ndim - 1))
    n = x.size // x.shape[-1]
    xf = x.astype(jnp.float32)
    mean = jnp.sum(xf, axis=reduce_axes) / n
    mean_sq = jnp.sum(xf * xf, axis=reduce_axes) / n
    var = jnp.maximum(mean_sq - mean * mean, 0.0)
    return mean, var, lax.rsqrt(var + eps)


def _bn_scale_bias(mean, inv, scale, bias, dtype):
    # fold (mean, inv, scale, bias) in f32, apply as one fused
    # multiply-add in the compute dtype — keeps activations bf16 (an f32
    # scale would silently upcast the whole network downstream)
    sf = scale.astype(jnp.float32)
    mul = (inv * sf).astype(dtype)
    add = (bias.astype(jnp.float32) - mean * inv * sf).astype(dtype)
    return mul, add


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _bn_train_fused(x, scale, bias, eps):
    """Training-mode BN core with a two-pass hand-written backward.

    The autodiff backward of the folded form materializes several
    standalone activation-sized multiplies (x̂ recompute, dvar/dmean
    broadcasts) that XLA:TPU does not fuse (an earlier session's profile,
    which summed per-op durations: a hypothesis, PERF.md).  The custom VJP
    expresses the whole backward as one reduction pass over (g, x) and
    one elementwise pass dx = a·g + b·x + c, each a single fusion.
    """
    mean, var, inv = _bn_stats(x, eps)
    mul, add = _bn_scale_bias(mean, inv, scale, bias, x.dtype)
    return x * mul + add, mean, var


def _bn_train_fused_fwd(x, scale, bias, eps):
    mean, var, inv = _bn_stats(x, eps)
    mul, add = _bn_scale_bias(mean, inv, scale, bias, x.dtype)
    return (x * mul + add, mean, var), (x, mean, inv, scale)


def _bn_bwd_core(gm, x, mean, inv, scale, mean_ct, var_ct):
    """Shared two-pass BN backward given the (possibly relu-gated)
    f32 cotangent ``gm``; returns (dx, dscale, dbias).

    Pass 1 is one fused reduction over (gm, x); pass 2 is
    dx = a·gm + b·x + c — γ·inv·(gm − Σgm/n − x̂·Σ(gm·x̂)/n) rearranged
    so the whole thing is a single elementwise fusion.  The (mean, var)
    output cotangents (zero in the training path — they only feed the
    non-differentiated EMA state — but cheap to honor exactly) fold
    into the same b/c vectors."""
    reduce_axes = tuple(range(x.ndim - 1))
    n = x.size // x.shape[-1]
    xf = x.astype(jnp.float32)
    sum_g = jnp.sum(gm, axis=reduce_axes)
    sum_gx = jnp.sum(gm * xf, axis=reduce_axes)
    sum_g_xhat = (sum_gx - mean * sum_g) * inv
    sf = scale.astype(jnp.float32)
    a = sf * inv
    b = -sf * inv * inv * sum_g_xhat / n
    c = -a * sum_g / n - b * mean
    b = b + 2.0 * var_ct / n
    c = c + (mean_ct - 2.0 * var_ct * mean) / n
    dx = (a * gm + b * xf + c).astype(x.dtype)
    return dx, sum_g_xhat.astype(scale.dtype), sum_g.astype(scale.dtype)


def _bn_train_fused_bwd(eps, res, cts):
    x, mean, inv, scale = res
    g, mean_ct, var_ct = cts
    return _bn_bwd_core(g.astype(jnp.float32), x, mean, inv, scale,
                        mean_ct, var_ct)


_bn_train_fused.defvjp(_bn_train_fused_fwd, _bn_train_fused_bwd)


def _make_bn_act_fused(act, gate):
    """Factory for BN→activation pairs sharing one custom VJP.

    Autodiff stores two activation-sized residuals per pair (x for the
    BN backward, the pre-activation for the act gate).  Here only x is
    saved; the backward recomputes the gate from x and the per-channel
    (mean, inv, scale, bias) vectors inside its existing passes — one
    fewer activation HBM round-trip per pair, on top of the fused-BN
    backward's two-pass structure (see ``_bn_train_fused``).

    ``act(pre)`` is the forward activation; ``gate(pre)`` its f32
    derivative evaluated on the pre-activation recomputed EXACTLY as
    the forward computed it (same ops, same dtype), so the subgradient
    convention at ties is whatever ``gate`` encodes."""

    @functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
    def bn_act(x, scale, bias, eps):
        mean, var, inv = _bn_stats(x, eps)
        mul, add = _bn_scale_bias(mean, inv, scale, bias, x.dtype)
        return act(x * mul + add), mean, var

    def fwd(x, scale, bias, eps):
        mean, var, inv = _bn_stats(x, eps)
        mul, add = _bn_scale_bias(mean, inv, scale, bias, x.dtype)
        return (act(x * mul + add), mean, var), (x, mean, inv, scale, bias)

    def bwd(eps, res, cts):
        x, mean, inv, scale, bias = res
        g, mean_ct, var_ct = cts
        mul, add = _bn_scale_bias(mean, inv, scale, bias, x.dtype)
        gm = g.astype(jnp.float32) * gate(x * mul + add)
        return _bn_bwd_core(gm, x, mean, inv, scale, mean_ct, var_ct)

    bn_act.defvjp(fwd, bwd)
    return bn_act


# relu: sign() reproduces jnp.maximum's tie convention (gradient 1/2
# where the pre-activation is exactly 0)
_bn_relu_train_fused = _make_bn_act_fused(
    lambda pre: jnp.maximum(pre, 0),
    lambda pre: (jnp.sign(pre.astype(jnp.float32)) + 1.0) * 0.5)
# relu6 (MobileNet-style blocks): jax.nn.relu6's gradient is 0 at BOTH
# saturation boundaries (strict inequalities)
_bn_relu6_train_fused = _make_bn_act_fused(
    jax.nn.relu6,
    lambda pre: ((pre.astype(jnp.float32) > 0)
                 & (pre.astype(jnp.float32) < 6)).astype(jnp.float32))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _bn_add_relu_train_fused(x, shortcut, scale, bias, eps):
    """relu(bn(x) + shortcut) — the ResNet block-end pattern — as one
    custom VJP.

    Autodiff saves x (BN backward) plus the pre-relu sum (relu gate) —
    two activation-sized residuals at the block's WIDEST tensor.  Here
    the residuals are x and shortcut, and for identity blocks the
    shortcut is the block input that the first conv's backward already
    keeps resident, so XLA stores one activation instead of two; the
    gate is recomputed from (x, shortcut) inside the backward's
    existing passes."""
    mean, var, inv = _bn_stats(x, eps)
    mul, add = _bn_scale_bias(mean, inv, scale, bias, x.dtype)
    return jnp.maximum(x * mul + add + shortcut, 0), mean, var


def _bn_add_relu_train_fused_fwd(x, shortcut, scale, bias, eps):
    mean, var, inv = _bn_stats(x, eps)
    mul, add = _bn_scale_bias(mean, inv, scale, bias, x.dtype)
    y = jnp.maximum(x * mul + add + shortcut, 0)
    return (y, mean, var), (x, shortcut, mean, inv, scale, bias)


def _bn_add_relu_train_fused_bwd(eps, res, cts):
    x, shortcut, mean, inv, scale, bias = res
    g, mean_ct, var_ct = cts
    # recompute the pre-activation exactly as the forward did; sign()
    # reproduces jnp.maximum's tie convention (gradient 1/2 at 0)
    mul, add = _bn_scale_bias(mean, inv, scale, bias, x.dtype)
    pre = x * mul + add + shortcut
    gate = (jnp.sign(pre.astype(jnp.float32)) + 1.0) * 0.5
    gm = g.astype(jnp.float32) * gate
    dx, dscale, dbias = _bn_bwd_core(gm, x, mean, inv, scale,
                                     mean_ct, var_ct)
    return dx, gm.astype(shortcut.dtype), dscale, dbias


_bn_add_relu_train_fused.defvjp(_bn_add_relu_train_fused_fwd,
                                _bn_add_relu_train_fused_bwd)


def _ema_state(state, mean, var, momentum):
    return {
        "mean": momentum * state["mean"] + (1 - momentum) * mean,
        "var": momentum * state["var"] + (1 - momentum) * var,
    }


def batchnorm(params, state, x, train=True, momentum=0.9, eps=1e-5,
              fused=True):
    """BatchNorm over N,H,W.  In SPMD training under jit, batch statistics
    are computed over the *global* batch automatically when the batch dim
    is mesh-sharded (XLA turns the mean reductions into all-reduces).

    ``fused=True`` (training only) routes through a custom-VJP core whose
    backward is two fused HBM passes instead of autodiff's unfused chain
    (see ``_bn_train_fused``); set False for the plain autodiff path.

    Returns (y, new_state); state is unchanged in eval mode.
    """
    if train:
        if fused:
            y, mean, var = _bn_train_fused(
                x, params["scale"], params["bias"], eps)
        else:
            mean, var, inv = _bn_stats(x, eps)
            mul, add = _bn_scale_bias(
                mean, inv, params["scale"], params["bias"], x.dtype)
            y = x * mul + add
        return y, _ema_state(state, mean, var, momentum)
    mean, var = state["mean"], state["var"]
    inv = lax.rsqrt(var + eps)
    mul, add = _bn_scale_bias(mean, inv, params["scale"], params["bias"],
                              x.dtype)
    return x * mul + add, state


def _batchnorm_act(fused_core, act, params, state, x, train, momentum,
                   eps, fused):
    if train and fused:
        y, mean, var = fused_core(x, params["scale"], params["bias"], eps)
        return y, _ema_state(state, mean, var, momentum)
    y, new_state = batchnorm(params, state, x, train=train,
                             momentum=momentum, eps=eps, fused=fused)
    return act(y), new_state


def batchnorm_relu(params, state, x, train=True, momentum=0.9, eps=1e-5,
                   fused=True):
    """BatchNorm followed by ReLU.  In fused training mode the pair
    shares one custom VJP (``_make_bn_act_fused``) that stores no
    pre-activation residual; otherwise it is exactly
    ``relu(batchnorm(...))``.  Returns (y, new_state)."""
    return _batchnorm_act(_bn_relu_train_fused, relu, params, state, x,
                          train, momentum, eps, fused)


def batchnorm_relu6(params, state, x, train=True, momentum=0.9, eps=1e-5,
                    fused=True):
    """BatchNorm followed by ReLU6 (MobileNet-style blocks); fused
    training mode shares one custom VJP, otherwise exactly
    ``jax.nn.relu6(batchnorm(...))``.  Returns (y, new_state)."""
    return _batchnorm_act(_bn_relu6_train_fused, jax.nn.relu6, params,
                          state, x, train, momentum, eps, fused)


def batchnorm_add_relu(params, state, x, shortcut, train=True, momentum=0.9,
                       eps=1e-5, fused=True):
    """relu(batchnorm(x) + shortcut) — the ResNet block-end.  In fused
    training mode the whole pattern shares one custom VJP
    (``_bn_add_relu_train_fused``) that stores no pre-relu sum;
    otherwise it is exactly relu(batchnorm(...) + shortcut).
    Returns (y, new_state)."""
    if train and fused:
        y, mean, var = _bn_add_relu_train_fused(
            x, shortcut, params["scale"], params["bias"], eps)
        return y, _ema_state(state, mean, var, momentum)
    y, new_state = batchnorm(params, state, x, train=train,
                             momentum=momentum, eps=eps, fused=fused)
    return relu(y + shortcut), new_state


def layernorm_init(dim, dtype=jnp.float32):
    return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}


def layernorm(params, x, eps=1e-6):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mu) * lax.rsqrt(var + eps)
    return y * params["scale"] + params["bias"]


# -- pooling / activations ---------------------------------------------------

def max_pool(x, window=2, stride=2, padding="VALID"):
    return lax.reduce_window(
        x,
        -jnp.inf,
        lax.max,
        (1, window, window, 1),
        (1, stride, stride, 1),
        padding,
    )


def avg_pool_global(x):
    return jnp.mean(x, axis=(1, 2))


def relu(x):
    return jnp.maximum(x, 0)


# -- losses ------------------------------------------------------------------

def softmax_cross_entropy(logits, labels, num_classes=None):
    """Mean CE; integer labels.  Stable log-softmax in float32."""
    with jax.named_scope("loss"):
        logits = logits.astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1, keepdims=True)
        logp = logits - logz
        nll = -jnp.take_along_axis(
            logp, labels[..., None].astype(jnp.int32), axis=-1)
        return jnp.mean(nll)


def accuracy(logits, labels):
    return jnp.mean((jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32))
