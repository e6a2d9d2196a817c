"""Fused RMSNorm: pallas kernel + XLA reference.

RMSNorm is the transformer's bandwidth-bound elementwise hot op; the
fused kernel keeps the activation in VMEM for the reduce + scale instead
of two HBM round trips.  Differentiable via custom VJP that recomputes
through the reference formulation (cheap: O(N) recompute).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from tensorflowonspark_tpu.ops._pallas import resolve_interpret


def rmsnorm_reference(x, scale, eps=1e-6):
    xf = x.astype(jnp.float32)
    rms = lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * rms * scale.astype(jnp.float32)).astype(x.dtype)


def _rmsnorm_kernel(x_ref, s_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    rms = lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    o_ref[...] = (x * rms * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _rmsnorm_forward(x, scale, eps, block_rows, interpret):
    import jax.experimental.pallas as pl

    shape = x.shape
    dim = shape[-1]
    x2 = x.reshape(-1, dim)
    rows = x2.shape[0]
    block_rows = min(block_rows, rows)
    pad = (-rows) % block_rows
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=((rows + pad) // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, dim), lambda i: (i, 0)),
            pl.BlockSpec((dim,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, dim), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        interpret=interpret,
        name="tfos_rmsnorm",
    )(x2, scale)
    return out[:rows].reshape(shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _rmsnorm(x, scale, eps, block_rows, interpret):
    return _rmsnorm_forward(x, scale, eps, block_rows, interpret)


def _rmsnorm_fwd(x, scale, eps, block_rows, interpret):
    return _rmsnorm(x, scale, eps, block_rows, interpret), (x, scale)


def _rmsnorm_bwd(eps, block_rows, interpret, res, g):
    x, scale = res
    _, vjp = jax.vjp(lambda x_, s_: rmsnorm_reference(x_, s_, eps), x, scale)
    return vjp(g)


_rmsnorm.defvjp(_rmsnorm_fwd, _rmsnorm_bwd)


def fused_rmsnorm(x, scale, eps=1e-6, block_rows=256, interpret=None):
    """RMSNorm over the last axis; any leading shape; differentiable."""
    return _rmsnorm(x, scale, eps, block_rows, resolve_interpret(interpret))
