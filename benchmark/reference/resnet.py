"""Plain reference: ResNet (He et al., arXiv:1512.03385) forward pass and
loss in straightforward float32 ``jax.numpy``/``lax``: no kernels, no
custom VJPs, no bf16, no space-to-depth stem, nothing imported from the
program.  Callers set ``jax.default_matmul_precision("highest")``.

It reads the program's PARAMETER TREE (weights are data): ``stem.w``
HWIO, ``bn_*``/``bn1..3`` with ``scale``/``bias``, blocks ``s<stage>b<i>``
with ``conv1..3`` (bottleneck) or ``conv1..2`` (basic) and an optional
``proj``/``bn_proj`` shortcut, ``fc.w``/``fc.b``.

Departures from the paper, all the program's too: the stride-2
convolution of a bottleneck is its 3x3 (the "v1.5" placement); batch
norm is in training mode (statistics of this batch, biased variance,
eps 1e-5), as the first training step computes it.
"""

import jax.numpy as jnp
from jax import lax

EPS = 1e-5


def _conv(x, w, stride=1):
    return lax.conv_general_dilated(
        x, w.astype(jnp.float32), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _bn(x, p):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean((x - mean) ** 2, axis=(0, 1, 2))
    return (x - mean) / jnp.sqrt(var + EPS) * p["scale"] + p["bias"]


def _block(x, p, stride):
    shortcut = x
    if "proj" in p:
        shortcut = _bn(_conv(x, p["proj"]["w"], stride), p["bn_proj"])
    if "conv3" in p:                                   # bottleneck
        y = jnp.maximum(_bn(_conv(x, p["conv1"]["w"]), p["bn1"]), 0)
        y = jnp.maximum(_bn(_conv(y, p["conv2"]["w"], stride), p["bn2"]), 0)
        y = _bn(_conv(y, p["conv3"]["w"]), p["bn3"])
    else:                                              # basic
        y = jnp.maximum(_bn(_conv(x, p["conv1"]["w"], stride), p["bn1"]), 0)
        y = _bn(_conv(y, p["conv2"]["w"]), p["bn2"])
    return jnp.maximum(y + shortcut, 0)


def logits(params, images):
    """uint8/float [N, H, W, 3] -> float32 [N, classes]."""
    x = jnp.asarray(images).astype(jnp.float32)
    x = jnp.maximum(_bn(_conv(x, params["stem"]["w"], 2),
                        params["bn_stem"]), 0)
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          "SAME")
    stage = 0
    while f"s{stage}b0" in params:
        b = 0
        while f"s{stage}b{b}" in params:
            stride = 2 if (b == 0 and stage > 0) else 1
            x = _block(x, params[f"s{stage}b{b}"], stride)
            b += 1
        stage += 1
    x = jnp.mean(x, axis=(1, 2))
    return x @ params["fc"]["w"].astype(jnp.float32) + params["fc"]["b"]


def loss(params, images, labels):
    """Mean softmax cross entropy over the batch."""
    lg = logits(params, images)
    lg = lg - jnp.max(lg, axis=-1, keepdims=True)
    logp = lg - jnp.log(jnp.sum(jnp.exp(lg), axis=-1, keepdims=True))
    return -jnp.mean(jnp.take_along_axis(
        logp, jnp.asarray(labels)[:, None].astype(jnp.int32), axis=-1))
