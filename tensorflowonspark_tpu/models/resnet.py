"""ResNet family (parity workloads: reference examples/resnet — ResNet-56
CIFAR-10 via resnet_cifar_dist.py — and the ResNet-50/ImageNet north-star
config from BASELINE.json).

TPU-first choices:
- NHWC + HWIO everywhere (XLA:TPU's preferred conv layout for MXU tiling);
- parameters in float32, activations/conv compute in bfloat16 (the TPU
  MXU accumulates bf16 convolutions in float32 natively);
- BN running stats in a separate state tree (no optimizer traffic);
- train step is one jittable function — under a mesh-sharded batch, XLA
  emits the gradient all-reduce over ICI.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import optax

from tensorflowonspark_tpu.models import layers as L

# stage plans: depth -> (block, per-stage block counts).
# ImageNet family: 4 stages, width 64.  CIFAR family (6n+2 layers): 3
# stages of n basic blocks — use width=16, small_inputs=True, e.g.
# init(key, depth=56, num_classes=10, width=16, small_inputs=True).
_PLANS = {
    18: ("basic", (2, 2, 2, 2)),
    34: ("basic", (3, 4, 6, 3)),
    50: ("bottleneck", (3, 4, 6, 3)),
    101: ("bottleneck", (3, 4, 23, 3)),
    152: ("bottleneck", (3, 8, 36, 3)),
    # CIFAR 6n+2 plans (reference resnet_cifar_dist.py workload family)
    20: ("basic", (3, 3, 3)),
    32: ("basic", (5, 5, 5)),
    44: ("basic", (7, 7, 7)),
    56: ("basic", (9, 9, 9)),
    110: ("basic", (18, 18, 18)),
}


def _block_init(key, kind, in_ch, ch, stride, dtype):
    ks = jax.random.split(key, 4)
    p, s = {}, {}
    if kind == "bottleneck":
        out_ch = ch * 4
        p["conv1"] = L.conv_init(ks[0], 1, 1, in_ch, ch, dtype, use_bias=False)
        p["bn1"], s["bn1"] = L.batchnorm_init(ch)
        p["conv2"] = L.conv_init(ks[1], 3, 3, ch, ch, dtype, use_bias=False)
        p["bn2"], s["bn2"] = L.batchnorm_init(ch)
        p["conv3"] = L.conv_init(ks[2], 1, 1, ch, out_ch, dtype, use_bias=False)
        p["bn3"], s["bn3"] = L.batchnorm_init(out_ch)
    else:
        out_ch = ch
        p["conv1"] = L.conv_init(ks[0], 3, 3, in_ch, ch, dtype, use_bias=False)
        p["bn1"], s["bn1"] = L.batchnorm_init(ch)
        p["conv2"] = L.conv_init(ks[1], 3, 3, ch, ch, dtype, use_bias=False)
        p["bn2"], s["bn2"] = L.batchnorm_init(ch)
    if stride != 1 or in_ch != out_ch:
        p["proj"] = L.conv_init(ks[3], 1, 1, in_ch, out_ch, dtype, use_bias=False)
        p["bn_proj"], s["bn_proj"] = L.batchnorm_init(out_ch)
    return p, s, out_ch


def _block_apply(p, s, x, kind, stride, train, bn_fused=True):
    ns = {}
    bn = functools.partial(L.batchnorm, train=train, fused=bn_fused)
    # BN→ReLU pairs (and the block-end BN→add→ReLU) route through
    # combined custom VJPs — no stored pre-activation residuals — when
    # bn_fused; see layers.batchnorm_relu / batchnorm_add_relu
    bnr = functools.partial(L.batchnorm_relu, train=train, fused=bn_fused)
    bnar = functools.partial(L.batchnorm_add_relu, train=train,
                             fused=bn_fused)
    shortcut = x
    if "proj" in p:
        shortcut = L.conv(p["proj"], x, stride=stride)
        shortcut, ns["bn_proj"] = bn(p["bn_proj"], s["bn_proj"], shortcut)
    if kind == "bottleneck":
        y = L.conv(p["conv1"], x)
        y, ns["bn1"] = bnr(p["bn1"], s["bn1"], y)
        y = L.conv(p["conv2"], y, stride=stride)
        y, ns["bn2"] = bnr(p["bn2"], s["bn2"], y)
        y = L.conv(p["conv3"], y)
        y, ns["bn3"] = bnar(p["bn3"], s["bn3"], y, shortcut)
    else:
        y = L.conv(p["conv1"], x, stride=stride)
        y, ns["bn1"] = bnr(p["bn1"], s["bn1"], y)
        y = L.conv(p["conv2"], y)
        y, ns["bn2"] = bnar(p["bn2"], s["bn2"], y, shortcut)
    return y, ns


def init(key, depth=50, num_classes=1000, width=64, small_inputs=False,
         dtype=jnp.float32):
    """Build (params, state).  ``small_inputs``: CIFAR-style 3x3 stem
    without max-pool (reference resnet_cifar uses the small stem)."""
    kind, counts = _PLANS[depth]
    keys = jax.random.split(key, sum(counts) + 2)
    ki = iter(keys)
    params, state = {}, {}
    if small_inputs:
        params["stem"] = L.conv_init(next(ki), 3, 3, 3, width, dtype, use_bias=False)
    else:
        params["stem"] = L.conv_init(next(ki), 7, 7, 3, width, dtype, use_bias=False)
    params["bn_stem"], state["bn_stem"] = L.batchnorm_init(width)
    in_ch = width
    for stage, nblocks in enumerate(counts):
        ch = width * (2 ** stage)
        for b in range(nblocks):
            stride = 2 if (b == 0 and stage > 0) else 1
            name = f"s{stage}b{b}"
            params[name], state[name], in_ch = _block_init(
                next(ki), kind, in_ch, ch, stride, dtype
            )
    params["fc"] = L.dense_init(next(ki), in_ch, num_classes, dtype)
    return params, state


def _stem_space_to_depth(w7, x):
    """The 7x7/s2 stem as a 4x4/s1 conv over 2x2 space-to-depth input.

    MXU-tiling fix for the ImageNet stem: a 3-input-channel conv wastes
    most of a (128-lane) MXU pass.  Grouping 2x2 pixels into channels
    (H,W,3 -> H/2,W/2,12) and folding the kernel accordingly computes the
    EXACT same outputs (the 7x7 kernel zero-pads to 8x8 = 4 taps of
    stride-2 phase pairs) with a 192-deep contraction instead of 147 on a
    much squarer operand — the standard MLPerf-ResNet space-to-depth
    transform, applied in-model so checkpoints keep the 7x7 layout.
    """
    b, h, w, c = x.shape
    xs = x.reshape(b, h // 2, 2, w // 2, 2, c)
    xs = xs.transpose(0, 1, 3, 2, 4, 5).reshape(b, h // 2, w // 2, 4 * c)
    # kernel: (7,7,C,O) -> zero row/col after -> (4,2,4,2,C,O) ->
    # (p,q,u,v,C,O) -> (4,4,4C,O); channel order (u,v,c) matches xs
    k = jnp.pad(w7, ((0, 1), (0, 1), (0, 0), (0, 0)))
    k = k.reshape(4, 2, 4, 2, c, -1).transpose(0, 2, 1, 3, 4, 5)
    k = k.reshape(4, 4, 4 * c, -1).astype(x.dtype)
    # SAME geometry of the original: out 112 = in 112 with pad (1, 2)
    return jax.lax.conv_general_dilated(
        xs, k, window_strides=(1, 1), padding=((1, 2), (1, 2)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def apply(params, state, images, depth=50, train=True, small_inputs=False,
          compute_dtype=jnp.bfloat16, stem_s2d=True, bn_fused=True):
    """images [N,H,W,3] → logits [N,num_classes]; returns (logits, new_state)."""
    kind, counts = _PLANS[depth]
    new_state = {}
    # the scopes (stem, stage1..stage4, head) are the stable names a
    # device trace is reduced by; metadata only
    with jax.named_scope("stem"):
        x = images.astype(compute_dtype)
        if small_inputs:
            x = L.conv(params["stem"], x)
        elif stem_s2d and x.shape[1] % 2 == 0 and x.shape[2] % 2 == 0:
            x = _stem_space_to_depth(params["stem"]["w"], x)
        else:
            x = L.conv(params["stem"], x, stride=2)
        x, new_state["bn_stem"] = L.batchnorm_relu(
            params["bn_stem"], state["bn_stem"], x, train, fused=bn_fused)
        if not small_inputs:
            # SAME padding: 112 -> 56 (the standard ResNet stem; VALID's
            # 55 also breaks the TPU's (8,128) tiling on every stage-1
            # tensor)
            x = L.max_pool(x, window=3, stride=2, padding="SAME")
    for stage, nblocks in enumerate(counts):
        with jax.named_scope(f"stage{stage + 1}"):
            for b in range(nblocks):
                stride = 2 if (b == 0 and stage > 0) else 1
                name = f"s{stage}b{b}"
                x, new_state[name] = _block_apply(
                    params[name], state[name], x, kind, stride, train,
                    bn_fused)
    with jax.named_scope("head"):
        x = L.avg_pool_global(x).astype(jnp.float32)
        return L.dense(params["fc"], x), new_state


def make_train_step(optimizer, depth=50, small_inputs=False,
                    compute_dtype=jnp.bfloat16, remat=False, stem_s2d=True,
                    accum_steps=1, bn_fused=True):
    """(params, state, opt_state, images, labels) →
    (params, state, opt_state, loss, acc); jittable, SPMD-ready.

    ``accum_steps>1`` accumulates gradients over that many microbatches
    under one jit (effective batch beyond HBM limits).  BatchNorm
    normalizes each microbatch with its own statistics (as a sequential
    small-batch loop would), so results are close to — not bit-identical
    with — the one-big-batch step; the running-statistics EMA is
    threaded through the chain and advances once per microbatch.
    Accuracy is the last microbatch's.
    """

    fwd = apply
    if remat:
        fwd = jax.checkpoint(apply, static_argnums=(3, 4, 5, 6, 7, 8))

    def loss_fn(params, state, images, labels):
        logits, new_state = fwd(
            params, state, images, depth, True, small_inputs, compute_dtype,
            stem_s2d, bn_fused
        )
        return L.softmax_cross_entropy(logits, labels), (logits, new_state)

    def value_and_grad(params, state, images, labels):
        if accum_steps == 1:
            return jax.value_and_grad(loss_fn, has_aux=True)(
                params, state, images, labels)
        from tensorflowonspark_tpu.utils.train import \
            accumulated_value_and_grad

        def micro_loss(p, aux_prev, x, y):
            _, st = aux_prev  # BN running stats advance per microbatch
            return loss_fn(p, st, x, y)

        vg = accumulated_value_and_grad(micro_loss, accum_steps,
                                        has_aux=True, carry_aux=True)
        micro_b = images.shape[0] // accum_steps
        logits0 = jnp.zeros((micro_b, params["fc"]["w"].shape[1]),
                            jnp.float32)
        return vg(params, images, labels, init_aux=(logits0, state))

    def train_step(params, state, opt_state, images, labels):
        (loss, (logits, new_state)), grads = value_and_grad(
            params, state, images, labels)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        # accum path: logits/labels are the last microbatch's slice
        acc_labels = (labels if accum_steps == 1
                      else labels[-logits.shape[0]:])
        return (params, new_state, opt_state, loss,
                L.accuracy(logits, acc_labels))

    return train_step


def flops_per_image(depth=50, image_size=224):
    """Forward-pass FLOPs per image, 2 FLOPs per MAC — the standard MFU
    convention (PaLM appendix B; same convention as
    utils.metrics.transformer_flops_per_token).

    The 224x224 table is multiply-accumulate counts (torchvision's
    published GMacs; cross-checked shape-exactly by
    scripts/resnet_traffic.py at 4.12 GMACs for depth 50), doubled here.
    NOTE: before round 4 this function returned the MAC count mislabeled
    as 2*MACs, so every earlier reported ResNet MFU (BENCH_r01–r03,
    PERF.md history) undercounts by exactly 2x; step times and img/s
    were always convention-free.  bench_config.json's stored resnet
    "mfu" was rescaled in the same commit as this fix.
    """
    if depth in (18, 34, 50, 101, 152):
        # standard 224x224 multiply-accumulate counts
        macs = {18: 1.81e9, 34: 3.66e9, 50: 4.09e9,
                101: 7.8e9, 152: 11.5e9}[depth]
        ref = 224
    else:
        # CIFAR 6n+2 family at 32x32 (these were already 2*MACs)
        macs = {20: 0.041e9, 32: 0.069e9, 44: 0.097e9,
                56: 0.126e9, 110: 0.255e9}[depth]
        ref = 32
    return 2.0 * macs * (image_size / ref) ** 2
