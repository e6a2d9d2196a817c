"""Operations an algorithm needs, computed from shapes.  No jax.

``train_mfu`` divides by these and by nothing from the program.  2 FLOPs
per multiply-accumulate; a train step is forward + backward = 3 x forward;
recomputation (remat) is NOT counted.
"""

# ResNet stage plans (He et al., arXiv:1512.03385 table 1)
_RESNET_PLANS = {18: ("basic", (2, 2, 2, 2)), 34: ("basic", (3, 4, 6, 3)),
                 50: ("bottleneck", (3, 4, 6, 3)),
                 101: ("bottleneck", (3, 4, 23, 3)),
                 152: ("bottleneck", (3, 8, 36, 3))}


def resnet_forward_macs(depth=50, image=224, classes=1000, width=64):
    """Multiply-accumulates of one forward pass of one image, counted
    layer by layer from the shapes (convolutions and the classifier;
    batch norm, pooling and the loss are not matmul work).  Stride-2
    3x3 convolution inside the bottleneck (the "v1.5" placement the repo
    and torchvision use).  ResNet-50 at 224: 4.09e9, the published count.
    """
    kind, counts = _RESNET_PLANS[depth]

    def conv(hw_out, k, cin, cout):
        return hw_out * hw_out * k * k * cin * cout

    hw = -(-image // 2)                       # 7x7/2 stem, SAME
    macs = conv(hw, 7, 3, width)
    hw = -(-hw // 2)                          # 3x3/2 max pool, SAME
    cin = width
    for stage, n in enumerate(counts):
        ch = width * 2 ** stage
        for b in range(n):
            stride = 2 if (b == 0 and stage > 0) else 1
            hw_out = -(-hw // stride)
            if kind == "bottleneck":
                cout = ch * 4
                macs += conv(hw, 1, cin, ch)           # 1x1 reduce
                macs += conv(hw_out, 3, ch, ch)        # 3x3 (strided)
                macs += conv(hw_out, 1, ch, cout)      # 1x1 expand
            else:
                cout = ch
                macs += conv(hw_out, 3, cin, ch)
                macs += conv(hw_out, 3, ch, ch)
            if stride != 1 or cin != cout:
                macs += conv(hw_out, 1, cin, cout)     # projection
            cin, hw = cout, hw_out
    return macs + cin * classes


def resnet_train_flops_per_image(depth=50, image=224, classes=1000):
    return 3 * 2 * resnet_forward_macs(depth, image, classes)


def decoder_train_flops_per_token(dim, n_layers, vocab, seq, mlp_ratio=4):
    """Forward + backward FLOPs per token of a decoder-only transformer
    with an untied output head, as the algorithm requires them:

    - per layer 4*dim^2 (q, k, v, out) + 2*mlp_ratio*dim^2 (MLP) weights,
      6 FLOPs per weight per token;
    - the head, dim*vocab weights, ONCE: the embedding is a lookup (a
      gather), not a matmul, and costs no FLOPs;
    - causal attention: a token attends to seq/2 positions on average, so
      QK^T and PV are 2*2*(seq/2)*dim forward = 2*seq*dim, x3 with the
      backward = 6*seq*dim per layer.

    DIFFERS ON PURPOSE from the program's
    ``utils.metrics.transformer_flops_per_token``, which charges
    6 FLOPs/param to ``vocab*dim*2`` (embedding lookup included) and, by
    default, counts attention dense (12*L*dim*seq).
    """
    per_layer = 6 * (4 + 2 * mlp_ratio) * dim * dim + 6 * seq * dim
    return n_layers * per_layer + 6 * dim * vocab


def program_decoder_flops_per_token(dim, n_layers, vocab, seq, mlp_ratio=4,
                                    causal=False):
    """What ``utils.metrics.transformer_flops_per_token`` computes, copied
    so that the benchmark can print beside its own number what the
    program's formula would give.  tests/benchmark_tests checks the copy
    against the original."""
    n_params = vocab * dim * 2 + n_layers * (
        dim * dim * 4 + dim * dim * mlp_ratio * 2)
    attn = 12 * n_layers * dim * seq
    if causal:
        attn //= 2
    return 6 * n_params + attn


def decoder_param_count(dim, n_layers, vocab, mlp_ratio=4):
    """Parameters of the repo's decoder block at these sizes (two norm
    scales per layer, one final norm, untied embedding and head)."""
    per_layer = (4 + 2 * mlp_ratio) * dim * dim + 2 * dim
    return 2 * vocab * dim + n_layers * per_layer + dim
