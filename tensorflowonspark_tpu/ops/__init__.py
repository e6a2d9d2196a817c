"""TPU kernel library (pallas) + XLA reference implementations.

The reference framework has no ops layer at all — TensorFlow is its
compute substrate (SURVEY.md §1 "TFoS has no kernel/ops layer").  In a
TPU-native framework the hot ops are first-party: flash attention for
the transformer/long-context path and fused normalization, written in
pallas against the MXU/VMEM model (/opt/skills/guides/pallas_guide.md),
with pure-XLA reference implementations used for verification and as
the CPU fallback.
"""

from tensorflowonspark_tpu.ops.attention import (  # noqa: F401
    YarnScaling,
    apply_rope,
    flash_attention,
    mha_reference,
    rope_angles,
    yarn_frequencies,
    yarn_mscale,
    yarn_softmax_scale,
)
from tensorflowonspark_tpu.ops.norm import (  # noqa: F401
    fused_rmsnorm,
    rmsnorm_reference,
)
