"""Decoder-only transformer — the long-context flagship model family.

No counterpart exists in the reference (pre-LLM design, SURVEY.md §5
"Long-context — absent"); this is the model family that exercises the
framework's first-class mesh axes: data/fsdp (batch), model (tensor
parallel, Megatron-style column→row sharded matmul pairs where GSPMD
inserts the all-reduces), and seq (ring-attention sequence parallelism
via parallel/ring.py).

TPU-first choices mirror models/resnet.py: params live in float32,
activations/matmuls run in the config compute dtype (bfloat16 on TPU)
with f32 accumulation; layers are scanned (one compiled layer body);
attention is ops.flash_attention (pallas) unless a sequence-parallel
attn_fn is injected.

Two blocks live here.  The CLASSIC block (``Config``'s defaults:
per-head K/V attention, GELU MLP, float32 weights) has its sublayers
written once (``_attn_inputs``, ``_mlp``, ``_last_logits``) and three
programs over them besides ``apply``: ``prefill``, ``prefill_extend``
and ``decode_step_paged``.  The LATENT block (``attn_kind="latent"``:
latent attention, a gated SwiGLU MLP in the leading dense layers, an
expert layer with a shared expert in the rest, weights in
``param_dtype``) is the section "The latent block", with the same three
programs.  Its HYBRID form (``linear_layers``: a mixer per layer, the
layers named mixing by the gated delta rule of
``models/linear_attention.py`` and keeping per-session state instead of
rows, the others latent attention, here without rotary) is the section
"The hybrid block", with ``prefill`` and the step (a tail over a cached
prefix would need the state at the prefix's end); its latent layers may
read only what a learned sparse selection picks (``index_topk``,
``models/sparse_attention.py``) and its residual may be several
hyper-connected streams (``hc_mult``, ``models/hyper_connections.py``).
``init`` and ``apply`` dispatch on the config, and the serving engine
takes any block's
incremental functions and cache layout from ``Config.decode_fns()``: one
paged cache, one step (ROADMAP.md Design 4 has what still keeps the sets
of programs apart).
"""

from __future__ import annotations

import dataclasses
import functools
import typing

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from tensorflowonspark_tpu import ops
from tensorflowonspark_tpu.models import hyper_connections as hc
from tensorflowonspark_tpu.models import latent_attention as latent
from tensorflowonspark_tpu.models import layers as L
from tensorflowonspark_tpu.models import linear_attention as linear
from tensorflowonspark_tpu.models import moe
from tensorflowonspark_tpu.models import sparse_attention as sparse


@dataclasses.dataclass(frozen=True)
class Config:
    vocab_size: int = 32000
    dim: int = 512
    n_layers: int = 4
    n_heads: int = 8
    max_seq: int = 2048
    mlp_ratio: int = 4
    rope_base: float = 10000.0
    dtype: str = "bfloat16"  # compute dtype; weights: ``param_dtype``
    # 'flash' = pallas kernel (single-chip / shard_map contexts only:
    # GSPMD cannot auto-partition a pallas_call); 'reference' = pure XLA
    # einsum formulation, partitionable by GSPMD on any mesh.
    attn_impl: str = "flash"
    # -- what describes a published block; the defaults are the classic one
    param_dtype: str = "float32"     # the type weights are made, kept and
    #                                  exported in
    attn_kind: str = "mha"           # "mha": per-head K/V | "latent"
    kv_lora_rank: int = 0            # latent: width of the cached latent
    qk_nope_dim: int = 0             # latent: per-head q/k width without rope
    qk_rope_dim: int = 0             # latent: rotary width (one key for all heads)
    v_head_dim: int = 0              # latent: per-head v width
    qk_norm: bool = False            # latent: RMSNorm on each head's q
    rope_scaling: ops.YarnScaling | None = None
    ffn_kind: str = "gelu"           # MLP of a dense layer: "gelu" | "swiglu"
    ffn_dim: int = 0                 # its width; 0: dim * mlp_ratio
    n_dense_layers: int = 0          # leading dense layers before the expert ones
    n_experts: int = 0               # routed experts = router outputs; 0: none
    n_experts_held: int = 0          # experts THIS program holds; 0: all
    expert_offset: int = 0           # first held expert's index
    experts_per_token: int = 0
    expert_dim: int = 0
    n_shared_experts: int = 0
    routed_scale: float = 1.0
    qk_rotary: bool = True           # latent: rotate the rope part of q and
    #                                  of the shared key (False: no position
    #                                  enters the score at all)
    norm_eps: float = 1e-6           # the latent block's RMSNorms
    # -- a mixer per layer: the layers named here (0-based) mix by the
    # gated delta rule (models/linear_attention.py), the others by
    # ``attn_kind``.  The default is one kind for all layers.
    linear_layers: tuple = ()
    linear_heads: int = 0
    linear_head_dim: int = 0
    linear_conv: int = 4             # length of the short convolution
    linear_rank: int = 0             # width of the decay's and the output
    #                                  gate's low-rank maps
    state_dtype: str = "float32"     # a session's recurrent state
    linear_gate_lower_bound: float = 0.0  # < 0: the delta rule's log-decay
    #                                  is bounded below by it (the "safe"
    #                                  gate); 0: softplus, unbounded
    # -- latent attention's query through a low-rank latent, and a learned
    # sparse selection of the positions it reads (models/sparse_attention.py)
    q_lora_rank: int = 0             # > 0: q = RMSNorm(h W_qa) W_qb
    index_heads: int = 0             # the indexer's heads
    index_head_dim: int = 0
    index_rope_dim: int = 0          # the indexer's rotary width
    index_topk: int = 0              # positions a query reads; 0: all
    index_kpool: int = 1             # positions pooled into one index key;
    #                                  a query's own group is always read
    # -- hyper-connections (models/hyper_connections.py): hc_mult residual
    # streams that every sublayer reads and writes; 0: one plain residual
    hc_mult: int = 0
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    swiglu_limit: float = 0.0        # > 0: the gate clamped above and the
    #                                  up projection to +-limit

    def __post_init__(self):
        if self.attn_kind not in ("mha", "latent"):
            raise ValueError(f"unknown attn_kind {self.attn_kind!r}")
        if self.ffn_kind not in ("gelu", "swiglu"):
            raise ValueError(f"unknown ffn_kind {self.ffn_kind!r}")
        if self.attn_kind == "latent":
            # without rotary the rope part may be absent altogether
            if min(self.kv_lora_rank, self.qk_nope_dim, self.v_head_dim) < 1 \
                    or self.qk_rope_dim % 2 \
                    or self.qk_rope_dim < (2 if self.qk_rotary else 0):
                raise ValueError(
                    "latent attention needs kv_lora_rank, qk_nope_dim, "
                    "v_head_dim >= 1 and an even qk_rope_dim >= 2 (>= 0 "
                    "without rotary)")
            if self.ffn_kind != "swiglu":
                raise ValueError("the latent block's MLP is ffn_kind="
                                 "'swiglu'")
        elif (self.ffn_kind != "gelu" or self.n_experts
              or self.rope_scaling is not None):
            raise ValueError(
                "per-head K/V attention (attn_kind='mha') runs only the "
                "classic block: GELU MLP, no experts, plain rotary.  A "
                "gated MLP, experts or rope scaling need "
                "attn_kind='latent'")
        if self.linear_layers:
            dense = {i in self.linear_layers
                     for i in range(self.dense_layers)}
            if (self.attn_kind != "latent"
                    or tuple(sorted(set(self.linear_layers)))
                    != tuple(self.linear_layers)
                    or not 0 <= self.linear_layers[0]
                    or self.linear_layers[-1] >= self.n_layers
                    or min(self.linear_heads, self.linear_head_dim,
                           self.linear_rank) < 1 or self.linear_conv < 2
                    or len(dense) > 1):
                raise ValueError(
                    "linear_layers names, in order, layers of a latent "
                    "block (attn_kind='latent') and needs linear_heads, "
                    "linear_head_dim, linear_rank >= 1 and linear_conv >= "
                    "2; the leading dense layers share one kind of mixer")
        if (self.index_topk or self.hc_mult) and not self.linear_layers:
            raise ValueError(
                "a sparse selection (index_topk) and hyper-connections "
                "(hc_mult) are written for the hybrid block: name its "
                "delta-rule layers in linear_layers")
        if self.index_topk and not (
                min(self.index_heads, self.index_head_dim, self.q_lora_rank,
                    self.index_kpool) >= 1
                and self.index_topk % self.index_kpool == 0
                and 0 <= self.index_rope_dim <= self.index_head_dim
                and self.index_rope_dim % 2 == 0):
            raise ValueError(
                "a sparse selection needs index_heads, index_head_dim, "
                "q_lora_rank (its queries read the query latent) >= 1, "
                "index_topk a multiple of index_kpool and an even "
                "index_rope_dim <= index_head_dim")
        if (self.q_lora_rank and self.classic) or self.hc_mult < 0 \
                or (self.hc_mult and self.hc_sinkhorn_iters < 1) \
                or self.linear_gate_lower_bound > 0 or self.swiglu_limit < 0:
            raise ValueError(
                "q_lora_rank is latent attention's; hc_mult >= 0 with "
                "hc_sinkhorn_iters >= 1; linear_gate_lower_bound <= 0; "
                "swiglu_limit >= 0")
        if self.n_experts:
            held = self.n_experts_held or self.n_experts
            if not (0 < self.experts_per_token <= self.n_experts
                    and self.expert_dim > 0
                    and 0 <= self.expert_offset
                    and self.expert_offset + held <= self.n_experts
                    and 0 <= self.n_dense_layers < self.n_layers):
                raise ValueError(
                    "experts need 0 < experts_per_token <= n_experts, an "
                    "expert_dim, held experts inside [0, n_experts) and "
                    "at least one expert layer after the dense ones")

    @property
    def head_dim(self):
        assert self.dim % self.n_heads == 0
        return self.dim // self.n_heads

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def classic(self):
        return self.attn_kind == "mha"

    @property
    def q_head_dim(self):
        return self.qk_nope_dim + self.qk_rope_dim

    @property
    def latent_row(self):
        """Numbers the latent cache keeps per token and layer."""
        return self.kv_lora_rank + self.qk_rope_dim

    @property
    def dense_layers(self):
        """Layers unrolled before the scan: the leading dense ones of an
        expert model."""
        return self.n_dense_layers if self.n_experts else 0

    @property
    def mixers(self):
        """Each layer's mixer, in order: ``"kda"`` | ``attn_kind``."""
        return tuple("kda" if i in self.linear_layers else self.attn_kind
                     for i in range(self.n_layers))

    def decode_fns(self):
        """The seam between this model and the serving engine
        (:class:`DecodeFns`): the incremental functions and the cache's
        row layout, for whichever block this config describes."""
        return _decode_fns(self)


def _layer_init(key, cfg):
    ks = jax.random.split(key, 6)
    dim, mlp = cfg.dim, cfg.dim * cfg.mlp_ratio
    dt = jnp.dtype(cfg.param_dtype)
    dense = lambda k, i, o: L._he_init(k, (i, o), i, dt)
    return {
        "ln1": jnp.ones((dim,), dt),
        "wqkv": dense(ks[0], dim, 3 * dim),
        "wo": dense(ks[1], dim, dim),
        "ln2": jnp.ones((dim,), dt),
        "w1": dense(ks[2], dim, mlp),
        "w2": dense(ks[3], mlp, dim),
    }


def init(key, cfg: Config):
    """Params pytree in ``cfg.param_dtype``; per-layer trees stacked on a
    leading axis so apply() scans one compiled layer body.  The latent
    block's leading dense layers are a second stack, ``dense_layers``,
    and the layers after them that mix by the gated delta rule a third,
    ``kda_layers`` (``layers`` then holds the others, in order)."""
    k_embed, k_head, k_layers = jax.random.split(key, 3)
    dt = jnp.dtype(cfg.param_dtype)
    nd = cfg.dense_layers
    layer_init = _layer_init if cfg.classic else functools.partial(
        _latent_layer_init, expert=bool(cfg.n_experts))
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    mixers = cfg.mixers

    def keys_of(kda):
        """The keys of the layers after the dense ones that mix so."""
        return layer_keys[jnp.asarray(
            [i for i in range(nd, cfg.n_layers)
             if (mixers[i] == "kda") == kda], jnp.int32)]

    layers = jax.vmap(lambda k: layer_init(k, cfg))(
        keys_of(False) if cfg.linear_layers
        else layer_keys[nd:] if nd else layer_keys)
    params = {
        "embed": (jax.random.normal(
            k_embed, (cfg.vocab_size, cfg.dim), jnp.float32
        ) * 0.02).astype(dt),
        "layers": layers,
        "ln_f": jnp.ones((cfg.dim,), dt),
        "head": L._he_init(k_head, (cfg.dim, cfg.vocab_size), cfg.dim, dt),
    }
    if nd:
        params["dense_layers"] = jax.vmap(
            lambda k: _latent_layer_init(k, cfg, expert=False,
                                         mixer=mixers[0])
        )(layer_keys[:nd])
    if cfg.linear_layers:
        params["kda_layers"] = jax.vmap(
            lambda k: layer_init(k, cfg, mixer="kda"))(keys_of(True))
    return params


def param_specs(cfg: Config, *, tp_axis="model", fsdp_axis="fsdp", mesh=None):
    """Megatron-style PartitionSpecs matching init()'s tree.

    Column-parallel (out-dim on tp): wqkv, w1, head; row-parallel (in-dim
    on tp): wo, w2 — each column→row pair needs exactly one all-reduce,
    which GSPMD inserts from these annotations.  Layer trees carry the
    leading scan axis (None).  Pass ``mesh`` to drop axes the mesh does
    not define (e.g. a data x seq x model mesh without fsdp).
    """
    if not cfg.classic:
        raise NotImplementedError(
            "param_specs describes the classic block; the latent block "
            "has no tensor-parallel layout yet (its expert axis: "
            "models/moe.param_specs)")
    if mesh is not None:
        axes = set(mesh.shape)
        tp_axis = tp_axis if tp_axis in axes else None
        fsdp_axis = fsdp_axis if fsdp_axis in axes else None
    col = P(fsdp_axis, tp_axis)
    row = P(tp_axis, fsdp_axis)
    lcol = P(None, fsdp_axis, tp_axis)
    lrow = P(None, tp_axis, fsdp_axis)
    return {
        "embed": P(None, fsdp_axis),
        "layers": {
            "ln1": P(None, None),
            "wqkv": lcol,
            "wo": lrow,
            "ln2": P(None, None),
            "w1": lcol,
            "w2": lrow,
        },
        "ln_f": P(None),
        "head": col,
    }


def _matmul(x, w):
    return jnp.dot(
        x, w.astype(x.dtype), preferred_element_type=jnp.float32
    ).astype(x.dtype)


# The classic block's sublayers, each written once.  They open no
# ``jax.named_scope``: the scopes are the stable names a device trace is
# read by (PERF.md section 3; metadata only), and they differ between the
# training program and the serving ones, so every caller opens its own.

def _attn_inputs(p, x, cfg, cos, sin, positions):
    """Norm, ``wqkv``, heads apart, rope on q and k at ``positions``
    (None: the array index): ``q, k, v`` each [B, S, H, D].  Keys come
    out POST-rotation, which is how they are cached."""
    b, s, _ = x.shape
    y = ops.rmsnorm_reference(x, p["ln1"])
    qkv = _matmul(y, p["wqkv"]).reshape(b, s, 3, cfg.n_heads, cfg.head_dim)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    q = ops.apply_rope(q, cos, sin, positions=positions)
    k = ops.apply_rope(k, cos, sin, positions=positions)
    return q, k, v


def _mlp(p, x):
    """The GELU MLP of ``norm(x)``, residual not added."""
    y = ops.rmsnorm_reference(x, p["ln2"])
    return _matmul(jax.nn.gelu(_matmul(y, p["w1"])), p["w2"])


def _block(p, x, cfg, rope, attn_fn, scope):
    """The classic block over a whole sequence: ``(x after attention, the
    MLP's output still to be added, k, v)``, k and v [B, S, H, D]."""
    cos, sin, positions = rope
    with jax.named_scope(scope + "attn"):
        q, k, v = _attn_inputs(p, x, cfg, cos, sin, positions)
        attn = attn_fn(q, k, v).reshape(x.shape)
        x = x + _matmul(attn, p["wo"])
    with jax.named_scope(scope + "mlp"):
        return x, _mlp(p, x), k, v


def _layer_apply(p, x, cfg, rope, attn_fn):
    # ``jax.checkpoint`` wraps THIS function: the keys and values are
    # dropped in here, so what remat saves does not know of them
    x, y, _k, _v = _block(p, x, cfg, rope, attn_fn, "block/")
    return x + y


def _default_attn_fn(cfg, attn_fn):
    """``attn_fn`` where the caller gave one, else the config's causal
    attention over whole sequences."""
    if attn_fn is not None:
        return attn_fn
    base = (ops.flash_attention if cfg.attn_impl == "flash"
            else ops.mha_reference)
    return functools.partial(base, causal=True)


def _lm_head(params, x, logits_dtype, return_hidden, eps=1e-6):
    with jax.named_scope("lm_head"):
        x = ops.rmsnorm_reference(x, params["ln_f"], eps)
        if return_hidden:
            return x
        logits = _matmul(x, params["head"])
        return (logits if logits_dtype is None
                else logits.astype(logits_dtype))


def apply(params, tokens, cfg: Config, *, attn_fn=None,
          logits_dtype=jnp.float32, remat=False, positions=None,
          return_hidden=False):
    """tokens [B, S] int32 -> logits [B, S, vocab] (``logits_dtype``,
    default float32; pass None to keep the compute dtype — the training
    loss does, so the [B,S,vocab] activation stays bfloat16 in HBM).
    ``return_hidden=True`` skips the head matmul and returns the final
    hidden states [B, S, dim] (the blockwise-CE loss consumes these).

    ``attn_fn(q, k, v) -> out`` on [B, S, H, D]; default is causal
    pallas flash attention.  Pass
    ``parallel.sequence_parallel_attention(mesh, 'ring', causal=True)``
    for sequence-parallel long-context runs.

    ``positions`` ([S] or [B, S] int32): explicit global rope positions
    for sequences not in contiguous order — e.g. zigzag-permuted
    long-context batches (``parallel.zigzag_permutation``).  The default
    causal flash mask assumes CONTIGUOUS order; with permuted input,
    pass an ``attn_fn`` whose masking understands the layout
    (``sequence_parallel_attention(mesh, 'zigzag', causal=True)``).

    ``remat=True`` checkpoints each scanned layer: the backward pass
    recomputes layer internals instead of keeping ~10·dim·B·S bytes per
    layer resident, trading ~30% more FLOPs for an O(L·B·S·dim) →
    O(B·S·dim) activation footprint (how the bigger sweep batches fit).
    ``remat="dots"`` is the selective policy: every matmul output is
    saved and only the cheap elementwise chain is recomputed (jax
    dots_with_no_batch_dims_saveable — the attention einsums inside the
    flash kernel are custom-VJP-opaque and unaffected).  A
    save-only-attn-output policy was evaluated and rejected: the flash
    custom-VJP's residuals (lse etc.) are not name-saveable, so its
    forward re-runs on backward regardless — full remat cost plus extra
    residency.
    """
    if not cfg.classic:
        if positions is not None or remat:
            raise NotImplementedError(
                "the latent block's apply takes no positions= or remat=")
        return _latent_apply(params, tokens, cfg, attn_fn=attn_fn,
                             logits_dtype=logits_dtype,
                             return_hidden=return_hidden)
    if positions is not None and attn_fn is None:
        # the default flash mask is causal by ARRAY INDEX; on permuted
        # input that silently attends to the future — demand an attn_fn
        # whose masking understands the layout
        raise ValueError(
            "positions= implies a non-contiguous sequence layout; pass an "
            "attn_fn that masks by global position (e.g. "
            "sequence_parallel_attention(mesh, 'zigzag', causal=True))")
    attn_fn = _default_attn_fn(cfg, attn_fn)
    dtype = cfg.compute_dtype
    with jax.named_scope("embed"):
        x = params["embed"].astype(dtype)[tokens]
    if positions is None:
        rope_len = tokens.shape[1]
        pos2d = None
    else:
        # cover every global position: jax gather would silently CLAMP
        # an index past the table instead of erroring
        rope_len = max(tokens.shape[1], cfg.max_seq)
        pos = jnp.asarray(positions, jnp.int32)
        pos2d = jnp.broadcast_to(
            pos[None] if pos.ndim == 1 else pos, tokens.shape)
    cos, sin = ops.rope_angles(rope_len, cfg.head_dim, cfg.rope_base)
    rope = (cos, sin, pos2d)

    layer_fn = _layer_apply
    if remat:
        if remat == "dots":
            policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        elif remat is True:
            policy = None  # full remat
        else:
            raise ValueError(
                f"remat must be bool or 'dots'; got {remat!r}")
        layer_fn = jax.checkpoint(
            _layer_apply, static_argnums=(2, 4),  # cfg, attn_fn
            policy=policy)

    def body(x, layer_params):
        return layer_fn(layer_params, x, cfg, rope, attn_fn), None

    x, _ = lax.scan(body, x, params["layers"])
    return _lm_head(params, x, logits_dtype, return_hidden)


def _blockwise_nll(x, head, labels, block_v):
    """Per-position next-token NLL WITHOUT materializing [N, vocab].

    Streams the vocabulary in ``block_v`` slices: each scan step does
    one [N, D] x [D, block_v] matmul and folds it into a running
    (max, sumexp, gold-logit) online-logsumexp state — the CE analogue
    of flash attention's online softmax.  The body is jax.checkpoint'd,
    so the backward recomputes each block's logits instead of keeping
    them: peak logits memory drops from N·V to N·block_v (at dim 1024 /
    seq 2048 / vocab 16k / batch 32 that is ~2 GB of bf16 logits that
    never hit HBM), buying batch headroom the sweep can spend.

    ``x``: [N, D] final hidden states (compute dtype); ``head``:
    [D, V] f32 params; ``labels``: [N] int.  Single-chip / data-parallel
    path — under Megatron TP keep the dense CE (the column-parallel
    head wants the per-shard logsumexp exchange instead).
    """
    n, _d = x.shape
    v = head.shape[1]
    if v % block_v:
        raise ValueError(f"vocab {v} not divisible by ce_block {block_v}")
    nb = v // block_v
    # [nb, D, block_v] scan operand: reshape splits V contiguously
    head_blocks = head.reshape(-1, nb, block_v).transpose(1, 0, 2)
    labels = labels.astype(jnp.int32)

    def body(carry, inp):
        m, s, gold = carry
        vb, w = inp
        logits = jnp.dot(
            x, w.astype(x.dtype), preferred_element_type=jnp.float32)
        bm = jnp.max(logits, axis=-1)
        nm = jnp.maximum(m, bm)
        s = s * jnp.exp(m - nm) \
            + jnp.sum(jnp.exp(logits - nm[:, None]), axis=-1)
        base = vb * block_v
        in_blk = (labels >= base) & (labels < base + block_v)
        idx = jnp.clip(labels - base, 0, block_v - 1)
        g = jnp.take_along_axis(logits, idx[:, None], axis=1)[:, 0]
        gold = gold + jnp.where(in_blk, g, 0.0)
        return (nm, s, gold), None

    # finite lower bound, not -inf: exp(min - nm) underflows to exactly
    # 0 like -inf would, but the backward pass never sees inf arithmetic
    init = (jnp.full((n,), jnp.finfo(jnp.float32).min, jnp.float32),
            jnp.zeros((n,), jnp.float32),
            jnp.zeros((n,), jnp.float32))
    (m, s, gold), _ = lax.scan(
        jax.checkpoint(body), init,
        (jnp.arange(nb), head_blocks))
    return m + jnp.log(s) - gold


def loss_fn(params, tokens, cfg: Config, *, attn_fn=None, remat=False,
            labels=None, positions=None, ce_impl="dense", ce_block=2048):
    """Next-token cross entropy (mean over B, S-1).

    Default: labels are ``tokens`` shifted by one (contiguous order).
    For permuted layouts (zigzag long-context), pass explicit ``labels``
    aligned with ``tokens``' positions (-1 = ignore, e.g. each row's
    final global position) plus matching ``positions`` — see
    ``zigzag_lm_batch``.

    ``ce_impl="dense"`` (default): logits stay in the compute dtype
    (bfloat16); the softmax/CE reductions accumulate in float32 — XLA
    fuses the upcast into the reduce, so no [B, S, vocab] float32
    tensor ever hits HBM (round-2 finding: the f32 logits path cost
    ~2 GB of HBM traffic per step at dim 1024 / seq 2048 / vocab 16k).

    ``ce_impl="blockwise"``: never materializes [B, S, vocab] at all —
    the head matmul streams in ``ce_block``-wide vocab slices through an
    online logsumexp (``_blockwise_nll``), checkpointed so the backward
    recomputes each slice.  Single-chip / data-parallel option for when
    logits memory bounds the batch size (a sweep axis)."""
    if ce_impl not in ("dense", "blockwise"):
        raise ValueError(f"unknown ce_impl {ce_impl!r}")
    if ce_impl == "blockwise":
        x = apply(params, tokens, cfg, attn_fn=attn_fn, remat=remat,
                  positions=positions, return_hidden=True)
        if labels is None:
            x = x[:, :-1]
            labels = tokens[:, 1:]
            valid = None
        else:
            valid = labels >= 0
            labels = jnp.maximum(labels, 0)
        b, s, d = x.shape
        with jax.named_scope("loss"):
            nll = _blockwise_nll(
                x.reshape(b * s, d), params["head"],
                labels.reshape(b * s), ce_block).reshape(b, s)
    else:
        logits = apply(params, tokens, cfg, attn_fn=attn_fn,
                       logits_dtype=None, remat=remat, positions=positions)
        if labels is None:
            logits = logits[:, :-1]
            labels = tokens[:, 1:]
            valid = None
        else:
            valid = labels >= 0
            labels = jnp.maximum(labels, 0)
        with jax.named_scope("loss"):
            lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
            gold = jnp.take_along_axis(
                logits, labels[..., None].astype(jnp.int32), axis=-1
            )[..., 0].astype(jnp.float32)
            nll = lse - gold
    with jax.named_scope("loss"):
        if valid is None:
            return jnp.mean(nll)
        vf = valid.astype(jnp.float32)
        return jnp.sum(nll * vf) / jnp.maximum(jnp.sum(vf), 1.0)


# ---------------------------------------------------------------------------
# Incremental (KV-cached) decode — the serving/decode tier's model half.
#
# No reference counterpart (the reference delegates all inference to TF
# Serving, SURVEY.md §2.2): ``prefill`` runs the prompt once and hands back
# the per-layer keys/values, ``decode_step_paged`` extends every slot of a
# block-paged pool (serving/decode/kvcache.py) by a window of tokens,
# ``prefill_extend`` runs a prompt's tail over a cached prefix.  All reuse
# ``_layer_apply``'s sublayers (rmsnorm / rope / gelu MLP / f32-accumulated
# matmuls), so a KV-cached greedy decode is token-identical to re-running
# ``apply`` on the growing sequence — ``greedy_decode_reference`` below is
# that oracle, and tests/test_decode.py gates the parity.
#
# The cache is two pools, keys and values, ``[num_blocks, n_layers,
# block_size, n_heads, head_dim]``: a token's row whole, in the order the
# block computes it.  The step takes both pools as its layer loop's carry,
# writes layer i's window rows at ``[block, i, offset]`` and gathers
# ``pool[tables, i]``; the engine donates them, so they are written in
# place and nothing pool-sized is copied or re-laid out (scanned as
# ``pool.transpose(1, 0, ...)``, or with heads before positions, they are).
# ---------------------------------------------------------------------------

_NEG_INF = -1e30  # finite mask fill (ops.attention convention: never -inf)


def _classic_only(cfg, name):
    if not cfg.classic:
        raise ValueError(
            f"transformer.{name} is the classic block's; a latent config "
            f"has its own (cfg.decode_fns() hands out either block's)")


def _last_logits(params, x, lengths, eps=1e-6):
    """Head over each row's final REAL position: [B, T, dim] -> [B, vocab]."""
    b, t, _ = x.shape
    x = ops.rmsnorm_reference(x, params["ln_f"], eps)
    if lengths is None:
        last = jnp.full((b,), t - 1, jnp.int32)
    else:
        last = jnp.asarray(lengths, jnp.int32) - 1
    x_last = jnp.take_along_axis(
        x, jnp.clip(last, 0, t - 1)[:, None, None], axis=1)[:, 0]
    return _matmul(x_last, params["head"]).astype(jnp.float32)


def prefill(params, tokens, cfg: Config, *, lengths=None, attn_fn=None):
    """Prompt pass for incremental decode.

    ``tokens`` [B, T] int32 right-padded prompts, ``lengths`` [B] true
    prompt lengths (default: all T).  Returns ``(logits, k, v)`` —
    ``logits`` [B, vocab] float32 at each row's final REAL position (the
    next-token distribution), ``k``/``v`` [B, n_layers, T, n_heads,
    head_dim], keys rope-rotated: each token's row whole, the order the
    block computes them in and the pools keep them in
    (``PagedKVCache.insert_tail`` cuts them into blocks).

    Padded tail positions produce garbage k/v, but they are never read:
    causal masking keeps them out of the real positions' attention here,
    and ``decode_step_paged`` masks to ``position <= cursor`` while its
    next write lands AT the cursor, overwriting the first padded column.
    """
    _classic_only(cfg, "prefill")
    attn_fn = _default_attn_fn(cfg, attn_fn)
    t = tokens.shape[1]
    x = params["embed"].astype(cfg.compute_dtype)[tokens]
    cos, sin = ops.rope_angles(t, cfg.head_dim, cfg.rope_base)
    rope = (cos, sin, None)

    def body(x, layer_params):
        x, y, k, v = _block(layer_params, x, cfg, rope, attn_fn, "")
        return x + y, (k, v)

    x, (k, v) = lax.scan(body, x, params["layers"])
    return (_last_logits(params, x, lengths), *_batch_major(k, v))


def _batch_major(*stacked):
    """A scan's per-layer outputs [L, B, T, H, D] as a prefill hands them
    back, [B, L, T, H, D]."""
    with jax.named_scope("write_kv"):
        return tuple(a.transpose(1, 0, 2, 3, 4) for a in stacked)


def _paged_context(pool, layer, tables):
    """Every mapped position of each row of ``tables``, ``[rows,
    blocks*block_size, *row]``, of one layer of a ``[num_blocks,
    n_layers, block_size, *row]`` pool (a classic pool's row is
    ``[n_heads, head_dim]``, a latent pool's ``[latent_row]``)."""
    with jax.named_scope("gather_kv"):
        ctx = pool[tables, layer]               # [rows, blocks, bs, *row]
        return ctx.reshape((ctx.shape[0], -1) + ctx.shape[3:])


def decode_step_paged(params, tokens, cfg: Config, pool_k, pool_v,
                      block_tables, lengths):
    """One fused decode iteration over a block-paged KV pool.

    The step of ``kvcache.PagedKVCache``, continuous batching over ALL
    slots: ``tokens`` [S, W] int32 is a WINDOW of W tokens per slot (W=1
    is the plain step, and the draft model's; W=K is the
    speculative-verify step over a draft window), token j of slot s
    sitting at logical position ``lengths[s] + j``.  ``pool_k``/
    ``pool_v`` are the shared pools [num_blocks, n_layers, block_size,
    n_heads, head_dim], each token's row whole; ``block_tables``
    [S, blocks_per_slot] int32 maps each slot's logical blocks to
    physical ones (unused entries point at sentinel block 0);
    ``lengths`` [S] int32.  Returns ``(logits [S, W, vocab] float32,
    new_pool_k, new_pool_v)``.

    Both pools go through the layer loop as its carry: layer i writes
    its window rows at ``[block, i, offset]`` and reads ``pool[tables,
    i]``, so with the pools donated (the engine does) they are written in
    place and nothing pool-sized is copied or re-laid out.

    Write discipline: every window token's k/v lands at ``table[s,
    pos//bs]``, row ``pos%bs``; positions past the slot's mapped capacity
    are routed into the sentinel block, so a window that overruns
    ``max_seq`` can never clobber another slot's live blocks.  Query j
    attends ``position <= lengths[s] + j`` — causal inside the window,
    and stale entries past a rejected draft's rollback cursor are
    unreachable until a later (correct) write lands on them.  Free slots
    are numerically inert by construction: with length 0, token 0 and an
    all-sentinel table a free slot attends the sentinel block only —
    finite garbage confined to that slot's logits rows, which the
    scheduler discards.  No operation mixes slots.
    """
    _classic_only(cfg, "decode_step_paged")
    dtype = cfg.compute_dtype
    h, hd = cfg.n_heads, cfg.head_dim
    s_slots, w = tokens.shape
    bs = pool_k.shape[2]
    cap = block_tables.shape[1] * bs       # per-slot mapped capacity
    lengths = jnp.asarray(lengths, jnp.int32)
    tables = jnp.asarray(block_tables, jnp.int32)
    scale = 1.0 / (hd ** 0.5)

    # positions of the window tokens, [S, W]
    pos = lengths[:, None] + jnp.arange(w, dtype=jnp.int32)[None, :]
    posc = jnp.clip(pos, 0, cap - 1)
    # where each row goes, flattened: (block, offset); overflow (pos >=
    # cap) lands in the sentinel block's matching row
    wblk = jnp.where(pos < cap,
                     jnp.take_along_axis(tables, posc // bs, axis=1),
                     0).reshape(-1)
    woff = (pos % bs).reshape(-1)
    # [S, 1, W, cap] — query j sees position <= lengths + j
    kv_mask = (jnp.arange(cap)[None, None, None, :]
               <= pos[:, None, :, None])

    x = params["embed"].astype(dtype)[tokens]               # [S, W, dim]
    cos, sin = ops.rope_angles(cap, cfg.head_dim, cfg.rope_base)

    def body(carry, inp):
        x, pool_k, pool_v = carry
        p, i = inp
        with jax.named_scope("attn"):
            q, k, v = _attn_inputs(p, x, cfg, cos, sin, posc)
        with jax.named_scope("write_kv"):
            pool_k = pool_k.at[wblk, i, woff].set(
                k.reshape(-1, h, hd).astype(pool_k.dtype))
            pool_v = pool_v.at[wblk, i, woff].set(
                v.reshape(-1, h, hd).astype(pool_v.dtype))
        kg = _paged_context(pool_k, i, tables).astype(jnp.float32)
        vg = _paged_context(pool_v, i, tables).astype(jnp.float32)
        with jax.named_scope("attn"):
            qf = q.astype(jnp.float32)                   # [S, W, H, D]
            scores = jnp.einsum("swhd,smhd->shwm", qf, kg) * scale
            scores = jnp.where(kv_mask, scores, _NEG_INF)
            probs = jax.nn.softmax(scores, axis=-1)
            attn = jnp.einsum("shwm,smhd->swhd", probs, vg)
            attn = attn.astype(dtype).reshape(s_slots, w, h * hd)
            x = x + _matmul(attn, p["wo"])
        with jax.named_scope("mlp"):
            y = _mlp(p, x)
        return (x + y, pool_k, pool_v), None

    (x, pool_k, pool_v), _ = lax.scan(
        body, (x, pool_k, pool_v),
        (params["layers"], jnp.arange(cfg.n_layers)))
    x = ops.rmsnorm_reference(x, params["ln_f"])
    logits = _matmul(x, params["head"]).astype(jnp.float32)
    return logits, pool_k, pool_v


def prefill_extend(params, tokens, cfg: Config, pool_k, pool_v,
                   prefix_tables, prefix_lens, *, lengths=None):
    """Tail prefill on top of trie-matched resident prefix blocks.

    The prefix-sharing half of admission: the matched prompt prefix's
    k/v already live in the paged pool, so only the unmatched TAIL is
    computed.  ``tokens`` [B, T] int32 right-padded tails; ``lengths``
    [B] true tail lengths (default: all T); ``prefix_tables``
    [B, nbp] int32 physical blocks of each row's matched prefix (pad
    rows with sentinel 0); ``prefix_lens`` [B] int32 matched token
    counts (whole blocks, possibly 0).  Tail queries attend the
    gathered prefix (masked to ``position < prefix_lens``) plus the
    tail causally; rope positions are ``prefix_lens + arange(T)``.
    Layer i reads the prefix as ``pool[prefix_tables, i]`` of the pools
    as they are ([num_blocks, n_layers, block_size, n_heads, head_dim]):
    no pool is re-laid out.

    Returns ``(logits [B, vocab] float32 at the last REAL tail
    position, k, v [B, n_layers, T, n_heads, head_dim])`` — the tail
    k/v in prefill layout, which ``PagedKVCache.insert_tail`` scatters
    into the slot's private blocks (the tail starts block-aligned, so
    the writes never touch shared blocks).
    """
    _classic_only(cfg, "prefill_extend")
    dtype = cfg.compute_dtype
    h, hd = cfg.n_heads, cfg.head_dim
    b, t = tokens.shape
    bs = pool_k.shape[2]
    pcap = prefix_tables.shape[1] * bs
    plens = jnp.asarray(prefix_lens, jnp.int32)
    ptab = jnp.asarray(prefix_tables, jnp.int32)
    scale = 1.0 / (hd ** 0.5)

    pos = plens[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    cos, sin = ops.rope_angles(pcap + t, cfg.head_dim, cfg.rope_base)
    # [B, 1, 1, P] prefix visibility; [T, T] causal within the tail
    pmask = (jnp.arange(pcap)[None, :] < plens[:, None])[:, None, None, :]
    cmask = (jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
             )[None, None, :, :]

    x = params["embed"].astype(dtype)[tokens]               # [B, T, dim]

    def layer(x, inp):
        p, i = inp
        with jax.named_scope("attn"):
            q, k, v = _attn_inputs(p, x, cfg, cos, sin, pos)
        kp = _paged_context(pool_k, i, ptab).astype(jnp.float32)
        vp = _paged_context(pool_v, i, ptab).astype(jnp.float32)  # [B,P,H,D]
        with jax.named_scope("attn"):
            qf = q.astype(jnp.float32)
            sp = jnp.einsum("bthd,bphd->bhtp", qf, kp) * scale
            st = jnp.einsum("bthd,bshd->bhts", qf,
                            k.astype(jnp.float32)) * scale
            sp = jnp.where(pmask, sp, _NEG_INF)
            st = jnp.where(cmask, st, _NEG_INF)
            probs = jax.nn.softmax(
                jnp.concatenate([sp, st], axis=-1), axis=-1)
            pp, pt = probs[..., :pcap], probs[..., pcap:]
            attn = (jnp.einsum("bhtp,bphd->bthd", pp, vp)
                    + jnp.einsum("bhts,bshd->bthd", pt,
                                 v.astype(jnp.float32)))
            attn = attn.astype(dtype).reshape(b, t, h * hd)
            x = x + _matmul(attn, p["wo"])
        with jax.named_scope("mlp"):
            y = _mlp(p, x)
        return x + y, (k, v)

    x, (k, v) = lax.scan(layer, x,
                         (params["layers"], jnp.arange(cfg.n_layers)))
    return (_last_logits(params, x, lengths), *_batch_major(k, v))


# ---------------------------------------------------------------------------
# The latent block: latent attention (models/latent_attention.py), a gated
# SwiGLU MLP in the dense layers, an expert layer with a shared expert in
# the rest (models/moe.py).  Pre-norm RMSNorm, sequential residuals, as the
# classic block.  The leading dense layers are unrolled, the others scan.
#
# Its cache is ONE pool of rows ``[c; k_rope]`` (``cfg.latent_row`` wide, for
# all heads): ``[num_blocks, n_layers, block_size, latent_row]``.  Prefill
# runs the expanded attention path and hands back rows; the decode step and
# a tail over a cached prefix run the absorbed path over gathered rows.  The
# step takes the pool as a loop carry and writes its rows in place (the
# engine donates it), so nothing pool-sized is copied.
# ---------------------------------------------------------------------------

def _latent_layer_init(key, cfg, expert, mixer="latent"):
    ka, kf = jax.random.split(key)
    dt = jnp.dtype(cfg.param_dtype)
    p = {"ln1": jnp.ones((cfg.dim,), dt), "ln2": jnp.ones((cfg.dim,), dt)}
    if mixer == "kda":
        p["kda"] = linear.init(ka, cfg, dt)
    else:
        p["attn"] = latent.init(ka, cfg, dt)
        if cfg.index_topk:
            p["index"] = sparse.init(jax.random.fold_in(key, 7), cfg, dt)
    if cfg.hc_mult:
        p["hc_attn"] = hc.init(jax.random.fold_in(key, 8), cfg, dt)
        p["hc_ffn"] = hc.init(jax.random.fold_in(key, 9), cfg, dt)
    if expert:
        p["moe"] = moe.init(
            kf, cfg.dim, cfg.expert_dim, cfg.n_experts,
            num_held=cfg.n_experts_held or cfg.n_experts,
            num_shared=cfg.n_shared_experts, dtype=dt)
    else:
        width = cfg.ffn_dim or cfg.dim * cfg.mlp_ratio
        k1, k2, k3 = jax.random.split(kf, 3)
        p["wg"] = L._he_init(k1, (cfg.dim, width), cfg.dim, dt)
        p["wu"] = L._he_init(k2, (cfg.dim, width), cfg.dim, dt)
        p["wd"] = L._he_init(k3, (width, cfg.dim), width, dt)
    return p


def _latent_ffn(p, x, cfg, index, live=None):
    """``(x + FFN(norm(x)), expert statistics or None)`` of layer
    ``index``; ``live`` marks the tokens whose routing the statistics
    count.  An expert layer's ``p["moe"]`` holds the routed experts of
    ALL the expert layers of its stack (``_latent_layers``), and layer
    ``index`` is ``index - cfg.dense_layers`` of them."""
    y, stats = _ffn_out(p, x, cfg, index, live)
    return x + y, stats


def _ffn_out(p, x, cfg, index, live=None):
    """``(FFN(norm(x)), expert statistics or None)``: :func:`_latent_ffn`
    without the residual."""
    y = ops.rmsnorm_reference(x, p["ln2"], cfg.norm_eps)
    limit = cfg.swiglu_limit or None
    if "moe" in p:
        y, stats = moe.apply(
            p["moe"], y, top_k=cfg.experts_per_token,
            routed_scale=cfg.routed_scale, expert_offset=cfg.expert_offset,
            live=live, bank=(index - cfg.dense_layers,
                             cfg.n_experts_held or cfg.n_experts),
            limit=limit)
        return y, {k: stats[k] for k in _MOE_COUNTERS}
    with jax.named_scope("mlp"):
        return moe.swiglu(y, p["wg"], p["wu"], p["wd"], limit), None


def _residual(p, name, x, cfg, f):
    """One sublayer ``f(x) -> (y, aux)`` on the residual: ``(x + y,
    aux)``, or under hyper-connections (``cfg.hc_mult``) the streams ``x``
    [..., n, C] through ``p[name]``'s maps (``hyper_connections.sublayer``)."""
    if cfg.hc_mult:
        return hc.sublayer(p[name], x, cfg, f)
    y, aux = f(x)
    return x + y, aux


_MOE_COUNTERS = ("picks", "picks_held", "rows_held", "experts_touched",
                 "tokens_per_expert_max", "dropped")


def _latent_layers(params, cfg, carry, layer_fn):
    """Run ``layer_fn(p, carry, layer_index) -> (carry, out, stats)``
    over every layer: the leading dense ones unrolled, the others under
    one scan.  Returns ``(carry, outs stacked [n_layers, ...], expert
    statistics stacked over the expert layers or None)``."""
    nd = cfg.dense_layers
    outs = []
    for i in range(nd):
        p = jax.tree_util.tree_map(lambda a: a[i], params["dense_layers"])
        carry, out, _ = layer_fn(p, carry, i)
        outs.append(out)

    # the routed experts do not ride the scan: sliced out layer by layer
    # they would be copied, whole, in front of the grouped-product kernel.
    # Every layer sees the bank of all layers' experts and picks its own
    # groups (moe.apply's ``bank``)
    layers, bank = params["layers"], {}
    if "moe" in layers:
        bank = {k: layers["moe"][k].reshape((-1,) + layers["moe"][k].shape[2:])
                for k in ("wg", "wu", "wd")}
        layers = dict(layers, moe={k: v for k, v in layers["moe"].items()
                                   if k not in bank})

    def body(carry, inp):
        p, index = inp
        if bank:
            p = dict(p, moe=dict(p["moe"], **bank))
        carry, out, stats = layer_fn(p, carry, index)
        return carry, (out, stats)

    carry, (scanned, stats) = lax.scan(
        body, carry, (layers, nd + jnp.arange(cfg.n_layers - nd)))
    if outs and scanned is not None:
        scanned = jnp.concatenate([jnp.stack(outs), scanned])
    return carry, scanned, stats


def _latent_forward(params, tokens, cfg, attn_fn):
    """Whole-sequence pass on the expanded path: ``(hidden [B, T, dim],
    rows [n_layers, B, T, latent_row])``."""
    attn_fn = _default_attn_fn(cfg, attn_fn)
    with jax.named_scope("embed"):
        x = params["embed"].astype(cfg.compute_dtype)[tokens]
    cos, sin = latent.rope_tables(cfg, tokens.shape[1])

    def layer(p, x, index):
        y = ops.rmsnorm_reference(x, p["ln1"], cfg.norm_eps)
        q, rows = latent.project(p["attn"], y, cfg, cos, sin)
        a = latent.attend_expanded(p["attn"], q, rows, cfg, attn_fn)
        x, stats = _latent_ffn(p, x + _matmul(a, p["attn"]["wo"]), cfg,
                               index)
        return x, rows, stats

    x, rows, _ = _latent_layers(params, cfg, x, layer)
    return x, rows


def _latent_apply(params, tokens, cfg, *, attn_fn, logits_dtype,
                  return_hidden):
    forward = _hybrid_forward if cfg.linear_layers else _latent_forward
    x, _rows = forward(params, tokens, cfg, attn_fn)
    return _lm_head(params, x, logits_dtype, return_hidden, cfg.norm_eps)


def latent_prefill(params, tokens, cfg: Config, *, lengths=None,
                   attn_fn=None):
    """The latent block's :func:`prefill`: ``(logits [B, vocab] float32
    at each row's last real position, rows [B, n_layers, T,
    latent_row])`` — the rows are all the cache keeps of a token."""
    x, rows = _latent_forward(params, tokens, cfg, attn_fn)
    return (_last_logits(params, x, lengths, cfg.norm_eps),
            rows.transpose(1, 0, 2, 3))


def latent_decode_step_paged(params, tokens, cfg: Config, pool,
                             block_tables, lengths):
    """The latent block's :func:`decode_step_paged`: one windowed decode
    iteration over the latent pool ``[num_blocks, n_layers, block_size,
    latent_row]``, same write and masking discipline (overflow into the
    sentinel block, query j sees ``position <= lengths + j``).  Returns
    ``(logits [S, W, vocab] float32, new_pool, counters)``; ``counters``
    are the expert layers' dispatch counts summed over layers (empty for
    a model without experts), a few ints that ride back with the logits.
    """
    dtype = cfg.compute_dtype
    s_slots, w = tokens.shape
    bs = pool.shape[2]
    cap = block_tables.shape[1] * bs
    lengths = jnp.asarray(lengths, jnp.int32)
    tables = jnp.asarray(block_tables, jnp.int32)
    pos = lengths[:, None] + jnp.arange(w, dtype=jnp.int32)[None, :]
    posc = jnp.clip(pos, 0, cap - 1)
    # a window token past the slot's mapped capacity lands in the sentinel
    wblk = jnp.where(pos < cap,
                     jnp.take_along_axis(tables, posc // bs, axis=1),
                     0).reshape(-1)
    woff = (posc % bs).reshape(-1)
    kv_mask = (jnp.arange(cap)[None, None, None, :]
               <= pos[:, None, :, None])                 # [S, 1, W, cap]
    x = params["embed"].astype(dtype)[tokens]            # [S, W, dim]
    cos, sin = latent.rope_tables(cfg, cap)
    # a free slot (length 0) carries padding: it runs, it is not counted
    live = jnp.broadcast_to((lengths > 0)[:, None], (s_slots, w))

    def layer(p, carry, index):
        x, pool = carry
        y = ops.rmsnorm_reference(x, p["ln1"], cfg.norm_eps)
        q, rows = latent.project(p["attn"], y, cfg, cos, sin, posc)
        with jax.named_scope("write_kv"):
            pool = pool.at[wblk, index, woff].set(
                rows.reshape(-1, rows.shape[-1]).astype(pool.dtype))
        ctx = _paged_context(pool, index, tables)
        a = latent.attend_absorbed(p["attn"], q, ctx, kv_mask, cfg)
        x, stats = _latent_ffn(p, x + _matmul(a, p["attn"]["wo"]), cfg,
                               index, live)
        return (x, pool), None, stats

    (x, pool), _, stats = _latent_layers(params, cfg, (x, pool), layer)
    return _step_logits(params, x, cfg), pool, _step_counters(cfg, stats)


def _step_logits(params, x, cfg):
    x = ops.rmsnorm_reference(x, params["ln_f"], cfg.norm_eps)
    return _matmul(x, params["head"]).astype(jnp.float32)


def _step_counters(cfg, stats):
    """A step's dispatch counts summed over its expert layers (``stats``:
    each counter stacked over them; None for a model without experts)."""
    if stats is None:
        return {}
    counters = {f"moe_{k}": jnp.sum(v) for k, v in stats.items()
                if k != "tokens_per_expert_max"}
    counters["moe_tokens_per_expert_max"] = jnp.max(
        stats["tokens_per_expert_max"])
    counters["moe_layers"] = jnp.asarray(
        cfg.n_layers - cfg.dense_layers, jnp.int32)
    return counters


def latent_prefill_extend(params, tokens, cfg: Config, pool, prefix_tables,
                          prefix_lens, *, lengths=None):
    """The latent block's :func:`prefill_extend`: the unmatched tail on
    top of trie-matched prefix blocks.  The tail attends the gathered
    prefix rows (``position < prefix_lens``) and itself causally, all on
    the absorbed path (keys and values are expanded for neither).
    Returns ``(logits [B, vocab], rows [B, n_layers, T, latent_row])``.
    The scores are [B, H, T, prefix + T] at once: tails of a few
    thousand tokens, not whole long prompts."""
    dtype = cfg.compute_dtype
    b, t = tokens.shape
    bs = pool.shape[2]
    pcap = prefix_tables.shape[1] * bs
    plens = jnp.asarray(prefix_lens, jnp.int32)
    ptab = jnp.asarray(prefix_tables, jnp.int32)
    pos = plens[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    cos, sin = latent.rope_tables(cfg, pcap + t)
    pmask = jnp.broadcast_to(
        (jnp.arange(pcap)[None, :] < plens[:, None])[:, None, None, :],
        (b, 1, t, pcap))
    cmask = jnp.broadcast_to(
        (jnp.arange(t)[None, :] <= jnp.arange(t)[:, None])[None, None],
        (b, 1, t, t))
    mask = jnp.concatenate([pmask, cmask], axis=-1)
    x = params["embed"].astype(dtype)[tokens]

    def layer(p, x, index):
        y = ops.rmsnorm_reference(x, p["ln1"], cfg.norm_eps)
        q, rows = latent.project(p["attn"], y, cfg, cos, sin, pos)
        ctx = jnp.concatenate(
            [_paged_context(pool, index, ptab).astype(dtype), rows], axis=1)
        a = latent.attend_absorbed(p["attn"], q, ctx, mask, cfg)
        x, stats = _latent_ffn(p, x + _matmul(a, p["attn"]["wo"]), cfg,
                               index)
        return x, rows, stats

    x, rows, _ = _latent_layers(params, cfg, x, layer)
    return (_last_logits(params, x, lengths, cfg.norm_eps),
            rows.transpose(1, 0, 2, 3))


# ---------------------------------------------------------------------------
# The hybrid block: the latent block with a mixer PER LAYER
# (``cfg.linear_layers``): a layer named there mixes by the gated delta
# rule (models/linear_attention.py) and keeps a recurrent state and a short
# convolution history per SESSION; the others are latent attention as above
# and keep rows per token.  The FFN side (``_latent_ffn``) is the latent
# block's.  Weights are three stacks: ``dense_layers``, ``kda_layers`` (the
# expert layers that mix by the delta rule) and ``layers`` (the others).
# Consecutive layers of one stack run under one scan, a lone layer unrolled;
# a stack is reached by index inside the scan and never sliced (a slice of
# stacked weights in front of a loop is copied).
#
# Its cache has three entries (``DecodeFns.rows``): the latent pool over the
# latent layers only, and the state and the history over the delta-rule
# layers, ``[those layers, slots, ...]``, indexed by the step's row = slot.
# ---------------------------------------------------------------------------

def _hybrid_runs(cfg):
    """``[(stack, mixer, first index in the stack, first index among the
    layers of that mixer, count), ...]``: runs of consecutive layers that
    share a weight stack."""
    runs, in_stack, of_mixer = [], {}, {}
    for i, mixer in enumerate(cfg.mixers):
        stack = ("dense_layers" if i < cfg.dense_layers
                 else "kda_layers" if mixer == "kda" else "layers")
        if runs and runs[-1][0] == stack:
            runs[-1][-1] += 1
        else:
            runs.append([stack, mixer, in_stack.get(stack, 0),
                         of_mixer.get(mixer, 0), 1])
        in_stack[stack] = in_stack.get(stack, 0) + 1
        of_mixer[mixer] = of_mixer.get(mixer, 0) + 1
    return runs


def _hybrid_layers(params, cfg, carry, layer_fn):
    """Run ``layer_fn(p, carry, mixer, cache_index, index) -> (carry,
    out, stats)`` over every layer in order.  ``cache_index`` counts the
    layers of that mixer (its layer of the cache's entries); ``index`` is
    what ``_latent_ffn`` takes: the layer's place in its OWN stack, as if
    that stack followed the dense layers.  Returns ``(carry, {mixer: outs
    stacked over its layers}, expert statistics stacked over the expert
    layers or None)``."""
    outs, stats = {}, []
    for stack, mixer, first, cache_first, count in _hybrid_runs(cfg):
        layers, bank = params[stack], {}
        if "moe" in layers:
            bank = {k: layers["moe"][k].reshape(
                (-1,) + layers["moe"][k].shape[2:])
                for k in ("wg", "wu", "wd")}
            layers = dict(layers, moe={k: v for k, v in layers["moe"].items()
                                       if k not in bank})

        def body(carry, i, layers=layers, bank=bank, mixer=mixer,
                 first=first, cache_first=cache_first):
            p = jax.tree_util.tree_map(lambda a: a[first + i], layers)
            if bank:
                p = dict(p, moe=dict(p["moe"], **bank))
            carry, out, st = layer_fn(p, carry, mixer, cache_first + i,
                                      cfg.dense_layers + first + i)
            return carry, (out, st)

        if count == 1:
            carry, (out, st) = body(carry, 0)
            out, st = jax.tree_util.tree_map(lambda a: a[None], (out, st))
        else:
            carry, (out, st) = lax.scan(body, carry, jnp.arange(count))
        outs.setdefault(mixer, []).append(out)
        if st is not None:
            stats.append(st)
    cat = lambda xs: jax.tree_util.tree_map(
        lambda *a: jnp.concatenate(a), *xs)
    return (carry, {m: cat(o) for m, o in outs.items() if o[0] is not None},
            cat(stats) if stats else None)


def _hybrid_forward(params, tokens, cfg, attn_fn, lengths=None):
    """Whole-sequence pass (chunked delta rule, expanded latent path or,
    under a sparse selection, the absorbed path over each query's selected
    positions): ``(hidden [B, T, dim], (rows [latent layers, B, T,
    latent_row], (index keys [latent layers, B, T, index_head_dim],)
    state [kda layers, B, H, d, d], history [kda layers, B, K - 1,
    3*H*d]))``, state and history as they stand after ``lengths``
    tokens."""
    attn_fn = _default_attn_fn(cfg, attn_fn)
    with jax.named_scope("embed"):
        x = params["embed"].astype(cfg.compute_dtype)[tokens]
        if cfg.hc_mult:
            x = hc.widen(x, cfg.hc_mult)
    cos, sin = latent.rope_tables(cfg, tokens.shape[1])

    def layer(p, x, mixer, _cache_index, index):
        def mix(x):
            y = ops.rmsnorm_reference(x, p["ln1"], cfg.norm_eps)
            if mixer == "kda":
                a, state, conv = linear.mix_prefill(p["kda"], y, cfg,
                                                    lengths)
                return a, (state, conv)
            if cfg.index_topk:
                cq = latent.query_latent(p["attn"], y, cfg)
                q, rows = latent.project(p["attn"], y, cfg, cos, sin, cq=cq)
                a, keys = sparse.prefill_attention(
                    p["attn"], p["index"], q, rows, cq, y, cfg)
                return _matmul(a, p["attn"]["wo"]), (rows, keys)
            q, out = latent.project(p["attn"], y, cfg, cos, sin)
            return _matmul(latent.attend_expanded(p["attn"], q, out, cfg,
                                                  attn_fn),
                           p["attn"]["wo"]), out

        x, out = _residual(p, "hc_attn", x, cfg, mix)
        x, stats = _residual(p, "hc_ffn", x, cfg,
                             lambda h: _ffn_out(p, h, cfg, index))
        return x, out, stats

    x, outs, _ = _hybrid_layers(params, cfg, x, layer)
    if cfg.hc_mult:
        x = hc.narrow(x)
    kept = outs["latent"] if cfg.index_topk else (outs["latent"],)
    return x, tuple(kept) + outs["kda"]


def hybrid_prefill(params, tokens, cfg: Config, *, lengths=None,
                   attn_fn=None):
    """The hybrid block's :func:`prefill`: ``(logits [B, vocab] float32
    at each row's last real position, (rows [B, latent layers, T,
    latent_row], (index keys [B, latent layers, T, index_head_dim],)
    state [B, kda layers, H, d, d], history [B, kda layers, K - 1,
    3*H*d]))``: all the cache keeps of a sequence."""
    x, kept = _hybrid_forward(params, tokens, cfg, attn_fn, lengths)
    return (_last_logits(params, x, lengths, cfg.norm_eps),
            tuple(jnp.swapaxes(a, 0, 1) for a in kept))


def hybrid_decode_step_paged(params, tokens, cfg: Config, pools,
                             block_tables, lengths):
    """The hybrid block's :func:`decode_step_paged`: ``pools`` = (latent
    pool [num_blocks, latent layers, block_size, latent_row], (under a
    sparse selection the index keys [num_blocks, latent layers,
    block_size, index_head_dim],) state [kda layers, slots, H, d, d],
    history [kda layers, slots, K - 1, 3*H*d]), all loop carries written
    in place (the engine donates them).  One token a slot: a recurrent
    state cannot take back a window whose tail is rejected.  A free
    slot's state is stepped like any other (finite, and overwritten whole
    when the slot is next given out).  Returns ``(logits [S, 1, vocab]
    float32, pools, counters)``; under a sparse selection the counters
    add ``dsa_*`` (``sparse_attention.step_context``) summed over the
    latent layers."""
    dtype = cfg.compute_dtype
    s_slots, w = tokens.shape
    if w != 1:
        raise ValueError(
            f"a layout with per-session state steps one token a slot, "
            f"not a window of {w}")
    bs = pools[0].shape[2]
    cap = block_tables.shape[1] * bs
    lengths = jnp.asarray(lengths, jnp.int32)
    tables = jnp.asarray(block_tables, jnp.int32)
    pos = lengths[:, None]
    posc = jnp.clip(pos, 0, cap - 1)
    wblk = jnp.where(pos < cap,
                     jnp.take_along_axis(tables, posc // bs, axis=1),
                     0).reshape(-1)
    woff = (posc % bs).reshape(-1)
    kv_mask = (jnp.arange(cap)[None, None, None, :]
               <= pos[:, None, :, None])                 # [S, 1, 1, cap]
    x = params["embed"].astype(dtype)[tokens]            # [S, 1, dim]
    if cfg.hc_mult:
        x = hc.widen(x, cfg.hc_mult)
    cos, sin = latent.rope_tables(cfg, cap)
    live = (lengths > 0)[:, None]

    def mix(p, y, mixer, at, pools, dsa):
        """The mixer of one layer: ``(out, (pools, dsa))``."""
        if mixer == "kda":
            state, conv = pools[-2:]
            a, s_new, c_new = linear.mix_step(p["kda"], y, cfg,
                                              state[at], conv[at])
            with jax.named_scope("write_state"):
                state = state.at[at].set(s_new)
                conv = conv.at[at].set(c_new)
            return a, (pools[:-2] + (state, conv), dsa)
        pool = pools[0]
        cq = latent.query_latent(p["attn"], y, cfg) if cfg.index_topk \
            else None
        q, rows = latent.project(p["attn"], y, cfg, cos, sin, posc, cq=cq)
        with jax.named_scope("write_kv"):
            pool = pool.at[wblk, at, woff].set(
                rows.reshape(-1, rows.shape[-1]).astype(pool.dtype))
        if cfg.index_topk:
            ipool = pools[1]
            keys = sparse.index_keys(p["index"], y, cfg, posc)
            with jax.named_scope("write_kv"):
                ipool = ipool.at[wblk, at, woff].set(
                    keys.reshape(-1, keys.shape[-1]).astype(ipool.dtype))
            ctx, mask, counts = sparse.step_context(
                p["index"], cq, y, cfg, pool, ipool, at, tables, posc,
                live[:, 0])
            dsa = {k: dsa[k] + v for k, v in counts.items()}
            pools = (pool, ipool) + pools[2:]
        else:
            ctx, mask = _paged_context(pool, at, tables), kv_mask
            pools = (pool,) + pools[1:]
        a = _matmul(latent.attend_absorbed(p["attn"], q, ctx, mask, cfg),
                    p["attn"]["wo"])
        return a, (pools, dsa)

    def layer(p, carry, mixer, at, index):
        x, pools, dsa = carry
        x, (pools, dsa) = _residual(
            p, "hc_attn", x, cfg,
            lambda x: mix(p, ops.rmsnorm_reference(x, p["ln1"],
                                                   cfg.norm_eps),
                          mixer, at, pools, dsa))
        x, stats = _residual(p, "hc_ffn", x, cfg,
                             lambda h: _ffn_out(p, h, cfg, index, live))
        return (x, pools, dsa), None, stats

    dsa = {name: jnp.zeros((), jnp.int32) for name in sparse.COUNTERS} \
        if cfg.index_topk else {}
    (x, pools, dsa), _, stats = _hybrid_layers(
        params, cfg, (x, tuple(pools), dsa), layer)
    if cfg.hc_mult:
        x = hc.narrow(x)
    return (_step_logits(params, x, cfg), pools,
            dict(_step_counters(cfg, stats), **dsa))


# ---------------------------------------------------------------------------
# The seam between a model and the serving engine.
# ---------------------------------------------------------------------------

class CacheEntry(typing.NamedTuple):
    """One entry of a model's cache layout (``DecodeFns.rows``).

    ``shape`` is what ONE layer keeps of one sequence.  With a ``None``
    in it the entry is ROWS PER TOKEN, the ``None`` standing for the token
    axis: a paged pool ``[num_blocks, layers, *shape]`` with ``block_size``
    for the None, a prefill handing back ``[B, layers, *shape]`` with T
    for it.  Without one it is PER-SESSION STATE of a fixed size,
    ``[layers, slots, *shape]``, a prefill handing back ``[B, layers,
    *shape]``: written whole when a session is admitted, owned with the
    slot, indexed by the step's row, never paged and never shared.
    ``layers`` counts the layers that HAVE such an entry (a model's mixers
    may differ by layer); ``dtype`` None means the cache's own."""
    name: str
    shape: tuple
    layers: int
    dtype: object = None

    @property
    def paged(self):
        return None in self.shape


@dataclasses.dataclass(frozen=True)
class DecodeFns:
    """What ``serving/decode`` needs of a model, and all it knows of it.

    ``rows``: the cache's layout, a tuple of :class:`CacheEntry` — rows
    per token (paged pools) and per-session state, each over the layers
    that have it.  ``pools`` below is the tuple of the cache's arrays in
    that order, ``rows`` of a prefill the tuple of what it hands back.

    ``prefill(params, tokens, lengths) -> (logits, rows)``
    ``prefill_extend(params, tokens, pools, prefix_tables, prefix_lens,
    lengths) -> (logits, rows)``, or None: a layout with state has none
    (a prefix's rows can be mapped, the state at its end is not kept)
    ``decode_step_paged(params, tokens [S, W], pools, block_tables,
    lengths) -> (logits, pools, counters)``; row s of a state entry is
    slot s's; ``counters`` is a (possibly empty) dict of scalars: the
    engine sums each over steps (keeps the maximum of a name ending in
    ``_max``) and ``summarize(totals)`` turns the totals into what
    ``stats()`` shows.
    Every layout's step writes its pools in place, and the engine
    donates them to it (the cache's insert too): a second copy of a pool
    never exists.
    ``resident(params) -> params``: the tree as the engine should HOLD it
    (:func:`_resident`): the leaves these programs use only through a cast
    to the compute type, cast once; every program above gives the same
    bits on either tree.  Default: the tree as given.
    ``span_counters``: the step counters the engine also writes onto its
    iteration's span (a capture then has them step by step).
    ``prefill_selected(lengths) -> int`` or None: the positions a prefill
    of prompts of ``lengths`` reads, summed over its queries and layers
    (a sparse selection's; written onto the prefill's span).
    """
    rows: tuple
    prefill: object
    prefill_extend: object
    decode_step_paged: object
    summarize: object = None
    resident: object = lambda params: params
    span_counters: tuple = ()
    prefill_selected: object = None

    @property
    def has_state(self):
        return any(not entry.paged for entry in self.rows)

    @property
    def donate(self):
        """True for every layout (the engine donates without asking);
        read by the benchmark's replay, ``benchmark/runners/
        serve_model.py``."""
        return True


def _summarize_moe(totals):
    """``stats()["moe"]`` from the step counters' totals."""
    touched = totals.get("moe_experts_touched", 0)
    return {"moe": {
        "picks_held": round(totals.get("moe_picks_held", 0)
                            / max(totals.get("moe_picks", 0), 1), 6),
        "experts_touched": round(
            touched / max(totals.get("moe_layers", 0), 1), 4),
        "tokens_per_expert_mean": round(
            totals.get("moe_rows_held", 0) / max(touched, 1), 4),
        "tokens_per_expert_max": int(
            totals.get("moe_tokens_per_expert_max", 0)),
        "dropped": int(totals.get("moe_dropped", 0)),
    }}


def _summarize_sparse(totals):
    """``stats()["moe"]`` and ``stats()["dsa"]``: a sparse selection's
    step counters as their totals (positions read, positions up to the
    queries, whole groups scored; summed over the latent layers)."""
    return dict(_summarize_moe(totals), dsa={
        name[len("dsa_"):]: int(totals.get(name, 0))
        for name in sparse.COUNTERS})


# The leaves (by their own name in the tree) that the three serving
# programs use ONLY as ``.astype(cfg.compute_dtype)``: the operands of
# ``_matmul`` / ``ragged_dot`` / the absorbed up-projections, the router's
# weight, the embedding table.  Not among them: every norm gain (multiplied
# in float32, ``ops.rmsnorm_reference``) and the router's bias (added in
# float32).  A leaf that gains another use leaves its set.
_CLASSIC_CAST = frozenset({"embed", "head", "wqkv", "wo", "w1", "w2"})
_LATENT_CAST = frozenset({
    "embed", "head", "wq", "wkva", "wkvb", "wo", "wg", "wu", "wd",
    "router", "shared_wg", "shared_wu", "shared_wd",
    # the delta-rule mixer's matrices (its ``conv``, ``a_log``, ``dt_bias``
    # and ``o_norm`` enter float32 sums)
    "wqkv", "wfa", "wfb", "wga", "wgb", "wbeta",
    # a low-rank query, the sparse selection's indexer and the
    # hyper-connections' maps (their gains, biases and ``alpha`` do not)
    "wqa", "wqb", "wiq", "wik", "wiw", "phi"})


def _resident(params, names, dtype):
    """``params`` with each leaf called one of ``names`` in ``dtype``:
    what a program that casts those leaves on every use may be handed
    instead, for the same bits.  A leaf that already has the type (and
    every other leaf) is handed back AS IT IS, the same object — decided
    here, outside any jit, because a jitted identity copies its input.
    The others go up and are cast one at a time, by the device's own
    convert (the one the programs made), so the device never holds more
    of the wide tree than one leaf."""
    def leaf(path, x):
        if getattr(path[-1], "key", None) not in names or x.dtype == dtype:
            return x
        return jax.block_until_ready(jnp.asarray(x).astype(dtype))

    return jax.tree_util.tree_map_with_path(leaf, params)


def _decode_fns(cfg):
    resident = functools.partial(
        _resident, names=_CLASSIC_CAST if cfg.classic else _LATENT_CAST,
        dtype=cfg.compute_dtype)
    if cfg.classic:
        # a token's row whole, [heads, head_dim]: what the block computes
        # and what the step writes in place and gathers
        per_head = CacheEntry("k", (None, cfg.n_heads, cfg.head_dim),
                              cfg.n_layers)

        def prefill_fn(p, toks, lens):
            logits, k, v = prefill(p, toks, cfg, lengths=lens)
            return logits, (k, v)

        def extend_fn(p, toks, pools, ptab, plens, lens):
            logits, k, v = prefill_extend(p, toks, cfg, *pools, ptab, plens,
                                          lengths=lens)
            return logits, (k, v)

        def step_paged_fn(p, toks, pools, tables, lens):
            logits, k, v = decode_step_paged(p, toks, cfg, *pools, tables,
                                             lens)
            return logits, (k, v), {}

        return DecodeFns(rows=(per_head, per_head._replace(name="v")),
                         prefill=prefill_fn, prefill_extend=extend_fn,
                         decode_step_paged=step_paged_fn, resident=resident)

    summarize = _summarize_moe if cfg.n_experts else None
    if cfg.linear_layers:
        n_kda = len(cfg.linear_layers)
        state, conv = linear.state_shapes(cfg)

        def prefill_fn(p, toks, lens):
            return hybrid_prefill(p, toks, cfg, lengths=lens)

        def step_paged_fn(p, toks, pools, tables, lens):
            return hybrid_decode_step_paged(p, toks, cfg, pools, tables,
                                            lens)

        n_latent = cfg.n_layers - n_kda
        rows = (CacheEntry("kv", (None, cfg.latent_row), n_latent),)
        sparse_kw = {}
        if cfg.index_topk:
            # the indexer's key of every token, beside its latent row
            rows += (CacheEntry("index", (None, cfg.index_head_dim),
                                n_latent),)
            sparse_kw = dict(
                summarize=_summarize_sparse, span_counters=sparse.COUNTERS,
                prefill_selected=lambda lens: n_latent
                * sparse.selected_positions(lens, cfg))
        return DecodeFns(
            rows=rows + (CacheEntry("state", state, n_kda,
                                    jnp.dtype(cfg.state_dtype)),
                         CacheEntry("conv", conv, n_kda)),
            prefill=prefill_fn, prefill_extend=None,
            decode_step_paged=step_paged_fn,
            **dict(dict(summarize=summarize, resident=resident),
                   **sparse_kw))

    def prefill_fn(p, toks, lens):
        logits, rows = latent_prefill(p, toks, cfg, lengths=lens)
        return logits, (rows,)

    def extend_fn(p, toks, pools, ptab, plens, lens):
        logits, rows = latent_prefill_extend(p, toks, cfg, pools[0], ptab,
                                             plens, lengths=lens)
        return logits, (rows,)

    def step_paged_fn(p, toks, pools, tables, lens):
        logits, pool, counters = latent_decode_step_paged(
            p, toks, cfg, pools[0], tables, lens)
        return logits, (pool,), counters

    return DecodeFns(rows=(CacheEntry("kv", (None, cfg.latent_row),
                                      cfg.n_layers),),
                     prefill=prefill_fn, prefill_extend=extend_fn,
                     decode_step_paged=step_paged_fn, summarize=summarize,
                     resident=resident)


@functools.lru_cache(maxsize=8)
def _jitted_apply(cfg, attn_fn):
    """One jit of ``apply`` per (config, attention) for the padded
    oracle: a fresh wrapper per call would compile again each time."""
    return jax.jit(functools.partial(apply, cfg=cfg, attn_fn=attn_fn))


def greedy_decode_reference(params, prompt, cfg: Config, *, max_tokens,
                            eos_id=None, attn_fn=None, pad_to=None):
    """Full-recompute greedy decode — the KV-cache parity oracle
    (tests/test_decode.py): each step re-runs ``apply`` on the whole
    growing sequence and argmaxes the final position.  O(T²) per token.

    Every new length is a new shape, hence a new compile of the whole
    forward: fine for test-sized models, minutes at real widths.
    ``pad_to`` right-pads each sequence to that one length and runs the
    forward under one jit instead — under a causal mask the logits at
    the last REAL position do not see the padding, so the tokens are
    the same and the oracle compiles once."""
    toks = [int(t) for t in prompt]
    if pad_to is None:
        def last_logits(seq):
            return apply(params, jnp.asarray([seq], jnp.int32), cfg,
                         attn_fn=attn_fn)[0, -1]
    else:
        if len(toks) + int(max_tokens) - 1 > pad_to:
            raise ValueError(
                f"pad_to={pad_to} is shorter than the longest sequence "
                f"({len(toks) + int(max_tokens) - 1})")
        fwd = _jitted_apply(cfg, attn_fn)

        def last_logits(seq):
            padded = jnp.asarray([seq + [0] * (pad_to - len(seq))],
                                 jnp.int32)
            return fwd(params, padded)[0, len(seq) - 1]
    out = []
    for _ in range(int(max_tokens)):
        nxt = int(jnp.argmax(last_logits(toks)))
        out.append(nxt)
        toks.append(nxt)
        if eos_id is not None and nxt == int(eos_id):
            break
    return out


def zigzag_lm_batch(tokens, perm):
    """Prepare a contiguous-order LM batch for zigzag training:
    returns ``(tokens_p, labels_p, positions)`` where ``tokens_p`` is
    the zigzag-permuted sequence, ``labels_p`` the next token of each
    position in ORIGINAL order (-1 at the final global position), and
    ``positions`` the global rope positions — feed to ``loss_fn(...,
    labels=labels_p, positions=positions)`` with a zigzag ``attn_fn``.
    """
    # roll + where, NOT concatenate(tokens[:, 1:], -1): under the SPMD
    # partitioner (seq-sharded tokens, jitted) the slice+concat lowering
    # summed the two seq shards' contributions — every label came back
    # exactly doubled, overran the vocab, and take_along_axis's
    # out-of-bounds fill turned the loss into NaN.  roll keeps the shift
    # a collective-permute, which partitions correctly.
    s = tokens.shape[1]
    labels = jnp.where(jnp.arange(s) == s - 1, jnp.array(-1, tokens.dtype),
                       jnp.roll(tokens, -1, axis=1))
    return tokens[:, perm], labels[:, perm], jnp.asarray(perm, jnp.int32)
