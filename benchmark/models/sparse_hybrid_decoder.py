"""Model adapter ``sparse_hybrid_decoder``: the program's
``models/transformer.py`` hybrid block with a learned sparse selection
and hyper-connected residual streams (a mixer per layer: Kimi Delta
Attention with a bounded decay, or latent attention without rotary that
reads the positions a DeepSeek sparse-attention indexer selects; four
residual streams through mHC; one leading dense SwiGLU layer, expert
layers with a shared expert, every SwiGLU clamped) at a published model's
sizes, SERVED through ``serving.Server`` + ``DecodeSpec``.

The configuration file keeps the published key names (``glm5_next_text``:
``layer_types`` and ``linear_attn_config``'s lists 0-based,
``n_routed_experts``); this file is the one place that maps them onto the
program's ``transformer.Config``.  A configuration is the chip's share of
a deployment (experts HELD of ``n_routed_experts_published``, the router
at ``router_width``, a slice of the vocabulary), cut in depth to the
leading dense layer and whole PERIODS of the pattern after it.  The
floors, checked on the sizes a chip run uses: whole periods, at least
four layers after the dense one, at least 8 experts held, at least an
eighth of the vocabulary.
"""

from benchmark.lib.manifest import rehearsed
from benchmark.models.latent_moe_decoder import (  # the same share
    MIN_EXPERT_LAYERS, MIN_EXPERTS_HELD, MIN_VOCAB_SHARE, init_params)

__all__ = ["sizes", "decode_spec", "init_params", "reference", "held"]

MIXERS = {"linear_attention": "kda", "deepseek_sparse_attention": "dsa"}


def sizes(ctx):
    cfg = rehearsed(ctx["config"], ctx["rehearse"])
    if not ctx["rehearse"]:
        check_floors(cfg)
    return cfg


def period(cfg):
    """The pattern's period after the leading dense (KDA) layers: whole
    periods of one DSA layer followed by KDA layers.  Raises where
    ``layer_types``, ``linear_attn_config``'s two lists and the depth do
    not describe that pattern."""
    depth, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    lin = cfg["linear_attn_config"]
    kinds = [MIXERS[k] for k in cfg["layer_types"]]
    dsa = [i for i, k in enumerate(kinds) if k == "dsa"]
    n = (depth - dense) // len(dsa) if dsa else 0
    if len(kinds) != depth or n < 2 \
            or dsa != list(range(dense, depth, n)) \
            or list(lin["full_attn_layers"]) != dsa \
            or list(lin["kda_layers"]) != [i for i in range(depth)
                                           if kinds[i] == "kda"]:
        raise ValueError(
            f"layer_types / linear_attn_config are not {dense} leading "
            f"layers and a pattern of one DSA layer in every {n or '?'} "
            f"over {depth} layers: {cfg['layer_types']}, {lin}")
    return n


def check_floors(cfg):
    dense, depth = int(cfg["first_k_dense_replace"]), cfg["num_hidden_layers"]
    n = period(cfg)
    if (depth - dense) % n:
        raise ValueError(
            f"{depth - dense} layers after the dense ones are not whole "
            f"periods of {n} (1 DSA to {n - 1} KDA)")
    if depth < dense + MIN_EXPERT_LAYERS:
        raise ValueError(
            f"num_hidden_layers {depth} is below the floor: {dense} leading "
            f"dense + {MIN_EXPERT_LAYERS} layers after them")
    if cfg["n_routed_experts"] < MIN_EXPERTS_HELD:
        raise ValueError(f"n_routed_experts {cfg['n_routed_experts']} held "
                         f"is below the floor of {MIN_EXPERTS_HELD}")
    if cfg["vocab_size"] * MIN_VOCAB_SHARE < cfg["vocab_size_published"]:
        raise ValueError(
            f"vocab_size {cfg['vocab_size']} is less than an eighth of the "
            f"published {cfg['vocab_size_published']}")


def model_config(cfg):
    """The program's ``transformer.Config`` at the configuration's sizes."""
    from tensorflowonspark_tpu.models import transformer

    period(cfg)
    lin = cfg["linear_attn_config"]
    unread = {
        "hidden_act": "silu", "scoring_func": "sigmoid",
        "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
        "norm_topk_prob": True, "mla_use_nope": True, "mhc": True,
        "qk_rope_head_dim": 0, "tie_word_embeddings": False,
        "attention_bias": False, "num_nextn_predict_layers": 0,
        "index_kpool_always_select_tail": True,
        "index_kpool_compress": True, "indexer_rope_interleave": True,
        "mlp_layer_types": ["dense"] * cfg["first_k_dense_replace"]
        + ["sparse"] * (cfg["num_hidden_layers"]
                        - cfg["first_k_dense_replace"]),
        "indexer_types": ["full"] * cfg["num_hidden_layers"]}
    for key, want in unread.items():
        if cfg[key] != want:
            raise ValueError(f"{key} = {cfg[key]!r}: this adapter maps the "
                             f"published {want!r} only")
    return transformer.Config(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        max_seq=cfg["max_position_embeddings"],
        rope_base=float(cfg["index_rope_theta"]),
        dtype=cfg["compute_dtype"], param_dtype=cfg["param_dtype"],
        state_dtype=cfg["state_dtype"], norm_eps=float(cfg["rms_norm_eps"]),
        attn_impl="flash", attn_kind="latent", qk_rotary=False,
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        index_heads=cfg["index_n_heads"],
        index_head_dim=cfg["index_head_dim"],
        index_rope_dim=cfg["index_rope_dim"],
        index_topk=cfg["index_topk"], index_kpool=cfg["index_kpool"],
        hc_mult=cfg["hc_mult"], hc_sinkhorn_iters=cfg["hc_sinkhorn_iters"],
        hc_eps=float(cfg["hc_eps"]),
        linear_layers=tuple(lin["kda_layers"]),
        linear_heads=lin["num_heads"], linear_head_dim=lin["head_dim"],
        linear_conv=lin["short_conv_kernel_size"],
        linear_rank=cfg["kda_low_rank"],
        linear_gate_lower_bound=float(lin["gate_lower_bound"]),
        ffn_kind="swiglu", ffn_dim=cfg["intermediate_size"],
        swiglu_limit=float(cfg["swiglu_limit"]),
        n_dense_layers=cfg["first_k_dense_replace"],
        n_experts=cfg["router_width"], n_experts_held=cfg["n_routed_experts"],
        expert_offset=int(cfg.get("expert_offset", 0)),
        experts_per_token=cfg["num_experts_per_tok"],
        expert_dim=cfg["moe_intermediate_size"],
        n_shared_experts=cfg["n_shared_experts"],
        routed_scale=float(cfg["routed_scaling_factor"]))


def held(cfg):
    """``(first expert held, how many)`` for the reference."""
    return int(cfg.get("expert_offset", 0)), int(cfg["n_routed_experts"])


def decode_spec(cfg, mix):
    """``(transformer.Config, serving.DecodeSpec)``: the pools are the
    sentinel plus the live set (slots x blocks for ``max_position_
    embeddings``), one prefill program pads to at most the mix's
    ``prefill_tokens`` (a longer prompt prefills alone); no trie and no
    draft (the layout has state)."""
    from tensorflowonspark_tpu import serving

    model = model_config(cfg)
    bs, slots = int(mix["block_size"]), int(mix["slots"])
    return model, serving.DecodeSpec(
        model, slots=slots, block_size=bs,
        num_blocks=1 + slots * -(-model.max_seq // bs),
        max_tokens=int(mix["max_tokens"]),
        prefill_tokens=mix.get("prefill_tokens"))


def reference_forward(params, tokens, cfg, **kw):
    """The plain reference over the same parameter tree: ``(logits,
    routing margins [expert layers, T])`` (and the selections, with
    ``selections=True``)."""
    from benchmark.reference import sparse_hybrid_decoder as ref

    return ref.forward(params, tokens, cfg, held(cfg), **kw)


def reference(params, cfg, q_block, at_width):
    """``forward(seq, at, round_to=None) -> (logits [len(at), V], routing
    margins or None)`` as numpy, in float32 ``highest``: ``seq`` padded on
    the right to whole chunks of the reference's rows (its small programs
    compile once whatever the length, and under causal attention, a
    causal selection and a recurrence no row at ``at`` sees the padding),
    ``at`` padded to ``at_width``."""
    import jax
    import numpy as np

    from benchmark.reference import sparse_hybrid_decoder as ref

    rows = min(ref.ROWS, int(cfg["max_position_embeddings"]))

    def forward(seq, at, round_to=None):
        n = -(-len(seq) // rows) * rows
        toks = np.zeros((n,), np.int32)
        toks[:len(seq)] = seq
        where = np.full((max(at_width, len(at)),), at[-1], np.int32)
        where[:len(at)] = at
        with jax.default_matmul_precision("highest"):
            logits, margins = reference_forward(
                params, toks, cfg, at=where, q_block=q_block, rows=rows,
                round_to=round_to)
        return (np.asarray(logits)[:len(at)],
                None if margins is None else np.asarray(margins))

    return forward
