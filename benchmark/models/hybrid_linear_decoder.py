"""Model adapter ``hybrid_linear_decoder``: the program's
``models/transformer.py`` hybrid block (a mixer per layer: Kimi Delta
Attention or latent attention without rotary; one leading dense SwiGLU
layer, expert layers with a shared expert) at a published model's sizes,
SERVED through ``serving.Server`` + ``DecodeSpec``.

The configuration file keeps the published key names; this file is the
one place that maps them onto the program's ``transformer.Config``.  A
configuration is the chip's share of a deployment, as
``models/latent_moe_decoder.py`` has it (experts HELD of
``num_experts_published``, the router at ``router_width``, a slice of the
vocabulary), cut in depth to whole PERIODS of the layer pattern:
``linear_attn_config``'s two 1-based lists say which layers mix by KDA and
which by latent attention, every ``period``-th layer being latent.  The
floors, checked on the sizes a chip run uses: whole periods only, one
leading dense layer + at least four layers after it, at least 8 experts
held, at least an eighth of the vocabulary.
"""

from benchmark.lib.manifest import rehearsed
from benchmark.models.latent_moe_decoder import (  # the same share
    MIN_EXPERT_LAYERS, MIN_EXPERTS_HELD, MIN_VOCAB_SHARE, held, init_params)

__all__ = ["sizes", "decode_spec", "init_params", "reference", "held"]


def sizes(ctx):
    cfg = rehearsed(ctx["config"], ctx["rehearse"])
    if not ctx["rehearse"]:
        check_floors(cfg)
    return cfg


def period(cfg):
    """The layer pattern's period: every ``period``-th layer (1-based) is
    latent attention, the others KDA.  Raises where the two lists are not
    that pattern over ``num_hidden_layers`` layers."""
    lin, depth = cfg["linear_attn_config"], cfg["num_hidden_layers"]
    full = list(lin["full_attn_layers"])
    n = full[0] if full else 0
    if n < 2 or full != list(range(n, depth + 1, n)) \
            or list(lin["kda_layers"]) != [i for i in range(1, depth + 1)
                                           if i % n]:
        raise ValueError(
            f"linear_attn_config's lists are not a pattern of one latent "
            f"layer in every {n or '?'} over {depth} layers: "
            f"{lin['full_attn_layers']}, {lin['kda_layers']}")
    return n


def check_floors(cfg):
    dense, depth = int(cfg["first_k_dense_replace"]), cfg["num_hidden_layers"]
    n = period(cfg)
    if depth % n:
        raise ValueError(
            f"num_hidden_layers {depth} is not whole periods of {n} layers "
            f"({n - 1} KDA to 1 latent): a cut in depth keeps the published "
            "ratio of the two mixers")
    if depth < dense + MIN_EXPERT_LAYERS:
        raise ValueError(
            f"num_hidden_layers {depth} is below the floor: {dense} leading "
            f"dense + {MIN_EXPERT_LAYERS} expert layers")
    if cfg["num_experts"] < MIN_EXPERTS_HELD:
        raise ValueError(f"num_experts {cfg['num_experts']} held is below "
                         f"the floor of {MIN_EXPERTS_HELD}")
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
        "rope_scaling": None, "q_lora_rank": None, "hidden_act": "silu",
        "moe_router_activation_func": "sigmoid", "moe_renormalize": True,
        "moe_layer_freq": 1, "num_expert_group": 1, "topk_group": 1,
        "mla_use_nope": True, "tie_word_embeddings": False,
        "num_nextn_predict_layers": 0}
    for key, want in unread.items():
        if cfg[key] != want:
            raise ValueError(f"{key} = {cfg[key]!r}: this adapter maps the "
                             f"published {want!r} only")
    return transformer.Config(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        max_seq=cfg["max_position_embeddings"],
        dtype=cfg["compute_dtype"], param_dtype=cfg["param_dtype"],
        state_dtype=cfg["state_dtype"], norm_eps=float(cfg["rms_norm_eps"]),
        attn_impl="flash", attn_kind="latent", qk_rotary=False,
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        linear_layers=tuple(i - 1 for i in lin["kda_layers"]),
        linear_heads=lin["num_heads"], linear_head_dim=lin["head_dim"],
        linear_conv=lin["short_conv_kernel_size"],
        linear_rank=cfg["kda_low_rank"],
        ffn_kind="swiglu", ffn_dim=cfg["intermediate_size"],
        n_dense_layers=cfg["first_k_dense_replace"],
        n_experts=cfg["router_width"], n_experts_held=cfg["num_experts"],
        expert_offset=int(cfg.get("expert_offset", 0)),
        experts_per_token=cfg["num_experts_per_token"],
        expert_dim=cfg["moe_intermediate_size"],
        n_shared_experts=cfg["num_shared_experts"],
        routed_scale=float(cfg["routed_scaling_factor"]))


def decode_spec(cfg, mix):
    """``(transformer.Config, serving.DecodeSpec)``: the pool is the
    sentinel plus the live set (slots x blocks for ``max_position_
    embeddings``), one prefill program pads to at most the mix's
    ``prefill_tokens``; no trie and no draft (the layout has state)."""
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
    routing margins [expert layers, T])``."""
    from benchmark.reference import hybrid_linear_decoder as ref

    return ref.forward(params, tokens, cfg, held(cfg), **kw)


def reference(params, cfg, q_block, at_width):
    """``forward(seq, at, round_to=None) -> (logits [len(at), V], routing
    margins or None)`` as numpy, in float32 ``highest``, ``seq`` padded on
    the right to one of TWO lengths and ``at`` to ``at_width``, as
    ``models/latent_moe_decoder.reference`` (a recurrence is causal too:
    no row at ``at`` sees the padding)."""
    import jax
    import numpy as np

    longest = int(cfg["max_position_embeddings"])
    pads = (1 << (longest.bit_length() - 2), longest)

    def forward(seq, at, round_to=None):
        n = next(p for p in pads if p >= len(seq))
        toks = np.zeros((n,), np.int32)
        toks[:len(seq)] = seq
        where = np.full((max(at_width, len(at)),), at[-1], np.int32)
        where[:len(at)] = at
        with jax.default_matmul_precision("highest"):
            logits, margins = reference_forward(
                params, toks, cfg, at=where, q_block=q_block,
                round_to=round_to)
        return (np.asarray(logits)[:len(at)],
                None if margins is None else np.asarray(margins))

    return forward
