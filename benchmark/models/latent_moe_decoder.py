"""Model adapter ``latent_moe_decoder``: the program's
``models/transformer.py`` latent block (latent attention, leading dense
SwiGLU layers, expert layers with a shared expert) at a published
model's sizes, SERVED through ``serving.Server`` + ``DecodeSpec``.

The configuration file keeps the published key names; this file is the
one place that maps them onto the program's ``transformer.Config``.  A
configuration is the chip's share of a deployment (``deployment`` in the
file): ``num_experts`` experts HELD of ``num_experts_published``, the
router at ``router_width``, a slice of the vocabulary.  The share keeps
to floors, checked here on the sizes a chip run uses: one leading dense
layer + at least four expert layers, at least 8 experts held, at least an
eighth of the vocabulary.
"""

from benchmark.lib.manifest import rehearsed

MIN_EXPERT_LAYERS = 4
MIN_EXPERTS_HELD = 8
MIN_VOCAB_SHARE = 8  # an eighth


def sizes(ctx):
    cfg = rehearsed(ctx["config"], ctx["rehearse"])
    if not ctx["rehearse"]:
        check_floors(cfg)
    return cfg


def check_floors(cfg):
    dense = int(cfg["first_k_dense_replace"])
    if cfg["num_hidden_layers"] < dense + MIN_EXPERT_LAYERS:
        raise ValueError(
            f"num_hidden_layers {cfg['num_hidden_layers']} is below the "
            f"floor: {dense} leading dense + {MIN_EXPERT_LAYERS} expert "
            "layers")
    if cfg["num_experts"] < MIN_EXPERTS_HELD:
        raise ValueError(f"num_experts {cfg['num_experts']} held is below "
                         f"the floor of {MIN_EXPERTS_HELD}")
    if cfg["vocab_size"] * MIN_VOCAB_SHARE < cfg["vocab_size_published"]:
        raise ValueError(
            f"vocab_size {cfg['vocab_size']} is less than an eighth of the "
            f"published {cfg['vocab_size_published']}")


def model_config(cfg):
    """The program's ``transformer.Config`` at the configuration's sizes."""
    from tensorflowonspark_tpu import ops
    from tensorflowonspark_tpu.models import transformer

    sc = cfg["rope_scaling"]
    if sc["type"] != "deepseek_yarn":
        raise ValueError(f"rope_scaling type {sc['type']!r}")
    rope = cfg["qk_rope_head_dim"]
    if cfg["q_head_dim"] != cfg["qk_nope_head_dim"] + rope \
            or cfg["head_dim"] != cfg["kv_lora_rank"] + rope:
        raise ValueError("q_head_dim / head_dim do not add up")
    return transformer.Config(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        max_seq=cfg["max_position_embeddings"],
        rope_base=float(cfg["rope_theta"]),
        dtype=cfg["compute_dtype"], param_dtype=cfg["param_dtype"],
        attn_impl="flash", attn_kind="latent",
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        qk_norm=bool(cfg["use_qk_norm"]),
        rope_scaling=ops.YarnScaling(
            factor=float(sc["factor"]),
            original_max_seq=int(sc["original_max_position_embeddings"]),
            beta_fast=float(sc["beta_fast"]),
            beta_slow=float(sc["beta_slow"]), mscale=float(sc["mscale"]),
            mscale_all_dim=float(sc["mscale_all_dim"])),
        ffn_kind="swiglu", ffn_dim=cfg["intermediate_size"],
        n_dense_layers=cfg["first_k_dense_replace"],
        n_experts=cfg["router_width"], n_experts_held=cfg["num_experts"],
        expert_offset=int(cfg.get("expert_offset", 0)),
        experts_per_token=cfg["num_experts_per_tok"],
        expert_dim=cfg["moe_intermediate_size"],
        n_shared_experts=cfg["num_shared_experts"],
        routed_scale=float(cfg["routed_scaling_factor"]))


def held(cfg):
    """``(first expert held, how many)`` for the reference."""
    return int(cfg.get("expert_offset", 0)), int(cfg["num_experts"])


def init_params(model, seed):
    """Weights from the seed, by the program's own ``init``, in the type
    the engine holds them (``param_dtype``)."""
    import jax

    from tensorflowonspark_tpu.models import transformer

    return jax.jit(lambda key: transformer.init(key, model))(
        jax.random.PRNGKey(seed))


def decode_spec(cfg, mix):
    """``(transformer.Config, serving.DecodeSpec)``: the pool is the
    sentinel plus the live set (slots x blocks for ``max_position_
    embeddings``), one prefill program pads to at most the mix's
    ``prefill_tokens``."""
    from tensorflowonspark_tpu import serving

    model = model_config(cfg)
    bs, slots = int(mix["block_size"]), int(mix["slots"])
    blocks_per_slot = -(-model.max_seq // bs)
    return model, serving.DecodeSpec(
        model, slots=slots, block_size=bs,
        num_blocks=1 + slots * blocks_per_slot,
        max_tokens=int(mix["max_tokens"]),
        prefill_tokens=mix.get("prefill_tokens"))


def reference_forward(params, tokens, cfg, **kw):
    """The plain reference over the same parameter tree: ``(logits,
    routing margins [expert layers, T])``; ``round_to``, ``at`` and
    ``q_block`` as the reference takes them."""
    from benchmark.reference import latent_moe_decoder as ref

    return ref.forward(params, tokens, cfg, held(cfg), **kw)


def reference(params, cfg, q_block, at_width):
    """``forward(seq, at, round_to=None) -> (logits [len(at), V], routing
    margins [expert layers, T] or None)`` as numpy, in float32
    ``highest``: :func:`reference_forward` over ``seq`` padded on the
    right to one of TWO lengths (under a causal mask no row at ``at``
    sees the padding, and a token's experts do not depend on its
    neighbours) with ``at`` padded to ``at_width``.  The reference runs
    operation by operation and every new shape compiles each of them
    again: with a length per request that took 25-50 s a request on the
    chip, with these shapes 2-6 s (PERF.md, PR27)."""
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
