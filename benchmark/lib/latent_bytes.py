"""Bytes a latent-attention expert model's decode step has to read once,
from the configuration file's own sizes (published key names): the
roofline's numerator for ``latent_step_roofline_frac``.

A step over S slots reads every non-expert weight once (attention of every
layer, the leading dense layers' MLP, each expert layer's shared expert
and router, the final norm and the head; of the embedding only S rows),
the routed experts that at least one token was sent to (``touched``: from
the program's counter, never all that are held: a dispatch that skips
untouched experts must not read over 100%), and the live rows of the
latent cache, once per layer.  Activations, the rows written and the
logits are left out: they are thousands of times smaller.
"""


def attention_params(c):
    h = c["hidden_size"]
    heads = c["num_attention_heads"]
    q = h * heads * (c["qk_nope_head_dim"] + c["qk_rope_head_dim"])
    kva = h * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
    kvb = c["kv_lora_rank"] * heads * (c["qk_nope_head_dim"]
                                       + c["v_head_dim"])
    o = heads * c["v_head_dim"] * h
    norms = 2 * h + c["kv_lora_rank"] \
        + (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
           if c.get("use_qk_norm") else 0)
    return q + kva + kvb + o + norms


def expert_params(c):
    """One routed expert (and one shared expert: the same width)."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def layer_counts(c):
    dense = int(c["first_k_dense_replace"])
    return dense, int(c["num_hidden_layers"]) - dense


def non_expert_params(c):
    """Everything a step reads whatever the routing: all layers'
    attention, the dense layers' MLP, the expert layers' shared experts
    and routers (at the router's published width), final norm, head."""
    dense, expert = layer_counts(c)
    h = c["hidden_size"]
    return ((dense + expert) * attention_params(c)
            + dense * 3 * h * c["intermediate_size"]
            + expert * (c["num_shared_experts"] * expert_params(c)
                        + h * c["router_width"] + c["router_width"])
            + h + h * c["vocab_size"])


def held_params(c):
    """All weights this share holds (the table of ISSUE 27)."""
    _dense, expert = layer_counts(c)
    return (non_expert_params(c) + c["vocab_size"] * c["hidden_size"]
            + expert * c["num_experts"] * expert_params(c))


def itemsize(name):
    return {"bfloat16": 2, "float16": 2, "float32": 4}[name]


def cache_row_bytes(c):
    """One token's latent rows, all layers."""
    return c["num_hidden_layers"] \
        * (c["kv_lora_rank"] + c["qk_rope_head_dim"]) \
        * itemsize(c["cache_dtype"])


def step_bytes(c, experts_touched, live_tokens, slots):
    """``experts_touched``: routed experts with at least one token, summed
    over the expert layers of ONE step; ``live_tokens``: cached positions
    of all sessions."""
    w = itemsize(c["param_dtype"])
    return (non_expert_params(c) * w
            + slots * c["hidden_size"] * w
            + experts_touched * expert_params(c) * w
            + live_tokens * cache_row_bytes(c))
