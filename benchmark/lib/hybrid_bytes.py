"""Bytes of a hybrid linear-attention / latent-attention expert model, from
the configuration file's own keys (published names): what the share holds
(the table of ISSUE 32) and what one decode step has to move once, the
roofline's numerator for ``hybrid_step_roofline_frac``.

A step over S slots reads every non-expert weight once (each layer's mixer
by its kind, layer 1's dense MLP, each expert layer's shared expert and
router, the final norm and the head; of the embedding only S rows), the
routed experts that at least one token was sent to (``touched``: from the
program's counter, never all that are held), the live rows of the latent
cache once per LATENT layer, and the recurrent state of every live
session, READ AND WRITTEN, once per KDA layer (a recurrence rewrites all
of its state every token: that is what it costs instead of a cache that
grows).  Activations, the latent row written and the logits are left out:
thousands of times smaller.
"""

from benchmark.lib.latent_bytes import itemsize


def kda_params(c):
    """One KDA mixer: the q/k/v projections, their convolutions, the
    decay's and the output gate's low-rank maps, beta, A_log, dt_bias, the
    output norm's gain, the output projection."""
    h, lin = c["hidden_size"], c["linear_attn_config"]
    wide = lin["num_heads"] * lin["head_dim"]
    low = h * c["kda_low_rank"] + c["kda_low_rank"] * wide
    return (3 * h * wide + 3 * wide * lin["short_conv_kernel_size"]
            + 2 * low + h * lin["num_heads"] + lin["num_heads"] + wide
            + lin["head_dim"] + wide * h)


def latent_params(c):
    """One latent mixer without rotary and without a q norm."""
    h, heads = c["hidden_size"], c["num_attention_heads"]
    return (h * heads * (c["qk_nope_head_dim"] + c["qk_rope_head_dim"])
            + h * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
            + c["kv_lora_rank"] * heads * (c["qk_nope_head_dim"]
                                           + c["v_head_dim"])
            + heads * c["v_head_dim"] * h + c["kv_lora_rank"])


def expert_params(c):
    """One routed expert (and the shared expert: the same width)."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def layer_counts(c):
    """``(dense, expert, kda, latent)`` layers of this share."""
    dense, depth = int(c["first_k_dense_replace"]), c["num_hidden_layers"]
    kda = len(c["linear_attn_config"]["kda_layers"])
    return dense, depth - dense, kda, depth - kda


def layer_params(c, number):
    """All of layer ``number`` (1-based) that this share holds, the two
    pre-norms' gains left out (2 x hidden: the table rounds to 0.1 M)."""
    kda = number in c["linear_attn_config"]["kda_layers"]
    mixer = kda_params(c) if kda else latent_params(c)
    if number <= c["first_k_dense_replace"]:
        return mixer + 3 * c["hidden_size"] * c["intermediate_size"]
    return mixer + router_params(c) \
        + (c["num_shared_experts"] + c["num_experts"]) * expert_params(c)


def router_params(c):
    return c["hidden_size"] * c["router_width"] + c["router_width"]


def vocabulary_params(c):
    """Embedding and head over the vocabulary slice."""
    return 2 * c["vocab_size"] * c["hidden_size"]


def held_params(c):
    """All weights this share holds."""
    return sum(layer_params(c, n)
               for n in range(1, c["num_hidden_layers"] + 1)) \
        + 2 * c["num_hidden_layers"] * c["hidden_size"] \
        + c["hidden_size"] + vocabulary_params(c)


def non_expert_params(c):
    """Everything a step reads whatever the routing."""
    _dense, expert, _kda, _latent = layer_counts(c)
    return held_params(c) - expert * c["num_experts"] * expert_params(c) \
        - c["vocab_size"] * c["hidden_size"]


def state_row_bytes(c):
    """One session's recurrent state and convolution history, all KDA
    layers: what a session costs whatever its length."""
    lin = c["linear_attn_config"]
    wide = lin["num_heads"] * lin["head_dim"]
    _dense, _expert, kda, _latent = layer_counts(c)
    return kda * (lin["num_heads"] * lin["head_dim"] ** 2
                  * itemsize(c["state_dtype"])
                  + (lin["short_conv_kernel_size"] - 1) * 3 * wide
                  * itemsize(c["cache_dtype"]))


def cache_row_bytes(c):
    """One token's latent rows, over the latent layers."""
    _dense, _expert, _kda, latent = layer_counts(c)
    return latent * (c["kv_lora_rank"] + c["qk_rope_head_dim"]) \
        * itemsize(c["cache_dtype"])


def step_bytes(c, experts_touched, live_tokens, live_sessions, slots):
    """``experts_touched``: routed experts with at least one token, summed
    over the expert layers of ONE step; ``live_tokens``: cached positions
    of all sessions; ``live_sessions``: sessions stepped (their state is
    read and written)."""
    w = itemsize(c["param_dtype"])
    return (non_expert_params(c) * w
            + slots * c["hidden_size"] * w
            + experts_touched * expert_params(c) * w
            + live_tokens * cache_row_bytes(c)
            + 2 * live_sessions * state_row_bytes(c))
