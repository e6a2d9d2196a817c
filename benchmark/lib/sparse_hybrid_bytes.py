"""Bytes of a sparse hybrid model (KDA layers, DSA latent-attention layers
with their indexer, four hyper-connected residual streams, expert layers
with a shared expert), from the configuration file's own keys (published
``glm5_next_text`` names): what the share holds (`PERF.md` section 4)
and what one decode step has to move once, the roofline's numerator for
``sparse_step_roofline_frac``.

A step over S slots reads every non-expert weight once (each layer's mixer
by its kind, the DSA layers' indexer included, the hyper-connections'
maps, the dense MLP, each expert layer's shared expert and router, the
final norm and the head; of the embedding only S rows), the routed experts
that at least one token was sent to (``touched``: from the program's
counter), of each DSA layer the index keys of the whole groups it SCORED
(one key a position, pooled where read) and the latent rows it SELECTED
(never the rest of the context: that is what the selection saves), the
recurrent state of every live session READ AND WRITTEN once per KDA layer,
and the slots' residual streams read and written around every sublayer.
Activations inside a sublayer, the rows written and the logits are left
out: thousands of times smaller.
"""

from benchmark.lib.latent_bytes import itemsize


def kda_params(c):
    """One KDA mixer (``hybrid_bytes.kda_params`` with these names)."""
    h, lin = c["hidden_size"], c["linear_attn_config"]
    wide = lin["num_heads"] * lin["head_dim"]
    low = h * c["kda_low_rank"] + c["kda_low_rank"] * wide
    return (3 * h * wide + 3 * wide * lin["short_conv_kernel_size"]
            + 2 * low + h * lin["num_heads"] + lin["num_heads"] + wide
            + lin["head_dim"] + wide * h)


def dsa_params(c):
    """One DSA mixer: the low-rank query (and its norm), the latent's
    down-projection (and its norm), its up-projection, the output
    projection; the indexer's query and key maps, its key norm's gain and
    bias, its head weights."""
    h, heads = c["hidden_size"], c["num_attention_heads"]
    qr, rank = c["q_lora_rank"], c["kv_lora_rank"]
    index = (qr * c["index_n_heads"] * c["index_head_dim"]
             + h * c["index_head_dim"] + 2 * c["index_head_dim"]
             + h * c["index_n_heads"])
    return (h * qr + qr + qr * heads * c["qk_nope_head_dim"]
            + h * (rank + c["qk_rope_head_dim"]) + rank
            + rank * heads * (c["qk_nope_head_dim"] + c["v_head_dim"])
            + heads * c["v_head_dim"] * h + index)


def hc_params(c):
    """One sublayer's hyper-connection maps: ``phi`` [n C, 2n + n^2],
    ``alpha`` [3], ``bias`` [2n + n^2]."""
    n = c["hc_mult"]
    width = 2 * n + n * n
    return n * c["hidden_size"] * width + 3 + width


def expert_params(c):
    """One routed expert (and the shared expert: the same width)."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def layer_counts(c):
    """``(dense, expert, kda, dsa)`` layers of this share."""
    dense, depth = int(c["first_k_dense_replace"]), c["num_hidden_layers"]
    kda = len(c["linear_attn_config"]["kda_layers"])
    return dense, depth - dense, kda, depth - kda


def router_params(c):
    return c["hidden_size"] * c["router_width"] + c["router_width"]


def layer_params(c, number):
    """All of layer ``number`` (0-based) that this share holds, its two
    pre-norms and two sublayers' hyper-connections included."""
    kda = number in c["linear_attn_config"]["kda_layers"]
    mixer = kda_params(c) if kda else dsa_params(c)
    common = mixer + 2 * c["hidden_size"] + 2 * hc_params(c)
    if number < c["first_k_dense_replace"]:
        return common + 3 * c["hidden_size"] * c["intermediate_size"]
    return common + router_params(c) \
        + (c["n_shared_experts"] + c["n_routed_experts"]) * expert_params(c)


def vocabulary_params(c):
    """Embedding and head over the vocabulary slice."""
    return 2 * c["vocab_size"] * c["hidden_size"]


def held_params(c):
    """All weights this share holds."""
    return sum(layer_params(c, n) for n in range(c["num_hidden_layers"])) \
        + c["hidden_size"] + vocabulary_params(c)


def non_expert_params(c):
    """Everything a step reads whatever the routing and the context."""
    _dense, expert, _kda, _dsa = layer_counts(c)
    return held_params(c) \
        - expert * c["n_routed_experts"] * expert_params(c) \
        - c["vocab_size"] * c["hidden_size"]


def state_row_bytes(c):
    """One session's recurrent state and convolution history, all KDA
    layers."""
    lin = c["linear_attn_config"]
    wide = lin["num_heads"] * lin["head_dim"]
    _dense, _expert, kda, _dsa = layer_counts(c)
    return kda * (lin["num_heads"] * lin["head_dim"] ** 2
                  * itemsize(c["state_dtype"])
                  + (lin["short_conv_kernel_size"] - 1) * 3 * wide
                  * itemsize(c["cache_dtype"]))


def latent_row_bytes(c):
    """One position's latent row in ONE DSA layer."""
    return (c["kv_lora_rank"] + c["qk_rope_head_dim"]) \
        * itemsize(c["cache_dtype"])


def index_row_bytes(c):
    """One position's index key in ONE DSA layer."""
    return c["index_head_dim"] * itemsize(c["cache_dtype"])


def stream_bytes(c, slots):
    """The slots' residual streams, read and written once around every
    sublayer (two a layer)."""
    return 2 * 2 * c["num_hidden_layers"] * slots * c["hc_mult"] \
        * c["hidden_size"] * itemsize(c["compute_dtype"])


def step_bytes(c, experts_touched, live_sessions, slots, scored_groups,
               selected_positions):
    """``experts_touched``: routed experts with at least one token, summed
    over the expert layers of ONE step; ``live_sessions``: sessions
    stepped (their state is read and written); ``scored_groups`` /
    ``selected_positions``: the step's DSA counters (summed over its DSA
    layers and slots): the whole groups scored, whose ``index_kpool`` keys
    each are read, and the positions read, whose latent rows are."""
    w = itemsize(c["param_dtype"])
    return (non_expert_params(c) * w
            + slots * c["hidden_size"] * w
            + experts_touched * expert_params(c) * w
            + scored_groups * c["index_kpool"] * index_row_bytes(c)
            + selected_positions * latent_row_bytes(c)
            + 2 * live_sessions * state_row_bytes(c)
            + stream_bytes(c, slots))
