"""``sparse_step_roofline_frac`` (layer: step): the least time the chip
could take for one decode step of a sparse hybrid model — the bytes it
must move once (``lib/sparse_hybrid_bytes.step_bytes``: non-expert weights
by layer kind with the indexer and the hyper-connections' maps, the
experts TOUCHED by the program's counter, the index keys of the groups the
DSA layers scored and the latent rows they SELECTED, the live sessions'
recurrent state read and written, the residual streams) over the chip's
published HBM bandwidth — divided by the step program's device time in the
traced slice.  Scored groups and selected rows are the slice's own step
counters per iteration (``dsa_selected_frac.counters``); experts touched
and live sessions are means over the steps of the window that the slice
lies in.  Bandwidth-bound: at 16 tokens a step the FLOPs are well under 1%
of what the bytes take.  None without a capture, the program or its
counters."""

from benchmark.layer_metrics import dsa_selected_frac, latent_step_ms
from benchmark.lib import peaks, sparse_hybrid_bytes


def read(facts):
    got = latent_step_ms.step_seconds(facts)
    dsa = dsa_selected_frac.counters(facts)
    moe, sizes = facts.get("engine_moe"), facts.get("model_sizes")
    if got is None or dsa is None or not moe or not sizes \
            or not facts.get("window_iterations"):
        return None
    args, iterations = dsa
    _dense, expert_layers, _kda, _dsa = sparse_hybrid_bytes.layer_counts(
        sizes)
    need = sparse_hybrid_bytes.step_bytes(
        sizes, moe["experts_touched"] * expert_layers,
        facts["window_decode_tokens"] / facts["window_iterations"],
        facts["slots"], args["dsa_scored_groups"] / iterations,
        args["dsa_selected_positions"] / iterations)
    floor_s = need / peaks.peak(facts["device"]["kind"], "hbm_bytes_per_s")
    return floor_s / got[0]
