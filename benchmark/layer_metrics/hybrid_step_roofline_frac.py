"""``hybrid_step_roofline_frac`` (layer: step): the least time the chip
could take for one decode step of a hybrid linear-attention model — the
bytes it must move once (``lib/hybrid_bytes.step_bytes``: non-expert
weights by layer kind, the experts TOUCHED by the program's counter, the
live latent rows over the latent layers, the live sessions' recurrent
state read and written) over the chip's published HBM bandwidth — divided
by the step program's device time in the traced slice.  Experts touched,
live rows and live sessions (decode tokens per iteration: a session steps
one token) are means over the steps of the window that the slice lies in.
Bandwidth-bound: at 64 tokens a step the FLOPs are 1% of what the bytes
take.  None without a capture, the program or its counters."""

from benchmark.layer_metrics import latent_step_ms
from benchmark.lib import hybrid_bytes, peaks


def read(facts):
    got = latent_step_ms.step_seconds(facts)
    moe, cache = facts.get("engine_moe"), facts.get("engine_cache")
    sizes = facts.get("model_sizes")
    if got is None or not moe or not cache or not sizes \
            or not cache.get("state_row_bytes") \
            or not facts.get("window_iterations"):
        return None
    _dense, expert_layers, _kda, _latent = hybrid_bytes.layer_counts(sizes)
    need = hybrid_bytes.step_bytes(
        sizes, moe["experts_touched"] * expert_layers, cache["live_tokens"],
        facts["window_decode_tokens"] / facts["window_iterations"],
        facts["slots"])
    floor_s = need / peaks.peak(facts["device"]["kind"], "hbm_bytes_per_s")
    return floor_s / got[0]
