"""``latent_step_roofline_frac`` (layer: step): the least time the chip
could take for one decode step, bytes it must read once
(``lib/latent_bytes.step_bytes``: non-expert weights, the experts TOUCHED
by the program's counter, the live latent rows) over the chip's published
HBM bandwidth, divided by the step program's device time in the traced
slice.  The experts touched and the live rows are means over the steps
of the window that the slice lies in (the runner differences the
counters' totals; the warm-up's steps and the pre-roll are not in them).
Bandwidth-bound: at 16 tokens a step the FLOPs are 0.1% of what the
bytes take.  None without a capture, the program or its counters."""

from benchmark.layer_metrics import latent_step_ms
from benchmark.lib import latent_bytes, peaks


def read(facts):
    got = latent_step_ms.step_seconds(facts)
    moe, cache = facts.get("engine_moe"), facts.get("engine_cache")
    sizes = facts.get("model_sizes")
    if got is None or not moe or not cache or not sizes:
        return None
    _dense, expert_layers = latent_bytes.layer_counts(sizes)
    need = latent_bytes.step_bytes(
        sizes, moe["experts_touched"] * expert_layers,
        cache["live_tokens"], facts["slots"])
    floor_s = need / peaks.peak(facts["device"]["kind"], "hbm_bytes_per_s")
    return floor_s / got[0]
