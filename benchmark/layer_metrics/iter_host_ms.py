"""``iter_host_ms`` (layer: serving scheduler): per decode iteration
(``tfos/decode/iterate``), its duration minus ``step_dispatch`` and
``logits_fetch``: the engine thread's own work between two steps (window
build, sampling, queue puts, retirement), which the device waits
through.  Admissions are outside it (``admit_frac``)."""

from benchmark.lib import program_trace as P


def read(facts):
    reduced = P.load(facts)
    it = P.span(reduced, "tfos/decode/iterate")
    if not it or not it["count"]:
        return None
    device_side = sum(
        (P.span(reduced, n) or {"total_s": 0.0})["total_s"]
        for n in ("tfos/decode/step_dispatch", "tfos/decode/logits_fetch"))
    return (it["total_s"] - device_side) * 1e3 / it["count"]
