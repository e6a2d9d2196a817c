"""``collective_ms`` (layer: mesh): the union of the intervals in which a
collective operation (all-reduce, all-gather, ...; asynchronous ones from
start to done) is in flight on the first device, per step of the traced
slice (steps: count of ``bench/dispatch_step``).  None without a capture,
without steps, or where no collective ran (one chip)."""

from benchmark.lib import program_trace as P


def read(facts):
    reduced = P.load(facts)
    dev = (reduced or {}).get("device")
    if not dev or not reduced["steps"] or not dev["collective_s"]:
        return None
    return dev["collective_s"] * 1e3 / reduced["steps"]
