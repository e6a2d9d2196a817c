"""``collective_exposed_frac`` (layer: mesh): of the union of collective
operations on the first device (``collective_ms``), the part during which
no other operation runs there: communication the step does not hide
behind compute.  None where no collective ran."""

from benchmark.lib import program_trace as P


def read(facts):
    dev = (P.load(facts) or {}).get("device")
    if not dev or not dev["collective_s"]:
        return None
    return dev["collective_exposed_s"] / dev["collective_s"]
