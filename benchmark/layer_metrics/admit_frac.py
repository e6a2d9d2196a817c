"""``admit_frac`` (layer: serving scheduler): time the engine thread
spent inside ``tfos/decode/admit`` (trie match, prefill to first-token
logits on the host, K/V insert) over the traced slice.  Every running
session waits through it.  None where the program writes no decode spans;
0.0 where it does and no admission fell into the slice."""

from benchmark.lib import program_trace as P


def read(facts):
    reduced = P.load(facts)
    if not P.span(reduced, "tfos/decode/iterate") \
            or not reduced["extent_s"]:
        return None
    row = P.span(reduced, "tfos/decode/admit") or {"total_s": 0.0}
    return row["total_s"] / reduced["extent_s"]
