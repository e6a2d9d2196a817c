"""``fed_vs_resident`` (layer: feed): items/s of the fed window over
items/s of the SAME compiled step re-dispatched on one resident batch
after the feed ended (chained, one value fetch at the end)."""


def read(facts):
    if not facts.get("resident_items_per_s"):
        return None
    return (facts["window_items"] / facts["window_s"]
            / facts["resident_items_per_s"])
