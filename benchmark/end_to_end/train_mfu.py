"""``train_mfu``: items the trainer(s) consumed THROUGH THE FEED inside the
window x FLOPs an item requires (benchmark/lib/flops.py) over window
seconds x chips x published peak (benchmark/lib/peaks.py).  No peak (a
rehearsal on the CPU): no value."""


def read(facts):
    if not facts.get("peak_flops") or "window_items" not in facts:
        return None
    return (facts["window_items"] * facts["flops_per_item"]
            / facts["window_s"] / facts["peak_flops"])
