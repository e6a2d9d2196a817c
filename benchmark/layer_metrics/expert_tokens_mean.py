"""``expert_tokens_mean`` (layer: step): tokens per TOUCHED routed expert
per decode step, from the program's own counters (``DecodeEngine.stats()
["moe"]``: picks that landed on held experts / experts with at least one
token, both summed over the layers and the steps of the WINDOW: the
runner takes the raw totals at its end less those at its start).  The
deployment this cell is a share of would read 4.  None where the program
has no such counter."""


def read(facts):
    moe = facts.get("engine_moe") or {}
    return moe.get("tokens_per_expert_mean")
