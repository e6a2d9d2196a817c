"""``dsa_selected_frac`` (layer: step): of the positions up to each decode
query, the share its DSA layers READ: the program's own step counters
(``dsa_selected_positions`` / ``dsa_live_positions``, summed over the DSA
layers and the live slots of every step; the engine writes them onto each
``tfos/decode/iterate`` span), summed over the decode iterations of the
traced slice.  A context of at most ``index_topk`` positions reads all of
it (1.0); at 8,192 positions about a quarter.  None without a capture or
where the program writes no such counter."""

from benchmark.lib import program_trace as P

SPAN = "tfos/decode/iterate"


def counters(facts):
    """The slice's DSA counters summed over its iterations ``{name:
    total}`` and the iterations, or None."""
    row = P.span(P.load(facts), SPAN)
    args = (row or {}).get("args") or {}
    if not args.get("dsa_live_positions"):
        return None
    return args, row["count"]


def read(facts):
    got = counters(facts)
    if got is None:
        return None
    args, _iterations = got
    return args["dsa_selected_positions"] / args["dsa_live_positions"]
