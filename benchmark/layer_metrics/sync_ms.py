"""``sync_ms`` (layer: mesh): mean duration of ``tfos/feed/sync``, the
has-data flag that ``infeed.synchronized`` all-gathers and FETCHES before
every step of a job of several processes.  The fetch queues behind every
step already dispatched, so this is what the global-stop protocol costs
the host per step.  None in a job of one process (no such span)."""

from benchmark.lib import program_trace as P


def read(facts):
    row = P.span(P.load(facts), "tfos/feed/sync")
    if not row or not row["count"]:
        return None
    return row["total_s"] * 1e3 / row["count"]
