"""``ttft_p50_ms``: median, over the requests sent inside the window, of
(the time the request was due) -> first token: the client's send lateness
plus the reply's ``ttft_ms``.  A median and not a tail: the window holds
some tens of requests (choosing-metrics: the highest percentile with ten
samples beyond it).

A per-layer metric (layer: serving scheduler): over two sets of six 40 s
runs its quartile spread was 25-47% (my chip run, PR23), too wide for any
bound an end-to-end metric may have."""

from benchmark.lib import stats


def read(facts):
    return stats.percentile(facts.get("ttft_ms") or [], 0.50)
