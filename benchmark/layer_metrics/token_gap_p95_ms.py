"""``token_gap_p95_ms``: 95th percentile of every per-token gap
(``token_ms``) of the requests sent inside the window.  The tail the
benchmark guards: a window holds thousands of gaps, and a prefill that
stalls the running sessions shows here first.

A per-layer metric (layer: serving scheduler): the p95 lands on or beside
the gaps that another session's prefill makes, and over two sets of six
40 s runs read ~115 or ~160 ms (spread 33%; my chip run, PR23): too wide
for any bound an end-to-end metric may have."""

from benchmark.lib import stats


def read(facts):
    return stats.percentile(facts.get("token_gap_ms") or [], 0.95)
