"""``feed_host_busy_frac`` (layer: feed): self time of
``tfos/feed/ring_read + to_columns + collate + h2d`` on the feed's
consumer thread over the traced slice: the share of the slice in which
that thread WORKED (copy out of the ring, decode, assembly, the caller's
collate, dispatch of the transfer), i.e. the consumer's side is the
wall.  The rest of the slice it waited: ``feed_ring_wait_frac`` for the
producer, ``tfos/feed/stage_full`` for the device."""

from benchmark.lib import program_trace as P


def read(facts):
    return P.feed_thread_self_frac(facts, P.FEED_BUSY_SPANS)
