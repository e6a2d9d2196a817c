"""``feed_ring_wait_frac`` (layer: feed): self time of
``tfos/feed/ring_wait`` on the feed's consumer thread over the traced
slice: the share of the slice in which that thread found the transport
EMPTY, i.e. the producer's side of the ring (feeder task, partition
hand-over) is the wall.  Read from the run's capture by
``lib/program_trace``; None without a capture or where the program
writes no feed spans."""

from benchmark.lib import program_trace as P


def read(facts):
    return P.feed_thread_self_frac(facts, ("tfos/feed/ring_wait",))
