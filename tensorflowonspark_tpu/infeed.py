"""Infeed pipelining: overlap host feed/conversion with device compute.

SURVEY.md §7 step 10's perf work ("infeed pipelining, double-buffering,
per-host sharded feeding"): the naive InputMode.SPARK loop is
  next_batch (host) -> np.stack (host) -> device_put -> step (device)
with the device idle during the host phases.  ``prefetch_to_device``
runs those host phases on a background thread ``depth`` batches ahead,
so the accelerator consumes batch t while t+1..t+depth are already
staged in HBM — the TPU-native analogue of the reference's
tf.data prefetch between DataFeed and model.fit
(examples/mnist/keras/mnist_spark.py:33-66).
"""

from __future__ import annotations

import logging
import queue as _queue
import threading
import time as _time

from tensorflowonspark_tpu.utils import telemetry

logger = logging.getLogger(__name__)

_END = object()


def batch_iterator(feed, batch_size, collate=None, min_batch=None,
                   columnar=False):
    """DataFeed -> iterator of collated host batches.

    ``collate(records) -> pytree of np arrays`` (default: identity);
    short tails below ``min_batch`` (default: batch_size) are dropped,
    matching the examples' skip-short-batch convention so SPMD steps
    always see full shapes (no recompilation, no ragged collectives).

    ``columnar=True`` pulls via ``feed.next_batch_columns`` — collate
    receives ``{tensor: dense ndarray[n, ...]}`` instead of per-tensor
    python lists, skipping the per-record loop + np.stack on the
    consumer hot path (requires the feed's input_mapping).
    """
    min_batch = batch_size if min_batch is None else min_batch
    pull = feed.next_batch_columns if columnar else feed.next_batch
    while not feed.should_stop():
        records = pull(batch_size)
        n = len(next(iter(records.values()))) if isinstance(records, dict) \
            else len(records)
        if n < min_batch:
            continue
        if collate is not None:
            with telemetry.span(telemetry.FEED_COLLATE):
                records = collate(records)
        yield records


def prefetch_to_device(it, depth=2, placement=None, on_abandon=None):
    """Stage ``it``'s batches onto devices ``depth`` ahead.

    placement: None (default device_put), a Sharding, or a callable
    pytree->pytree (e.g. ``lambda b: local_to_global(mesh, b)`` for
    multi-host global arrays).  Exceptions on the worker thread re-raise
    at the consuming iteration.

    on_abandon: called once if the consumer abandons the stream while the
    worker is still running (early ``break`` / ``close()``) — its job is
    to make the source iterator return promptly (device_feed passes the
    DataFeed's ``poison``).  Without it, a worker blocked in the source
    cannot be interrupted and is left as a daemon.
    """
    import jax

    if placement is None or not callable(placement):
        sharding = placement

        def place(batch):
            return jax.device_put(batch, sharding)
    else:
        place = placement

    q = _queue.Queue(maxsize=depth)
    cancelled = threading.Event()

    def worker():
        try:
            for batch in it:
                # check before place(): a cancelled worker must not stage
                # one more batch into HBM just for the drain to discard it
                if cancelled.is_set():
                    break
                with telemetry.span(telemetry.FEED_H2D):
                    staged = place(batch)
                # re-check after place(): the consumer may have abandoned
                # the stream during a long transfer — dropping the local
                # reference frees the device buffer, whereas enqueueing it
                # into the abandoned queue would pin HBM indefinitely
                if cancelled.is_set():
                    del staged
                    break
                # a full queue means the device is the wall
                with telemetry.span(telemetry.FEED_STAGE_FULL):
                    q.put(staged)
        except Exception as e:  # noqa: BLE001 - forwarded to consumer
            q.put(("__prefetch_error__", e))
        finally:
            q.put(_END)

    t = threading.Thread(target=worker, daemon=True, name="tfos-prefetch")
    t.start()

    finished = False
    try:
        while True:
            # an empty queue means the feed is the wall
            with telemetry.span(telemetry.FEED_NEXT):
                item = q.get()
            if item is _END:
                finished = True
                return
            if isinstance(item, tuple) and len(item) == 2 \
                    and item[0] == "__prefetch_error__":
                raise item[1]
            yield item
    finally:
        cancelled.set()
        if not finished:
            # abandoned mid-stream (or error raised): ask the source to
            # unblock, release a worker blocked on the full queue, and
            # drop staged batches so they don't pin device memory
            if on_abandon is not None:
                try:
                    on_abandon()
                except Exception:  # noqa: BLE001 - cleanup must not mask
                    logger.exception("prefetch on_abandon hook failed")
            deadline = _time.monotonic() + 3
            idle_polls = 0
            while _time.monotonic() < deadline:
                try:
                    item = q.get(timeout=0.2)
                except _queue.Empty:
                    if not t.is_alive():
                        break
                    # a live-but-idle worker is blocked in the source and
                    # will never produce once cancelled: stop burning time.
                    # With an on_abandon hook give the source one extra
                    # poll to unblock (poison slices are not instant), but
                    # never pay the full drain deadline on an idle worker —
                    # the join + daemon warning below covers a stuck one
                    idle_polls += 1
                    if idle_polls >= (3 if on_abandon is not None else 2):
                        break
                    continue
                idle_polls = 0
                if item is _END:
                    break
        t.join(timeout=2)
        if t.is_alive():
            logger.warning("prefetch worker did not exit (blocked in the "
                           "source iterator or mid-transfer); left as daemon")
        # final sweep: drop anything enqueued between the drain loop's
        # last poll and the worker's exit so it doesn't pin device memory
        while True:
            try:
                q.get_nowait()
            except _queue.Empty:
                break


def synchronized(it, feed=None):
    """Yield from ``it`` only while EVERY process still has a next item.

    The principled global-stop for ragged end-of-feed tails under
    synchronous collectives (SURVEY.md §7 hard parts): after end-of-feed,
    workers are left with DIFFERENT numbers of residual full batches, and
    a worker stepping one extra time would strand its peers' all-reduce —
    the reference's workaround was "train only 90% of the steps"
    (reference examples/mnist/keras/mnist_spark.py:58-66).  Here every
    process all-gathers a has-data flag before stepping, so all processes
    stop on exactly the same step.  The exchange is once per item,
    unconditionally — amortizing it would reintroduce the hang it
    prevents (a process that runs dry mid-window cannot participate in
    peers' device collectives).

    Pass ``feed`` (the DataFeed backing ``it``) so a process stopped
    with local batches remaining drains them (``feed.terminate()``),
    keeping the feeder-side consumption protocol intact.

    Scope: this aligns the *end-of-feed* tail — the signal that a feed is
    dry is its end-of-feed marker.  A worker starved MID-train (its
    partitions exhausted while peers keep receiving data, beyond what the
    prefetch/ring buffers absorb) blocks waiting for data before it can
    reach the flag exchange; keep per-worker record counts roughly
    balanced during feeding, as the engine's partitioning does (and as
    the reference equally required).

    Single-process: a plain passthrough with zero collectives.
    """
    import jax

    if jax.process_count() <= 1:
        yield from it
        return

    import numpy as np
    from jax.experimental import multihost_utils

    while True:
        item = next(it, None)
        mine = item is not None
        # the all-gather AND its fetch: the fetch queues behind every
        # step already dispatched, which is what this span shows
        with telemetry.span(telemetry.FEED_SYNC) as span:
            flags = multihost_utils.process_allgather(np.asarray(mine))
            ok = bool(np.asarray(flags).all())
            span.add(ok=ok)
        if not ok:
            if mine:
                logger.info(
                    "synchronized: a peer's feed ended; draining local "
                    "remainder"
                )
                if feed is not None:
                    feed.terminate()  # unblocks + ends the batch stream
                close = getattr(it, "close", None)
                if close is not None:
                    close()  # reap the prefetch thread + staged batches
            return
        yield item


def tfrecord_device_feed(source, batch_size, *, collate=None, depth=2,
                         placement=None, drop_remainder=True):
    """InputMode.TENSORFLOW fast path: stream TFRecord shards as dense
    column batches (``dfutil.iter_tfrecords_columnar`` — one shard
    resident at a time) straight into double-buffered device staging.

        for x, y in tfrecord_device_feed(files, per_proc,
                                         collate=my_collate):
            params, ... = step_fn(params, ..., x, y)

    ``collate({name: column_batch}) -> pytree`` (default: the dict as
    is); ``drop_remainder`` defaults True so SPMD steps always see full
    shapes.  ``source`` is a dir, file, or this worker's shard subset.
    """
    from tensorflowonspark_tpu import dfutil

    it = dfutil.iter_tfrecords_columnar(source, batch_size,
                                        drop_remainder=drop_remainder)
    if telemetry.enabled():
        # per-batch data/stage spans (stage tfrecord_read): decode/IO
        # cost of this hot path lands in trace_merge's -- data -- stall
        # table next to the pipeline stages (docs/data.md)
        from tensorflowonspark_tpu.data.pipeline import _instrumented

        it = _instrumented("tfrecord_read", it)
    if collate is not None:
        it = map(collate, it)
    return prefetch_to_device(it, depth=depth, placement=placement)


def device_feed(feed, batch_size, *, collate=None, depth=2, placement=None,
                min_batch=None, columnar=False):
    """The composed fast path: DataFeed -> collate -> double-buffered
    device staging.  Drop-in for the examples' while-loop:

        for batch in device_feed(ctx.get_data_feed(), per_proc,
                                 collate=my_collate,
                                 placement=lambda b: local_to_global(mesh, b)):
            params, ... = step_fn(params, ..., *batch)

    ``columnar=True``: collate sees dense per-tensor arrays (see
    ``batch_iterator``) — the preferred consumer for columnar feeds.
    """
    return prefetch_to_device(
        batch_iterator(feed, batch_size, collate, min_batch, columnar),
        depth=depth,
        placement=placement,
        # abandoning the stream (early break / close) poisons the feed so
        # the prefetch worker exits instead of polling the ring forever;
        # call feed.terminate() afterwards for the producer-drain handshake
        on_abandon=getattr(feed, "poison", None),
    )
