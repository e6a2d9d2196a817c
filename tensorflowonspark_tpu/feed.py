"""User-facing node API: path handling + the DataFeed queue consumer.

Parity target: reference ``tensorflowonspark/TFNode.py`` (hdfs_path,
DataFeed with next_batch/should_stop/batch_results/terminate, markers,
input_mapping).  Key redesign: queue items are **batches** (lists of
records) pushed by the feeder task, so a records-per-second hot loop costs
one IPC hop per *chunk* instead of one per record (the reference's
documented bottleneck, TFSparkNode.py:480-482 ↔ TFNode.py:265-287).

``DataFeed.next_batch`` therefore keeps a local leftover buffer: a consumed
chunk that overfills the requested batch carries into the next call.
"""

from __future__ import annotations

import logging
import queue as _queue
import threading
import time

from tensorflowonspark_tpu import marker
from tensorflowonspark_tpu.utils import faults, metrics_registry, telemetry

logger = logging.getLogger(__name__)


def hdfs_path(ctx, path):
    """Normalize a path against the cluster default FS (TFNode.py:29-64).

    Absolute schemes pass through; relative paths resolve against the
    engine's default filesystem (file://, hdfs://, gs://, s3a://...).
    """
    if path.startswith(
        ("file://", "hdfs://", "viewfs://", "gs://", "s3://", "s3a://", "har://")
    ):
        return path
    if ctx.default_fs.startswith(("hdfs://", "viewfs://", "gs://", "s3a://")):
        if path.startswith("/"):
            return ctx.default_fs + path
        return f"{ctx.default_fs}/user/{_user()}/{path}"
    if ctx.default_fs.startswith("file://"):
        if path.startswith("/"):
            return ctx.default_fs + path
        return f"file://{ctx.working_dir}/{path}"
    logger.warning("unknown default_fs %s, using path as-is", ctx.default_fs)
    return path


def _user():
    import getpass

    return getpass.getuser()


def open_feed_ring(mgr, qname="input", producer=False,
                   producer_nonblock=False):
    """Open the shm fast path advertised by the node, or None.

    THE transport handshake, shared by producer (feeder/shutdown closures)
    and consumer (DataFeed): the node's KV entry 'shm_input' is the single
    source of truth.  If a ring is advertised but cannot be opened on this
    side, raise — a silent one-sided fallback would leave producer and
    consumer on different transports and deadlock the feed.
    """
    if qname != "input":
        return None
    ring_name = mgr.get("shm_input")
    if not ring_name:
        return None
    try:
        from tensorflowonspark_tpu.recordio import shm as shmq

        return shmq.ShmQueue(str(ring_name), create=False, producer=producer,
                             producer_nonblock=producer_nonblock)
    except BlockingIOError:
        raise  # ring busy, not broken: dynamic-dispatch handover retries
    except Exception as e:
        raise RuntimeError(
            f"node advertised shm feed ring {ring_name!r} but this process "
            f"cannot open it: {e}; unset TFOS_SHM_FEED to disable the fast path"
        ) from e


def _sliced_column(chunk, i, off, take, shapes):
    """Field ``i``'s records [off, off+take) from a ColumnChunk, as an
    array slice — reshaped back to the original trailing shape when the
    feeder flattened an n-D field (``shapes``).  Pure views, no copies.
    THE single place the wire shape contract is applied; every consumer
    path (row reconstruction, per-tensor lists, dense batches) goes
    through it."""
    col = chunk.columns[i][off:off + take]
    if shapes is not None and shapes[i] is not None:
        col = col.reshape((-1,) + shapes[i])
    return col


class _ChunkClock:
    """THE one timing of a chunk fetch.  It alternates between "the
    transport is empty" (``tfos/feed/ring_wait``: the producer's side of
    the ring is the wall) and "copying a chunk out and decoding it"
    (``tfos/feed/ring_read``: the consumer's side); each ``switch``
    closes one span and opens the other on the same clock read, so the
    spans, ``TrainMetrics`` and the registry counters cannot disagree."""

    __slots__ = ("wait_s", "read_s", "_t", "_reading", "_span")

    def __init__(self):
        self.wait_s = self.read_s = 0.0
        self._reading = False
        self._span = telemetry.span(telemetry.FEED_RING_WAIT).__enter__()
        self._t = time.perf_counter()

    def switch(self, reopen=True, **attrs):
        """End the current phase (``attrs`` go on its span) and, unless
        this is the end of the fetch, begin the other one."""
        now = time.perf_counter()
        if self._reading:
            self.read_s += now - self._t
        else:
            self.wait_s += now - self._t
        self._t = now
        self._span.add(**attrs).__exit__(None, None, None)
        self._reading = not self._reading
        if reopen:
            self._span = telemetry.span(
                telemetry.FEED_RING_READ if self._reading
                else telemetry.FEED_RING_WAIT).__enter__()


class DataFeed:
    """Consumer side of the executor feed queues (TFNode.py:221-329)."""

    def __init__(
        self,
        mgr,
        train_mode=True,
        qname_in="input",
        qname_out="output",
        input_mapping=None,
        metrics=None,
    ):
        self.mgr = mgr
        self.train_mode = train_mode
        self.qname_in = qname_in
        self.qname_out = qname_out
        self.done_feeding = False
        # optional utils.metrics.TrainMetrics: feed-wait time lands in its
        # infeed-stall counter (SURVEY.md §5 observability target)
        self.metrics = metrics
        self.input_tensors = (
            sorted(input_mapping.values()) if input_mapping is not None else None
        )
        self._buffer = []  # leftover records from a partially-consumed chunk
        self._colblock = None  # (ColumnChunk, offset): partially-consumed
        self._col_meta = {}  # tensor -> (dtype, trailing shape) last seen
        # split-tagged delivery state (dynamic split dispatch): next
        # expected chunk seq per split id.  A re-served split (worker
        # SIGKILLed mid-split, provider requeued it pinned to this
        # trainer) replays from seq 0; chunks below the expected seq were
        # already consumed and are dropped here — the consumer half of
        # the exactly-once contract (data/splits.py).
        self._split_next = {}
        # The ring is single-consumer: a prefetch thread (infeed.py) and a
        # terminate() caller must never pop concurrently.  Gets poll under
        # this lock in short slices and re-check the stop flag between
        # slices, so terminate() from another thread can always interleave.
        self._lock = threading.Lock()
        self._stop_requested = False
        self._wait_acc = 0.0  # feed-wait seconds inside the current pull
        self._queue = None  # cached manager queue proxy (compat path)
        # shm fast path; the handshake (open_feed_ring) is shared with the
        # producer closures so both sides always agree on the transport
        self._ring = open_feed_ring(mgr, qname_in, producer=False)

    def _get_once(self, timeout_ms, honor_stop=False, available=None):
        """One bounded pop attempt; raises TimeoutError when empty.
        ``available()`` is called where the wait for a chunk ends and
        the read of it begins (``_ChunkClock.switch``).

        ``honor_stop`` (the consumer path): re-check the stop flag AFTER
        acquiring the lock — a consumer that queued on the lock behind
        terminate()'s drain (which holds it in up-to-1s slices) would
        otherwise act on a stop check from before the drain began and
        pop a chunk the drain was supposed to absorb.  terminate()
        itself pops with the flag set, so its calls leave this off."""
        with self._lock:
            if honor_stop and self._stop_requested:
                raise TimeoutError("feed terminating")
            if self._ring is not None:
                return self._ring.get(timeout_ms, available)
            if self._queue is None:  # resolve the manager proxy once
                self._queue = self.mgr.get_queue(self.qname_in)
            try:
                chunk = self._queue.get(block=True, timeout=timeout_ms / 1000.0)
            except _queue.Empty:
                raise TimeoutError("feed queue empty") from None
            if available is not None:
                available()  # the proxy hands the chunk over decoded
            self._queue.task_done()
            return chunk

    def _get_chunk(self):
        """Next chunk from the fast or compat transport: blocks until data
        arrives or terminate()/poison() is requested (then reports
        end-of-feed).  Poll slice: 100ms on the in-process shm ring (a
        local check), 1s on the manager-queue compat path where every
        attempt is a proxy RPC — the stop flag only needs sub-second
        responsiveness, not a 10Hz round-trip load on the manager."""
        clock = (_ChunkClock() if self.metrics is not None
                 or telemetry.active() or metrics_registry.enabled()
                 else None)
        available = None if clock is None else clock.switch
        slice_ms = 100 if self._ring is not None else 1000
        while True:
            if self._stop_requested:
                chunk = None  # terminate(): consume no further data
                break
            try:
                chunk = self._get_once(timeout_ms=slice_ms, honor_stop=True,
                                       available=available)
            except TimeoutError:
                continue
            faults.check("feed.get", eof=chunk is None)
            tag = getattr(chunk, "meta", None)
            if tag is not None and tag[0] == "split":
                _kind, sid, seq, _nblocks = tag
                expected = self._split_next.get(sid, 0)
                if seq < expected:  # re-served prefix: already consumed
                    metrics_registry.inc(
                        "tfos_data_split_dup_chunks_total")
                    if clock is not None:
                        clock.switch(dup=1)  # back to waiting
                    continue
                self._split_next[sid] = seq + 1
            break
        if clock is not None:
            self._account_chunk(clock, chunk)
        return chunk

    def _account_chunk(self, clock, chunk):
        """Close the fetch's clock and hand its ONE measurement to every
        reader: the two spans, ``TrainMetrics`` (``infeed_wait`` stays
        the sum, ``ring_wait_time`` the empty-transport part) and the
        live plane's counters."""
        try:
            records = len(chunk)
        except TypeError:  # None (eof) or a length-less marker
            records = 0
        attrs = {}
        columns = getattr(chunk, "columns", None)
        if columns is not None:
            attrs["bytes"] = sum(c.nbytes for c in columns)
        try:  # depth after the get, read once for every reader
            if self._ring is not None:
                attrs["depth_bytes"] = self._ring.qsize_bytes()
            elif self._queue is not None:
                attrs["depth_chunks"] = self._queue.qsize()
        except Exception:  # noqa: BLE001 - depth is best-effort
            pass
        clock.switch(reopen=False, records=records, eof=chunk is None,
                     **attrs)
        dt = clock.wait_s + clock.read_s
        self._wait_acc += dt
        if self.metrics is not None:
            self.metrics.infeed_wait(dt, ring_wait=clock.wait_s)
        metrics_registry.inc("tfos_feed_wait_seconds_total", dt)
        metrics_registry.inc("tfos_feed_chunks_total")
        metrics_registry.inc("tfos_feed_records_total", records)
        if "depth_bytes" in attrs:
            metrics_registry.set_gauge("tfos_feed_ring_bytes",
                                       attrs["depth_bytes"])
        elif "depth_chunks" in attrs:
            metrics_registry.set_gauge("tfos_feed_queue_depth",
                                       attrs["depth_chunks"])

    def _assemble(self, pull, batch_size):
        """One pull under its ``tfos/feed/to_columns`` span: the span's
        time minus the chunk fetches inside it (``wait_ms``, accumulated
        by ``_account_chunk``) is the consumer's own assembly (slice /
        concat / stack) cost — the row ``trace_merge``'s ``-- data --``
        section shows beside the pipeline stages."""
        self._wait_acc = 0.0
        with telemetry.span(telemetry.FEED_TO_COLUMNS) as span:
            out = pull(batch_size)
            if isinstance(out, dict):
                n = len(next(iter(out.values()))) if out else 0
            else:
                n = len(out)
            span.add(records=n, wait_ms=round(self._wait_acc * 1e3, 3))
        return out

    def next_batch(self, batch_size):
        """Gather up to ``batch_size`` records (TFNode.py:243-288).

        Returns a list of records, or — with ``input_mapping`` — a dict of
        {tensor_name: list_of_column_values}.  A ``None`` chunk in the
        queue means end-of-feed; an ``EndPartition`` marker ends the batch
        early in inference mode so results stay partition-aligned.
        """
        return self._assemble(self._next_batch, batch_size)

    def _next_batch(self, batch_size):
        logger.debug("next_batch(%d) invoked", batch_size)
        tensors = (
            [] if self.input_tensors is None else {t: [] for t in self.input_tensors}
        )
        count = 0

        def _append(record):
            nonlocal count
            if self.input_tensors is None:
                tensors.append(record)
            else:
                for i, t in enumerate(self.input_tensors):
                    tensors[t].append(record[i])
            count += 1

        def _take_columns(block):
            """Consume up to the batch remainder from a columnar chunk.

            With input_mapping, column slices extend the per-tensor lists
            directly — no per-record python loop (scalar columns extend
            with numpy scalars, width columns with row views, both of
            which np.asarray/np.stack handle in one memcpy downstream).
            n-D fields the feeder flattened (``chunk.shapes``) come back
            as reshape views, so each record sees its original shape.
            """
            nonlocal count
            chunk, off = block
            shapes = getattr(chunk, "shapes", None)
            take = min(batch_size - count, len(chunk) - off)
            if self.input_tensors is None:
                if shapes is not None:
                    cols = [
                        _sliced_column(chunk, i, off, take, shapes)
                        for i in range(len(chunk.columns))
                    ]

                    def _rowval(i, c, j):
                        # match columns_to_rows exactly: PYTHON scalars
                        # for 1-D columns, python lists for width
                        # columns (tolist, not list: list() would keep
                        # numpy scalar elements).  Shaped fields COPY:
                        # records from this path are independent objects
                        # a consumer may retain, and a view would pin
                        # the whole multi-MB chunk buffer per record
                        # (the mapping/columns paths keep views — their
                        # consumers collate immediately)
                        if shapes[i] is not None:
                            return c[j].copy()
                        return c[j].item() if c.ndim == 1 else c[j].tolist()

                    self._buffer.extend(
                        tuple(_rowval(i, c, j) for i, c in enumerate(cols))
                        for j in range(take))
                else:
                    from tensorflowonspark_tpu.recordio import marshal

                    self._buffer.extend(marshal.columns_to_rows(
                        [c[off:off + take] for c in chunk.columns]
                    ))
            else:
                for i, t in enumerate(self.input_tensors):
                    tensors[t].extend(
                        _sliced_column(chunk, i, off, take, shapes))
                count += take
            off += take
            return (chunk, off) if off < len(chunk) else None

        while count < batch_size:
            if self._buffer:
                _append(self._buffer.pop(0))
                continue
            if self._colblock is not None:
                self._colblock = _take_columns(self._colblock)
                continue
            chunk = self._get_chunk()
            if chunk is None:
                logger.info("next_batch() got None: end of feed")
                self.done_feeding = True
                break
            if isinstance(chunk, marker.EndPartition):
                logger.debug("next_batch() got EndPartition")
                if not self.train_mode and count > 0:
                    break
                continue
            if isinstance(chunk, marker.ColumnChunk):
                self._colblock = (chunk, 0)
                continue
            # chunk is a list of records (the batched redesign); tolerate a
            # stray single record for compatibility with hand-fed queues.
            if isinstance(chunk, list):
                self._buffer.extend(chunk)
            else:
                _append(chunk)
        return tensors

    def next_batch_columns(self, batch_size):
        """Gather up to ``batch_size`` records as DENSE per-tensor arrays:
        ``{tensor_name: ndarray[n, ...]}`` — the zero-python-loop consumer
        for columnar feeds (requires ``input_mapping``).

        ColumnChunk data is consumed as array SEGMENTS: an aligned chunk
        covering the whole batch passes through as a zero-copy view;
        spanning chunks cost one ``np.concatenate`` (a single memcpy) —
        vs ``next_batch`` + ``np.stack``'s per-record python loop over
        row views (~12k img/s single-threaded at 224px, PERF.md).  Row
        chunks from non-columnar feeders degrade gracefully to a
        per-segment ``np.stack``.  n-D fields flattened by the feeder
        (``ColumnChunk.shapes``) come back reshaped, views again.
        """
        if self.input_tensors is None:
            raise ValueError("next_batch_columns requires input_mapping")
        return self._assemble(self._next_batch_columns, batch_size)

    def _next_batch_columns(self, batch_size):
        import numpy as np

        segments = {t: [] for t in self.input_tensors}
        count = 0

        def _rows_segment(rows):
            nonlocal count
            for i, t in enumerate(self.input_tensors):
                segments[t].append(np.asarray([r[i] for r in rows]))
            count += len(rows)

        while count < batch_size:
            if self._buffer:
                take = min(batch_size - count, len(self._buffer))
                rows, self._buffer = (self._buffer[:take],
                                      self._buffer[take:])
                _rows_segment(rows)
                continue
            if self._colblock is not None:
                chunk, off = self._colblock
                shapes = getattr(chunk, "shapes", None)
                take = min(batch_size - count, len(chunk) - off)
                for i, t in enumerate(self.input_tensors):
                    segments[t].append(
                        _sliced_column(chunk, i, off, take, shapes))
                count += take
                off += take
                self._colblock = ((chunk, off) if off < len(chunk)
                                  else None)
                continue
            chunk = self._get_chunk()
            if chunk is None:
                logger.info("next_batch_columns() got None: end of feed")
                self.done_feeding = True
                break
            if isinstance(chunk, marker.EndPartition):
                if not self.train_mode and count > 0:
                    break
                continue
            if isinstance(chunk, marker.ColumnChunk):
                self._colblock = (chunk, 0)
                continue
            if isinstance(chunk, list):
                self._buffer.extend(chunk)
            else:
                _rows_segment([chunk])
        out = {}
        for t in self.input_tensors:
            parts = segments[t]
            if not parts:
                # honor the dense contract even for an empty pull: use
                # the dtype/trailing-shape last seen for this tensor so
                # callers can concatenate tails without rank/dtype traps
                dtype, trail = self._col_meta.get(t, (None, ()))
                out[t] = np.empty((0,) + tuple(trail), dtype=dtype)
            elif len(parts) == 1:
                out[t] = parts[0]  # aligned chunk: zero copy
            else:
                out[t] = np.concatenate(parts, axis=0)
            if len(out[t]):
                self._col_meta[t] = (out[t].dtype, out[t].shape[1:])
        return out

    def should_stop(self):
        """True once the feeder pushed the end-of-feed None (TFNode.py:290)."""
        return self.done_feeding

    def batch_results(self, results):
        """Push one batch of inference results (TFNode.py:294-305)."""
        queue = self.mgr.get_queue(self.qname_out)
        queue.put(list(results))

    def poison(self):
        """End the feed for its consumer without the producer handshake:
        the next _get_chunk poll reports end-of-feed.  Used when a
        prefetch worker is abandoned mid-stream (infeed.py) so the orphan
        thread exits within one poll slice instead of polling forever;
        the ring stays single-consumer and terminate() may still run the
        full producer drain afterwards."""
        self._stop_requested = True

    def terminate(self):
        """Request early stop and drain the input queue (TFNode.py:307-329).

        Sets state to 'terminating' so feeder tasks that land later skip
        straight to draining; then empties what is already queued so the
        producer's queue.join() returns.  Safe to call while another
        thread (e.g. the infeed prefetcher) is blocked in next_batch: the
        stop flag turns that thread's pending get into end-of-feed, and
        all pops here go through the same per-attempt lock, so the
        single-consumer ring never sees two concurrent readers.

        Ring path: "drained" is decided by the producer flock, not a
        timeout — an empty ring only ends the drain once no feeder holds
        the producer lock, so a slow producer mid-partition cannot strand
        data (and its hand-over wait) behind a 5s guess.
        """
        logger.info("terminate() invoked")
        self._stop_requested = True
        self.mgr.set("state", "terminating")
        if self._ring is not None:
            from tensorflowonspark_tpu.recordio import shm as shmq

            empty_checks = 0
            while True:
                try:
                    if self._get_once(timeout_ms=1000) is None:
                        break  # producer closed the ring: EOF
                    empty_checks = 0
                except TimeoutError:
                    if (self._ring.qsize_bytes() == 0
                            and not shmq.producer_active(self._ring.name)):
                        empty_checks += 1
                        if empty_checks >= 2:
                            break
            return
        while True:
            try:
                self._get_once(timeout_ms=5000)
            except Exception:  # noqa: BLE001 - Empty/Timeout/dead manager
                # = fully drained: a manager already torn down at job end
                # must not crash an otherwise-successful terminate
                break


def start_cluster_server(ctx, num_gpus=1, rdma=False):
    """Deprecated TF1-era API (TFNode.py:67-151): in the reference this
    started a tf.train.Server on the reserved port.  TPU-native jobs have
    no per-node gRPC server; joining the cluster is ctx.jax_initialize().
    Kept so ported main_funs run; returns an object with a .target-like
    coordinator address.
    """
    import warnings

    warnings.warn(
        "start_cluster_server is deprecated; use ctx.jax_initialize()",
        DeprecationWarning,
        stacklevel=2,
    )
    env = ctx.jax_initialize()

    class _Server:  # minimal tf.train.Server stand-in
        target = env.get("coordinator_address")

        @staticmethod
        def join():
            raise RuntimeError(
                "server.join() has no TPU equivalent; ps-style blocking is "
                "handled by the framework's control queue"
            )

    return _Server()


def export_saved_model(sess=None, export_dir=None, tag_set=None,
                       signatures=None, params=None, ctx=None,
                       metadata=None):
    """Deprecated TF1-era export (TFNode.py:159-208).  The TPU-native
    export is utils.checkpoint.export_model(export_dir, params, ctx);
    this shim forwards to it (chief-only contract preserved)."""
    import warnings

    from tensorflowonspark_tpu.utils import checkpoint as _ckpt

    warnings.warn(
        "TFNode.export_saved_model is deprecated; use "
        "utils.checkpoint.export_model",
        DeprecationWarning,
        stacklevel=2,
    )
    assert export_dir is not None and params is not None
    return _ckpt.export_model(export_dir, params, ctx, metadata=metadata)
