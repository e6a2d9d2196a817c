"""Shared-memory ring queue binding (native/shmqueue.cpp).

The fast same-host feed path: the feeder pushes serialized record chunks
into a SPSC byte ring in POSIX shm; the training process pops them with
no per-record IPC and no manager round-trips.  Used by the feed layer as
an accelerated transport when the native library is present; the manager
queue remains the control/compat path.
"""

from __future__ import annotations

import ctypes
import os
import pickle

from tensorflowonspark_tpu.recordio import native as _native

# fast-path frame magic: cannot collide with a pickle stream (protocol 2+
# starts with b'\x80'), so legacy and columnar messages share one ring
_COLMAGIC = b"TFC\x01"


def _align8(n):
    return (n + 7) & ~7


def _decode_columnar(buf):
    """Rebuild a ColumnChunk from a fast-path frame: columns are numpy
    VIEWS over ``buf`` (owned by the returned arrays via .base) — zero
    further copies.  Every column starts 8-byte aligned (the producer
    pads), so int64/float64 views never take numpy's unaligned paths."""
    import numpy as np

    from tensorflowonspark_tpu import marker as _marker

    hlen = int.from_bytes(bytes(buf[4:8]), "little")
    hdr = pickle.loads(bytes(buf[8:8 + hlen]))
    spec, shapes, descrs = hdr[:3]
    meta = hdr[3] if len(hdr) > 3 else None
    off = _align8(8 + hlen)
    cols = []
    mv = memoryview(buf)
    for dtype_str, shape in descrs:
        dt = np.dtype(dtype_str)
        count = 1
        for s in shape:
            count *= s
        a = np.frombuffer(mv, dtype=dt, count=count, offset=off)
        cols.append(a.reshape(shape))
        off = _align8(off + a.nbytes)
    return _marker.ColumnChunk(spec, tuple(cols), shapes=shapes, meta=meta)


def _lock_path(name):
    import tempfile

    return os.path.join(
        tempfile.gettempdir(), f".tfosq{name.replace('/', '_')}.lock"
    )


def producer_active(name):
    """True while some producer holds the ring's exclusive producer flock.

    Lets a draining consumer distinguish "ring momentarily empty but a
    feeder is still mid-partition" from "truly no more data coming"
    without guessing from timeouts (the reference had to guess,
    TFNode.py:307-329; the flock makes the check race-free here)."""
    import fcntl

    try:
        f = open(_lock_path(name), "w")
    except OSError:
        return False
    try:
        fcntl.flock(f, fcntl.LOCK_EX | fcntl.LOCK_NB)
        fcntl.flock(f, fcntl.LOCK_UN)
        return False
    except OSError:
        return True
    finally:
        f.close()


class ShmQueue:
    """Producer or consumer endpoint of a named shm ring.

    The ring is single-producer/single-consumer; pass ``producer=True``
    when opening as a writer — an exclusive flock serializes producer
    sessions (e.g. concurrent feeder tasks on a multi-core Spark
    executor), matching the multi-producer safety of the manager queue
    it replaces."""

    def __init__(self, name, capacity=64 << 20, create=False,
                 open_timeout_ms=60000, producer=False,
                 producer_nonblock=False):
        lib = _native.load()
        if lib is None:
            raise RuntimeError("native library unavailable; ShmQueue disabled")
        self._lib = lib
        self.name = name
        self._lockf = None
        if producer and not create:
            import fcntl

            self._lockf = open(_lock_path(name), "w")
            if producer_nonblock:
                # dynamic-dispatch ring handover: the new owner retries
                # instead of wedging behind the old owner's session flock
                try:
                    fcntl.flock(self._lockf,
                                fcntl.LOCK_EX | fcntl.LOCK_NB)
                except OSError:
                    self._lockf.close()
                    self._lockf = None
                    raise BlockingIOError(
                        f"shm queue {name}: producer flock held by "
                        "another session") from None
            else:
                fcntl.flock(self._lockf, fcntl.LOCK_EX)
        if create:
            self._h = lib.shq_create(name.encode(), capacity)
        else:
            self._h = lib.shq_open(name.encode(), open_timeout_ms)
        if not self._h:
            if self._lockf:
                self._lockf.close()
            raise OSError(f"cannot {'create' if create else 'open'} shm queue {name}")

    def put_bytes(self, data: bytes, timeout_ms=-1):
        rc = self._lib.shq_push(self._h, data, len(data), timeout_ms)
        if rc == -1:
            raise TimeoutError(f"shm queue {self.name} full")
        if rc == -2:
            raise BrokenPipeError(f"shm queue {self.name} closed")
        if rc == -3:
            raise ValueError("message larger than ring capacity")

    def get_bytes(self, timeout_ms=-1):
        """Returns payload bytes (possibly b""), or None at EOF."""
        n = self._lib.shq_pop(self._h, timeout_ms)
        if n == -1:
            raise TimeoutError(f"shm queue {self.name} empty")
        if n == -2:
            return None  # closed and drained
        return ctypes.string_at(self._lib.shq_buffer(self._h), n) if n else b""

    def put(self, obj, timeout_ms=-1):
        """Push one object.  ColumnChunks with contiguous numeric columns
        take a scatter-gather fast path: a small pickled header plus the
        raw column bytes memcpy'd straight from the numpy buffers into
        the ring — ONE payload copy on the producer side, vs pickling the
        arrays into an intermediate bytes first.  Everything else (row
        lists, markers, None) rides classic pickle."""
        fast = self._put_columnar(obj, timeout_ms)
        if not fast:
            self.put_bytes(
                pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL),
                timeout_ms)

    def get(self, timeout_ms=-1, available=None):
        """Pop one object: ``wait`` until one is there, then ``read`` it.
        ``available()`` is called between the two, which is where the
        feed splits its timing: an empty ring is the producer's time,
        the copy and decode are the consumer's."""
        n = self.wait(timeout_ms)
        if available is not None:
            available()
        return None if n is None else self.read(n)

    def wait(self, timeout_ms=-1):
        """Block until a message is AVAILABLE, without consuming it:
        its length in bytes, or None once the ring is closed and
        drained; TimeoutError when it stays empty."""
        n = self._lib.shq_peek_len(self._h, timeout_ms)
        if n == -1:
            raise TimeoutError(f"shm queue {self.name} empty")
        return None if n == -2 else n

    def read(self, n):
        """Consume the message ``wait`` announced.  Fast-path messages
        are popped directly into a caller-owned buffer (one copy) and
        the columns come back as numpy VIEWS over it — no pickle, no
        further copies."""
        import numpy as np

        # np.empty, NOT bytearray: bytearray(n) zero-fills, which is
        # a full hidden extra write of the payload size per message
        buf = np.empty(n, np.uint8)
        if n:
            got = self._lib.shq_pop_into(
                self._h, ctypes.c_void_p(buf.ctypes.data))
        else:
            got = self._lib.shq_pop_into(self._h, None)
        if got != n:  # single-consumer contract violated
            raise RuntimeError(
                f"shm queue {self.name}: peeked {n} bytes but popped "
                f"{got} (concurrent consumer?)")
        if n >= 4 and bytes(buf[:4]) == _COLMAGIC:
            return _decode_columnar(buf)
        # loads() takes any bytes-like: no tobytes() copy of the
        # whole payload just to unpickle a row-list message
        return pickle.loads(memoryview(buf) if n else b"")

    def _put_columnar(self, obj, timeout_ms):
        """Scatter-gather push of a ColumnChunk; False when not eligible
        (non-chunk payload, object/non-contiguous columns) so put()
        falls back to pickle."""
        from tensorflowonspark_tpu import marker as _marker

        if not isinstance(obj, _marker.ColumnChunk):
            return False
        import numpy as np

        cols = obj.columns
        if not cols or any(
            not isinstance(a, np.ndarray) or a.dtype.hasobject
            or not a.flags.c_contiguous
            for a in cols
        ):
            return False
        header = pickle.dumps(
            (obj.spec, getattr(obj, "shapes", None),
             [(a.dtype.str, a.shape) for a in cols],
             getattr(obj, "meta", None)),
            protocol=pickle.HIGHEST_PROTOCOL)
        # pad so every column lands 8-byte aligned in the frame (the
        # consumer views them in place; unaligned int64/float64 views
        # would take numpy's slow paths on every message)
        pad8 = b"\0" * 8
        segs = [(_COLMAGIC, len(_COLMAGIC)),
                (len(header).to_bytes(4, "little"), 4),
                (header, len(header))]
        off = 8 + len(header)
        if off % 8:
            segs.append((pad8, 8 - off % 8))
        col_segs = []
        for a in cols:
            col_segs.append((a, a.nbytes))
            if a.nbytes % 8:
                col_segs.append((pad8, 8 - a.nbytes % 8))
        n = len(segs) + len(col_segs)
        bufs = (ctypes.c_void_p * n)()
        lens = (ctypes.c_uint64 * n)()
        keepalive = []
        for i, (s, ln) in enumerate(segs):
            b = ctypes.create_string_buffer(s, len(s))
            keepalive.append(b)
            bufs[i] = ctypes.addressof(b)
            lens[i] = ln
        pad_buf = ctypes.create_string_buffer(pad8, 8)
        for j, (a, ln) in enumerate(col_segs):
            if a is pad8:
                bufs[len(segs) + j] = ctypes.addressof(pad_buf)
            else:
                bufs[len(segs) + j] = a.ctypes.data
                keepalive.append(a)
            lens[len(segs) + j] = ln
        rc = self._lib.shq_push_iov(self._h, bufs, lens, n, timeout_ms)
        if rc == -1:
            raise TimeoutError(f"shm queue {self.name} full")
        if rc == -2:
            raise BrokenPipeError(f"shm queue {self.name} closed")
        if rc == -3:
            raise ValueError("message larger than ring capacity")
        return True

    def close_write(self):
        self._lib.shq_close_write(self._h)

    def qsize_bytes(self):
        return self._lib.shq_size(self._h)

    def close(self):
        if self._h:
            self._lib.shq_free(self._h)
            self._h = None
        if self._lockf:
            self._lockf.close()  # releases the producer flock
            self._lockf = None

    def __del__(self):
        try:
            self.close()
        except Exception:  # noqa: BLE001
            pass


def available():
    return _native.load() is not None
