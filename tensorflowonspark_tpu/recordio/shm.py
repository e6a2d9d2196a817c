"""Shared-memory ring queue binding (native/shmqueue.cpp).

The fast same-host feed path: the feeder writes record chunks into a
SPSC byte ring in POSIX shm; the training process pops them with no
per-record IPC and no manager round-trips.  Used by the feed layer as
an accelerated transport when the native library is present; the manager
queue remains the control/compat path.

The producer has ONE primitive, reserve / commit: ``reserve`` waits for
room and hands out ring memory, ``commit`` publishes it.  ``put`` copies
an object in through it; the feeder encodes its frames straight into the
views ``reserve_columns`` returns (``node.train``).
"""

from __future__ import annotations

import ctypes
import os
import pickle

import numpy as np

from tensorflowonspark_tpu.recordio import native as _native

# fast-path frame magic: cannot collide with a pickle stream (protocol 2+
# starts with b'\x80'), so legacy and columnar messages share one ring
_COLMAGIC = b"TFC\x01"


# a message in the ring: this many bytes of length word, then the payload
# (native/shmqueue.cpp), so that a payload starts 8-byte aligned
_MSG_HEADER = 8


def _align8(n):
    return (n + 7) & ~7


def _nbytes(descr):
    dtype_str, shape = descr
    return int(np.dtype(dtype_str).itemsize * np.prod(shape, dtype=np.int64))


def _frame_layout(spec, shapes, descrs, meta=None):
    """THE layout of a columnar frame, for the producer that copies
    columns in (``put``) and the one that fills them in place
    (``reserve_columns``): magic, a small pickled header, then each
    column of ``descrs`` (``(dtype_str, shape)``) at its 8-aligned
    offset, so the consumer's views never take numpy's unaligned paths.
    Returns ``(header_bytes, column_offsets, total_bytes)``."""
    hdr = pickle.dumps((spec, shapes, list(descrs), meta),
                       protocol=pickle.HIGHEST_PROTOCOL)
    header = _COLMAGIC + len(hdr).to_bytes(4, "little") + hdr
    offsets, end = [], len(header)
    for d in descrs:
        offsets.append(_align8(end))
        end = offsets[-1] + _nbytes(d)
    return header, offsets, end


def _decode_columnar(buf):
    """Rebuild a ColumnChunk from a columnar frame (``_frame_layout``):
    columns are numpy VIEWS over ``buf`` (owned by the returned arrays
    via .base) — zero further copies."""
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

        self.capacity = lib.shq_capacity(self._h)
        # the ring's memory, for the producer to write through
        self._mem = np.ctypeslib.as_array(
            ctypes.cast(lib.shq_data(self._h),
                        ctypes.POINTER(ctypes.c_uint8)),
            shape=(self.capacity,))

    # -- producer: reserve, write, commit -----------------------------------

    def _reserve(self, nbytes, timeout_ms):
        """Wait for room for one message of ``nbytes`` and reserve it:
        the payload's offset in the ring.  Nothing is published before
        ``commit``; a message of more than half the ring wraps."""
        off = self._lib.shq_reserve(self._h, nbytes, timeout_ms)
        if off == -1:
            raise TimeoutError(f"shm queue {self.name} full")
        if off == -2:
            raise BrokenPipeError(f"shm queue {self.name} closed")
        if off == -3:
            raise ValueError("message larger than ring capacity")
        return off

    def _write(self, off, data):
        """Copy a bytes-like into the ring at ``off``, in two parts
        where a message wraps."""
        src = np.frombuffer(data, np.uint8)
        first = min(src.size, self.capacity - off)
        self._mem[off:off + first] = src[:first]
        if first < src.size:
            self._mem[:src.size - first] = src[first:]

    @property
    def room_wait_s(self):
        """Seconds this endpoint's reservations have waited for room."""
        return self._lib.shq_wait_ns(self._h) / 1e9

    def reserve(self, nbytes, timeout_ms=-1):
        """A writable ``uint8`` VIEW of ring memory for one message of
        ``nbytes``, once there is contiguous room for it (at most half
        the ring; a message that would straddle the ring's end starts at
        offset 0 behind a skip word).  Fill it, then ``commit()``; until
        then the consumer sees nothing, and ``drop()`` or the next
        reservation forgets it.  The view dies with this endpoint."""
        if _align8(_MSG_HEADER + nbytes) * 2 > self.capacity:
            raise ValueError(
                f"a {nbytes}-byte frame cannot be contiguous in a ring of "
                f"{self.capacity} bytes (at most half of it)")
        off = self._reserve(nbytes, timeout_ms)
        return self._mem[off:off + nbytes]

    def reserve_columns(self, spec, shapes, descrs, meta=None,
                        timeout_ms=-1):
        """Reserve one columnar frame and return its columns as arrays
        over ring memory, for the producer to fill in place: the layout
        ``_decode_columnar`` reads, with no copy in between."""
        header, offsets, total = _frame_layout(spec, shapes, descrs, meta)
        frame = self.reserve(total, timeout_ms)
        frame[:len(header)] = np.frombuffer(header, np.uint8)
        return tuple(
            frame[o:o + _nbytes(d)].view(d[0]).reshape(d[1])
            for o, d in zip(offsets, descrs))

    def commit(self):
        """Publish the reservation.  Returns the ring's free-running
        position behind it, for ``wait_consumed``."""
        return self._lib.shq_commit(self._h)

    def drop(self):
        """Forget the reservation: nothing of it is published."""
        self._lib.shq_drop(self._h)

    def wait_consumed(self, pos, timeout_ms=-1):
        """Block until the consumer has taken everything up to ``pos``
        (what ``commit`` returned), whatever was written behind it;
        TimeoutError otherwise."""
        if self._lib.shq_wait_tail(self._h, pos, timeout_ms) != 0:
            raise TimeoutError(
                f"shm queue {self.name}: not consumed up to {pos}")

    def put_bytes(self, data: bytes, timeout_ms=-1):
        self._write(self._reserve(len(data), timeout_ms), data)
        return self.commit()

    def put(self, obj, timeout_ms=-1):
        """Push one object.  ColumnChunks with contiguous numeric columns
        travel as a columnar frame: a small pickled header plus the raw
        column bytes copied straight from the numpy buffers into the
        ring — ONE payload copy on the producer side, vs pickling the
        arrays into an intermediate bytes first.  Everything else (row
        lists, markers, None) rides classic pickle."""
        from tensorflowonspark_tpu import marker as _marker

        cols = getattr(obj, "columns", None)
        if not isinstance(obj, _marker.ColumnChunk) or not cols or any(
            not isinstance(a, np.ndarray) or a.dtype.hasobject
            or not a.flags.c_contiguous
            for a in cols
        ):
            return self.put_bytes(
                pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL),
                timeout_ms)
        header, offsets, total = _frame_layout(
            obj.spec, getattr(obj, "shapes", None),
            [(a.dtype.str, a.shape) for a in cols],
            getattr(obj, "meta", None))
        base = self._reserve(total, timeout_ms)
        self._write(base, header)
        for o, a in zip(offsets, cols):
            self._write((base + o) % self.capacity, a.reshape(-1))
        return self.commit()

    # -- consumer -----------------------------------------------------------

    def get_bytes(self, timeout_ms=-1):
        """Returns payload bytes (possibly b""), or None at EOF."""
        n = self.wait(timeout_ms)
        return None if n is None else self._pop(n).tobytes()

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

    def _pop(self, n):
        """Consume the message ``wait`` announced into a buffer of its
        own: the consumer's one copy."""
        # np.empty, NOT bytearray: bytearray(n) zero-fills, which is
        # a full hidden extra write of the payload size per message
        buf = np.empty(n, np.uint8)
        got = self._lib.shq_pop_into(
            self._h, ctypes.c_void_p(buf.ctypes.data) if n else None)
        if got != n:  # single-consumer contract violated
            raise RuntimeError(
                f"shm queue {self.name}: peeked {n} bytes but popped "
                f"{got} (concurrent consumer?)")
        return buf

    def read(self, n):
        """Consume the message ``wait`` announced.  A columnar frame's
        columns come back as numpy VIEWS over the one buffer it was
        popped into — no pickle, no further copies."""
        buf = self._pop(n)
        if n >= 4 and bytes(buf[:4]) == _COLMAGIC:
            return _decode_columnar(buf)
        # loads() takes any bytes-like: no tobytes() copy of the
        # whole payload just to unpickle a row-list message
        return pickle.loads(memoryview(buf) if n else b"")

    def close_write(self):
        self._lib.shq_close_write(self._h)

    def qsize_bytes(self):
        return self._lib.shq_size(self._h)

    def close(self):
        if self._h:
            self._mem = None  # the mapping goes: no view may outlive it
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
