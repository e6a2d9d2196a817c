"""Shared-memory ring queue tests: cross-process, wrap-around, EOF."""

import multiprocessing as mp
import os
import time

import pytest

from tensorflowonspark_tpu.recordio import shm

pytestmark = pytest.mark.skipif(not shm.available(), reason="no native lib")


def test_basic_roundtrip():
    q = shm.ShmQueue(f"/tfosq-test-{os.getpid()}-a", capacity=1 << 16, create=True)
    try:
        q.put({"x": 1, "data": b"abc"})
        q.put_bytes(b"raw")
        q.put_bytes(b"")  # empty payload is data, not EOF
        assert q.get() == {"x": 1, "data": b"abc"}
        assert q.get_bytes() == b"raw"
        assert q.get_bytes() == b""
        q.close_write()
        assert q.get() is None  # EOF after close + drain
    finally:
        q.close()


def test_wraparound_many_messages():
    q = shm.ShmQueue(f"/tfosq-test-{os.getpid()}-b", capacity=1 << 12, create=True)
    try:
        payload = b"z" * 500
        for i in range(100):  # far more data than capacity; interleave
            q.put_bytes(payload + str(i).encode(), timeout_ms=1000)
            got = q.get_bytes(timeout_ms=1000)
            assert got == payload + str(i).encode()
    finally:
        q.close()


def test_full_queue_times_out():
    q = shm.ShmQueue(f"/tfosq-test-{os.getpid()}-c", capacity=1 << 12, create=True)
    try:
        with pytest.raises(ValueError):
            q.put_bytes(b"x" * (1 << 13))  # bigger than ring
        q.put_bytes(b"x" * 3000)
        with pytest.raises(TimeoutError):
            q.put_bytes(b"y" * 3000, timeout_ms=100)
    finally:
        q.close()


def _producer(name, n):
    q = shm.ShmQueue(name, create=False)
    for i in range(n):
        q.put_bytes(b"msg-%06d" % i)
    q.close_write()
    q.close()


def test_cross_process_stream():
    name = f"/tfosq-test-{os.getpid()}-d"
    q = shm.ShmQueue(name, capacity=1 << 14, create=True)
    try:
        n = 5000
        ctx = mp.get_context("spawn")
        p = ctx.Process(target=_producer, args=(name, n))
        p.start()
        got = 0
        while True:
            data = q.get_bytes(timeout_ms=30000)
            if data is None:
                break
            assert data == b"msg-%06d" % got
            got += 1
        assert got == n
        p.join(10)
        assert p.exitcode == 0
    finally:
        q.close()


def _make_chunk(n=24, hw=6):
    import numpy as np

    from tensorflowonspark_tpu import node as tfnode

    rng = np.random.default_rng(0)
    rows = [(rng.integers(0, 256, (hw, hw, 3), dtype=np.uint8), int(i))
            for i in range(n)]
    enc = tfnode._ChunkEncoder()
    chunk = enc(rows)
    from tensorflowonspark_tpu import marker

    assert isinstance(chunk, marker.ColumnChunk)  # precondition
    return rows, chunk


def test_columnar_fast_path_roundtrip():
    """The columnar wire (put -> reserve, copy, commit -> TFC frame
    -> shq_peek_len/shq_pop_into -> _decode_columnar): exact bytes back,
    shapes metadata intact, every column 8-byte ALIGNED (views over the
    popped buffer must not hit numpy's unaligned paths), and legacy
    pickled messages coexist on the same ring."""
    import numpy as np

    from tensorflowonspark_tpu import marker

    q = shm.ShmQueue(f"/tfosq-test-{os.getpid()}-f", capacity=1 << 22,
                     create=True)
    try:
        rows, chunk = _make_chunk()
        q.put(chunk)
        q.put(["legacy", ("row", 1)])     # classic pickle, same ring
        q.put(marker.EndPartition())
        q.put(None)

        got = q.get(timeout_ms=5000)
        assert isinstance(got, marker.ColumnChunk)
        assert got.spec == chunk.spec and got.shapes == chunk.shapes
        for a, b in zip(got.columns, chunk.columns):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(a, b)
            assert a.ctypes.data % 8 == 0, "column view not 8-byte aligned"
        # views share one buffer (zero-copy decode), not fresh copies
        assert got.columns[0].base is not None

        assert q.get(timeout_ms=5000) == ["legacy", ("row", 1)]
        assert isinstance(q.get(timeout_ms=5000), marker.EndPartition)
        assert q.get(timeout_ms=5000) is None  # classic-pickle None
    finally:
        q.close()


def test_columnar_fast_path_wraparound_stream():
    """Many columnar frames through a ring smaller than the total volume
    (wrap-around of the ring) — every frame decodes exactly."""
    import numpy as np

    q = shm.ShmQueue(f"/tfosq-test-{os.getpid()}-g", capacity=1 << 16,
                     create=True)
    try:
        _, chunk = _make_chunk(n=12, hw=4)
        for i in range(200):
            q.put(chunk, timeout_ms=2000)
            got = q.get(timeout_ms=2000)
            for a, b in zip(got.columns, chunk.columns):
                np.testing.assert_array_equal(a, b)
    finally:
        q.close()


def _columnar_producer(name, n):
    q = shm.ShmQueue(name, create=False, producer=True)
    _, chunk = _make_chunk(n=16, hw=5)
    for _ in range(n):
        q.put(chunk, timeout_ms=30000)
    q.put(None)
    q.close_write()
    q.close()


def test_columnar_cross_process_stream():
    """Producer process pushes ColumnChunks as columnar frames; this
    process decodes them — the exact transport the fed bench lane uses."""
    import numpy as np

    name = f"/tfosq-test-{os.getpid()}-h"
    q = shm.ShmQueue(name, capacity=1 << 20, create=True)
    try:
        ctx = mp.get_context("spawn")
        p = ctx.Process(target=_columnar_producer, args=(name, 50))
        p.start()
        _, want = _make_chunk(n=16, hw=5)
        got_n = 0
        while True:
            item = q.get(timeout_ms=30000)
            if item is None:
                break
            for a, b in zip(item.columns, want.columns):
                np.testing.assert_array_equal(a, b)
            got_n += 1
        assert got_n == 50
        p.join(10)
        assert p.exitcode == 0
    finally:
        q.close()


def test_throughput_smoke():
    """The ring should clear 100 MB/s same-process (sanity, not a
    bench — real hardware does GB/s).  Best-of-3: a single scheduler
    stall on a loaded box must not flake a functional suite."""
    q = shm.ShmQueue(f"/tfosq-test-{os.getpid()}-e", capacity=64 << 20, create=True)
    try:
        chunk = b"x" * (1 << 20)
        best = 0.0
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(64):
                q.put_bytes(chunk)
                q.get_bytes()
            dt = time.perf_counter() - t0
            best = max(best, 64 / dt)
            if best > 100:
                break
        assert best > 100, f"shm ring too slow: {best:.0f} MB/s"
    finally:
        q.close()


# -- reserve / commit: the producer's one primitive --------------------------

def _offset_in_ring(q, view):
    return view.ctypes.data - q._mem.ctypes.data


def test_reserve_write_commit_roundtrips_as_views():
    """reserve() hands out ring memory itself (a writable view, nothing
    copied on the way in), and nothing is visible before commit()."""
    import numpy as np

    q = shm.ShmQueue(f"/tfosq-test-{os.getpid()}-r1", capacity=1 << 14,
                     create=True)
    try:
        view = q.reserve(1000)
        assert isinstance(view, np.ndarray) and view.dtype == np.uint8
        assert view.shape == (1000,) and view.flags.writeable
        assert np.shares_memory(view, q._mem)
        assert view.ctypes.data % 8 == 0, "payload not 8-byte aligned"
        payload = np.arange(1000, dtype=np.uint64).astype(np.uint8)
        view[:] = payload
        assert q.qsize_bytes() == 0
        with pytest.raises(TimeoutError):
            q.get_bytes(timeout_ms=50)
        pos = q.commit()
        assert pos == q.qsize_bytes() > 1000
        assert q.get_bytes(timeout_ms=1000) == payload.tobytes()
        q.wait_consumed(pos, timeout_ms=0)  # the consumer is past it
    finally:
        q.close()


def test_dropped_reservation_publishes_nothing():
    q = shm.ShmQueue(f"/tfosq-test-{os.getpid()}-r2", capacity=1 << 12,
                     create=True)
    try:
        q.reserve(700)[:] = 1
        q.drop()
        assert q.commit() == 0 and q.qsize_bytes() == 0
        q.reserve(300)[:] = 2      # forgotten by the next reservation
        q.reserve(200)[:] = 3
        q.commit()
        assert q.get_bytes(timeout_ms=1000) == b"\x03" * 200
        assert q.qsize_bytes() == 0
        with pytest.raises(TimeoutError):
            q.get_bytes(timeout_ms=50)
    finally:
        q.close()


@pytest.mark.parametrize("nbytes", [1 << 13, (1 << 12) - 8, (1 << 11) + 1])
def test_reserve_larger_than_the_ring_can_view_raises(nbytes):
    """A view cannot wrap: reserve() refuses what is larger than the
    ring, and what is larger than the half of it that is always free in
    one piece (put() copies such a message in, in two parts)."""
    q = shm.ShmQueue(f"/tfosq-test-{os.getpid()}-r3", capacity=1 << 12,
                     create=True)
    try:
        with pytest.raises(ValueError):
            q.reserve(nbytes)
        assert q.qsize_bytes() == 0
        if nbytes < (1 << 12) - 16:
            q.put_bytes(b"w" * nbytes)  # the copying path may wrap
            assert q.get_bytes(timeout_ms=1000) == b"w" * nbytes
    finally:
        q.close()


def test_frame_that_would_straddle_the_end_takes_the_skip_word():
    """Frames of 1,000 bytes in a 4 KiB ring: the fifth would straddle
    the end, so a skip word goes there and the frame starts the next
    lap; the consumer follows it.  Three laps and more, every frame one
    contiguous view."""
    import numpy as np

    cap = 1 << 12
    q = shm.ShmQueue(f"/tfosq-test-{os.getpid()}-r4", capacity=cap,
                     create=True)
    try:
        offsets, laps = [], 0
        for i in range(40):
            view = q.reserve(1000, timeout_ms=1000)
            off = _offset_in_ring(q, view)
            assert 0 <= off and off + 1000 <= cap  # never wraps
            if offsets and off < offsets[-1]:
                assert off == 8  # lap start, behind the length word
                laps += 1
            offsets.append(off)
            view[:] = i
            q.commit()
            if i % 2:  # two in flight, so tail and head both cross the end
                for j in (i - 1, i):
                    got = np.frombuffer(q.get_bytes(timeout_ms=1000),
                                        np.uint8)
                    assert got.size == 1000 and (got == j).all()
        assert laps >= 3
        assert q.qsize_bytes() == 0
    finally:
        q.close()


def _frame_producer(name, n, sizes):
    import numpy as np

    q = shm.ShmQueue(name, create=False, producer=True)
    for i in range(n):
        view = q.reserve(sizes[i % len(sizes)], timeout_ms=30000)
        view[:] = i % 251
        view[:8] = np.frombuffer(np.int64(i).tobytes(), np.uint8)
        q.commit()
    q.close_write()
    q.close()


def test_skip_word_across_processes_and_laps():
    """Two processes, frames of mixed sizes through a ring a fraction of
    their volume: the consumer follows every skip word, in order."""
    import numpy as np

    name = f"/tfosq-test-{os.getpid()}-r5"
    cap = 1 << 14
    sizes = [5000, 1200, 7000, 3100, 64]   # several straddle per lap
    n = 400
    q = shm.ShmQueue(name, capacity=cap, create=True)
    try:
        ctx = mp.get_context("spawn")
        p = ctx.Process(target=_frame_producer, args=(name, n, sizes))
        p.start()
        total = 0
        for i in range(n):
            got = np.frombuffer(q.get_bytes(timeout_ms=30000), np.uint8)
            assert got.size == sizes[i % len(sizes)]
            assert int(got[:8].view(np.int64)[0]) == i
            assert (got[8:] == i % 251).all()
            total += got.size
        assert q.get_bytes(timeout_ms=30000) is None  # EOF
        assert total >= 3 * cap
        p.join(10)
        assert p.exitcode == 0
    finally:
        q.close()


def test_put_and_inplace_frames_interleave_in_order():
    """Pickled objects, copied ColumnChunks and frames encoded in place
    share one ring, and come out in the order they went in."""
    import numpy as np

    from tensorflowonspark_tpu import marker
    from tensorflowonspark_tpu.recordio import marshal

    q = shm.ShmQueue(f"/tfosq-test-{os.getpid()}-r6", capacity=1 << 15,
                     create=True)
    try:
        rows, chunk = _make_chunk(n=8, hw=4)
        spec, shapes = chunk.spec, chunk.shapes
        for lap in range(12):
            q.put({"lap": lap})
            cols = q.reserve_columns(
                spec, shapes, marshal.column_descrs(spec, len(rows)),
                timeout_ms=1000)
            for dst, src in zip(cols, chunk.columns):
                assert np.shares_memory(dst, q._mem)
                dst[...] = src
            q.commit()
            q.put(chunk)
            q.put(marker.EndPartition())

            assert q.get(timeout_ms=1000) == {"lap": lap}
            for _ in range(2):  # in place, then copied: the same frame
                got = q.get(timeout_ms=1000)
                assert isinstance(got, marker.ColumnChunk)
                assert got.spec == spec and got.shapes == shapes
                for a, b in zip(got.columns, chunk.columns):
                    assert a.dtype == b.dtype and a.ctypes.data % 8 == 0
                    np.testing.assert_array_equal(a, b)
            assert isinstance(q.get(timeout_ms=1000), marker.EndPartition)
        assert q.qsize_bytes() == 0
    finally:
        q.close()


def test_wait_consumed_ignores_what_a_later_producer_wrote():
    q = shm.ShmQueue(f"/tfosq-test-{os.getpid()}-r7", capacity=1 << 12,
                     create=True)
    try:
        pos = q.put(["mine"])
        q.put(["later"])
        with pytest.raises(TimeoutError):
            q.wait_consumed(pos, timeout_ms=50)
        assert q.get(timeout_ms=1000) == ["mine"]
        q.wait_consumed(pos, timeout_ms=1000)
        assert q.qsize_bytes() > 0  # the later message is still there
        assert q.room_wait_s == 0.0  # nobody ever waited for room
    finally:
        q.close()
