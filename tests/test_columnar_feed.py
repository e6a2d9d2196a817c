"""Columnar feed path: feeder-side encoding (node._ChunkEncoder) and
DataFeed's ColumnChunk consumption must be byte-equivalent to the row path
(the marshalling redesign of the reference's per-record pickle hop,
TFSparkNode.py:480-482)."""

import secrets

import numpy as np
import pytest
from feed_helpers import FakeMgr, patch_feeder, start_feeder

from tensorflowonspark_tpu import manager as tfmanager
from tensorflowonspark_tpu import marker, node
from tensorflowonspark_tpu.feed import DataFeed

ROWS = [([float(i), float(2 * i)], i % 7) for i in range(100)]


def test_encoder_numeric_rows_go_columnar():
    enc = node._ChunkEncoder()
    chunk = enc(list(ROWS))
    assert isinstance(chunk, marker.ColumnChunk)
    assert len(chunk) == len(ROWS)
    assert chunk.spec == [("d", 2), ("l", 0)]
    np.testing.assert_allclose(chunk.columns[0][3], [3.0, 6.0])
    assert chunk.columns[1][3] == 3


def test_encoder_string_rows_stay_rows():
    enc = node._ChunkEncoder()
    rows = [("hello", 1), ("world", 2)]
    assert enc(rows) is rows
    # and the encoder stays off for later chunks
    assert enc(list(ROWS)) is not None
    assert not isinstance(enc(list(ROWS)), marker.ColumnChunk)


def test_encoder_ragged_rows_fall_back():
    enc = node._ChunkEncoder()
    rows = [([1.0], 1), ([1.0, 2.0], 2)]
    out = enc(rows)
    assert out is rows


def test_encoder_disabled_by_env(monkeypatch):
    monkeypatch.setenv("TFOS_COLUMNAR_FEED", "0")
    enc = node._ChunkEncoder()
    assert enc(list(ROWS)) is not None
    assert not isinstance(enc(list(ROWS)), marker.ColumnChunk)


@pytest.fixture
def mgr():
    m = tfmanager.start(secrets.token_bytes(8), ["input", "output", "error"])
    yield m
    m.shutdown()


def _feed_chunks(mgr, chunks):
    q = mgr.get_queue("input")
    for c in chunks:
        q.put(c)
    q.put(None)


def _drain_batches(feed, batch_size):
    out = []
    while not feed.should_stop():
        out.append(feed.next_batch(batch_size))
    return out


def test_datafeed_columnar_mapping_equals_row_path(mgr):
    enc = node._ChunkEncoder()
    # batch size 16 deliberately misaligned with chunk size 24
    _feed_chunks(mgr, [enc(ROWS[i:i + 24]) for i in range(0, 100, 24)])
    feed = DataFeed(mgr, train_mode=True,
                    input_mapping={"x": "features", "y": "label"})
    batches = _drain_batches(feed, 16)
    xs, ys = [], []
    for b in batches:
        assert isinstance(b["features"], list)
        xs.extend(np.asarray(v) for v in b["features"])
        ys.extend(int(v) for v in b["label"])
    np.testing.assert_allclose(np.stack(xs), [r[0] for r in ROWS])
    assert ys == [r[1] for r in ROWS]


def test_datafeed_columnar_no_mapping_roundtrip(mgr):
    enc = node._ChunkEncoder()
    _feed_chunks(mgr, [enc(ROWS[:50]), enc(ROWS[50:])])
    feed = DataFeed(mgr, train_mode=True)
    records = []
    while not feed.should_stop():
        records.extend(feed.next_batch(13))
    assert len(records) == len(ROWS)
    for got, want in zip(records, ROWS):
        np.testing.assert_allclose(got[0], want[0])
        assert got[1] == want[1]


IMG_ROWS = [(np.full((4, 6, 3), i, np.uint8), i % 10) for i in range(64)]


def test_encoder_flattens_nd_image_fields():
    """n-D ndarray fields (images) go columnar as flattened width columns
    with the original shape carried in ColumnChunk.shapes — the wire
    format for the fed hot path (PERF.md 12k img/s np.stack wall)."""
    enc = node._ChunkEncoder()
    chunk = enc(list(IMG_ROWS[:32]))
    assert isinstance(chunk, marker.ColumnChunk)
    assert chunk.shapes == ((4, 6, 3), None)
    assert chunk.spec[0] == ("B", 4 * 6 * 3)
    assert chunk.columns[0].shape == (32, 72)
    np.testing.assert_array_equal(
        chunk.columns[0][5].reshape(4, 6, 3), IMG_ROWS[5][0])


def test_encoder_nd_shape_drift_falls_back_to_rows():
    enc = node._ChunkEncoder()
    assert isinstance(enc(list(IMG_ROWS[:8])), marker.ColumnChunk)
    drift = [(np.zeros((6, 4, 3), np.uint8), 1)] * 4  # transposed shape
    out = enc(drift)
    assert out is drift  # row path, not a silently mis-shaped column


def test_datafeed_nd_columnar_row_consumers_see_original_shape(mgr):
    enc = node._ChunkEncoder()
    _feed_chunks(mgr, [enc(list(IMG_ROWS[:40])), enc(list(IMG_ROWS[40:]))])
    feed = DataFeed(mgr, train_mode=True,
                    input_mapping={"image": "image", "label": "label"})
    got_imgs, got_labels = [], []
    for b in _drain_batches(feed, 16):
        for v in b["image"]:
            assert v.shape == (4, 6, 3)
            got_imgs.append(v)
        got_labels.extend(int(v) for v in b["label"])
    np.testing.assert_array_equal(
        np.stack(got_imgs), np.stack([r[0] for r in IMG_ROWS]))
    assert got_labels == [r[1] for r in IMG_ROWS]


def test_datafeed_nd_columnar_no_mapping_roundtrip(mgr):
    enc = node._ChunkEncoder()
    _feed_chunks(mgr, [enc(list(IMG_ROWS))])
    feed = DataFeed(mgr, train_mode=True)
    records = []
    while not feed.should_stop():
        records.extend(feed.next_batch(24))
    assert len(records) == len(IMG_ROWS)
    for got, want in zip(records, IMG_ROWS):
        assert got[0].shape == (4, 6, 3)
        np.testing.assert_array_equal(got[0], want[0])
        assert got[1] == want[1]


def test_next_batch_columns_dense_and_zero_copy(mgr):
    """Aligned chunk -> zero-copy dense batch; spanning chunks -> one
    concatenate; short tail returned as-is."""
    enc = node._ChunkEncoder()
    chunks = [enc(list(IMG_ROWS[:32])), enc(list(IMG_ROWS[32:56])),
              enc(list(IMG_ROWS[56:]))]
    _feed_chunks(mgr, chunks)
    feed = DataFeed(mgr, train_mode=True,
                    input_mapping={"image": "image", "label": "label"})

    b1 = feed.next_batch_columns(32)  # exactly chunk 1: zero copy
    assert b1["image"].shape == (32, 4, 6, 3)
    assert b1["image"].dtype == np.uint8  # narrow wire dtype preserved
    # a VIEW of the received chunk's column (reshape of a slice), not a
    # freshly stacked copy (the queue itself pickles, so identity with
    # the producer-side array is out of scope)
    assert b1["image"].base is not None
    np.testing.assert_array_equal(
        b1["image"], np.stack([r[0] for r in IMG_ROWS[:32]]))

    b2 = feed.next_batch_columns(32)  # spans chunks 2+3: one concat
    assert b2["image"].shape == (32, 4, 6, 3)
    np.testing.assert_array_equal(
        b2["image"], np.stack([r[0] for r in IMG_ROWS[32:]]))
    assert list(b2["label"]) == [r[1] for r in IMG_ROWS[32:]]

    tail = feed.next_batch_columns(32)  # end of feed: empty
    assert feed.should_stop() and len(tail["image"]) == 0


def test_next_batch_columns_row_chunk_fallback(mgr):
    """Non-columnar feeders (plain row lists) still work through the
    dense consumer, via per-segment np.stack."""
    _feed_chunks(mgr, [list(IMG_ROWS[:20]), list(IMG_ROWS[20:48])])
    feed = DataFeed(mgr, train_mode=True,
                    input_mapping={"image": "image", "label": "label"})
    b = feed.next_batch_columns(48)
    assert b["image"].shape == (48, 4, 6, 3)
    np.testing.assert_array_equal(
        b["image"], np.stack([r[0] for r in IMG_ROWS[:48]]))


def test_next_batch_columns_requires_mapping(mgr):
    feed = DataFeed(mgr, train_mode=True)
    with pytest.raises(ValueError, match="input_mapping"):
        feed.next_batch_columns(8)


def test_datafeed_mixed_row_and_columnar_chunks(mgr):
    enc = node._ChunkEncoder()
    _feed_chunks(mgr, [ROWS[:30], enc(ROWS[30:60]), ROWS[60:]])
    feed = DataFeed(mgr, train_mode=True,
                    input_mapping={"x": "features", "y": "label"})
    total = 0
    for b in _drain_batches(feed, 10):
        n = len(b["label"])
        assert len(b["features"]) == n
        total += n
    assert total == len(ROWS)


# -- frames encoded in place (node.train on the shm ring) --------------------

@pytest.mark.parametrize("record_bytes,capacity,limit,want", [
    (150_528 + 8, 256 << 20, 1024, 256),   # resnet50-fed: 38.5 MB frames
    (8 * 1024, 256 << 20, 1024, 1024),     # pythia-1.4b-train: unchanged
    (150_528 + 8, 64 << 20, 1024, 64),
    (80, 1 << 20, 100, 100),               # the chunk size caps it
    (80, 22 * 1024, 64, 16),
    (1 << 20, 1 << 20, 1024, 0),           # not one record fits: copy
])
def test_frame_records_derived_from_record_and_ring(
        record_bytes, capacity, limit, want):
    assert node._frame_records(record_bytes, capacity, limit) == want
    if want:  # a frame is at most a quarter of the ring
        assert want * record_bytes <= capacity // 4


@pytest.mark.parametrize("row,want", [
    ((np.zeros((224, 224, 3), np.uint8), 3), 150_528 + 8),
    ((np.zeros(2048, np.int32),), 8 * 1024),
    (([1.0, 2.0], 1), 24),
    (("text", 1), None),                   # the row path
])
def test_encoder_record_bytes(row, want):
    assert node._ChunkEncoder().record_bytes(row) == want


def test_encoder_fills_the_arrays_it_is_given():
    from tensorflowonspark_tpu.recordio import marshal

    enc = node._ChunkEncoder()
    given = []

    def alloc(spec, shapes, descrs):
        assert shapes == ((4, 6, 3), None)
        assert descrs == marshal.column_descrs(spec, 32)
        given.extend(np.full(s, 0xCD, d) for d, s in descrs)
        return given

    chunk = enc(list(IMG_ROWS[:32]), alloc)
    assert isinstance(chunk, marker.ColumnChunk)
    assert all(a is b for a, b in zip(chunk.columns, given))
    want = node._ChunkEncoder()(list(IMG_ROWS[:32]))
    assert chunk.spec == want.spec and chunk.shapes == want.shapes
    for a, b in zip(chunk.columns, want.columns):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)


def test_encoder_row_path_never_allocates():
    enc = node._ChunkEncoder()
    rows = [("hello", 1), ("world", 2)]

    def alloc(*a):
        raise AssertionError("no frame for a chunk that stays rows")

    assert enc(rows, alloc) is rows and enc.off
    assert enc(list(ROWS), alloc) is not None


def test_encoder_passes_on_what_alloc_raises():
    enc = node._ChunkEncoder()

    def alloc(*a):
        raise TimeoutError("ring full")

    with pytest.raises(TimeoutError):
        enc(list(ROWS), alloc)
    assert not enc.off  # the ring's trouble is not the data's


def _drain_ring(ring, n_records, box):
    chunks, got = [], 0
    while got < n_records:
        assert not (box["done"].is_set() and box["error"]), box["error"]
        c = ring.get(timeout_ms=10000)
        chunks.append(c)
        got += len(c)
    return chunks


def _ids_and_images(chunks):
    ids, imgs = [], []
    for c in chunks:
        if isinstance(c, marker.ColumnChunk):
            ids.extend(c.columns[1].tolist())
            imgs.extend(c.columns[0].reshape((-1,) + c.shapes[0]))
        else:
            ids.extend(r[1] for r in c)
            imgs.extend(r[0] for r in c)
    return ids, imgs


@pytest.fixture
def small_ring():
    from tensorflowonspark_tpu.recordio import shm

    if not shm.available():
        pytest.skip("no native lib")
    import os

    name = f"/tfosq-colfeed-{os.getpid()}"
    # 80-byte records (72 image bytes + the id): a quarter of 22 KiB less
    # the header's room holds 16 of them
    ring = shm.ShmQueue(name, capacity=22 * 1024, create=True)
    yield ring, FakeMgr({"shm_input": name, "state": "running"})
    ring.close()


def _id_rows(n, rng):
    return [(rng.integers(0, 256, (4, 6, 3), dtype=np.uint8), i)
            for i in range(n)]


def test_fed_partition_through_inplace_frames(small_ring, monkeypatch):
    """Frame (16) < chunk (64) < partition (200): twelve whole frames and
    a ragged last one, byte-identical and in order, none of them copied."""
    ring, mgr = small_ring
    calls = patch_feeder(monkeypatch, mgr, chunk_records=64, partition=4)
    rows = _id_rows(200, np.random.default_rng(11))
    t, box = start_feeder(rows)
    chunks = _drain_ring(ring, 200, box)
    assert box["done"].wait(10) and box["error"] is None
    assert all(isinstance(c, marker.ColumnChunk) for c in chunks)
    assert [len(c) for c in chunks] == [16] * 12 + [8]
    ids, imgs = _ids_and_images(chunks)
    assert ids == list(range(200))
    np.testing.assert_array_equal(np.stack(imgs),
                                  np.stack([r[0] for r in rows]))
    assert calls["done"] == [("input", 4)] and ring.qsize_bytes() == 0


@pytest.mark.parametrize("drift", ["shape", "value"])
def test_spec_drift_mid_frame_falls_to_the_row_path(
        small_ring, monkeypatch, drift):
    """Record 37 breaks the spec in the middle of the third frame — by
    shape (seen before the frame is reserved) or by value (seen while
    the reserved frame is being filled, so the reservation is dropped):
    that chunk and every later one travel as rows, at the chunk size,
    and no record is lost or repeated."""
    ring, mgr = small_ring
    patch_feeder(monkeypatch, mgr, chunk_records=64, partition=0)
    rows = _id_rows(200, np.random.default_rng(12))
    if drift == "shape":
        rows[37] = (np.zeros((6, 4, 3), np.uint8), 37)
    else:
        rows[37] = (rows[37][0], 37.5)  # a float under the int64 spec
    t, box = start_feeder(rows)
    chunks = _drain_ring(ring, 200, box)
    assert box["done"].wait(10) and box["error"] is None
    assert [len(c) for c in chunks] == [16, 16, 16, 64, 64, 24]
    assert [isinstance(c, marker.ColumnChunk) for c in chunks] \
        == [True, True, False, False, False, False]
    ids, imgs = _ids_and_images(chunks)
    assert ids == [r[1] for r in rows]
    for got, want in zip(imgs, rows):
        np.testing.assert_array_equal(got, want[0])
    assert ring.qsize_bytes() == 0


def test_inplace_frames_reach_datafeed_batches(small_ring, monkeypatch):
    """The whole path: feeder frames -> ring -> DataFeed dense batches of
    a size that divides neither the frame nor the chunk."""
    ring, mgr = small_ring
    patch_feeder(monkeypatch, mgr, chunk_records=64, partition=1)
    rows = _id_rows(150, np.random.default_rng(13))
    feed = DataFeed(mgr, train_mode=True,
                    input_mapping={"image": "image", "label": "label"})
    t, box = start_feeder(rows)
    ids, imgs = [], []
    while len(ids) < 150:
        b = feed.next_batch_columns(min(24, 150 - len(ids)))
        ids.extend(b["label"].tolist())
        imgs.append(np.array(b["image"]))
    assert box["done"].wait(10) and box["error"] is None
    assert ids == list(range(150))
    np.testing.assert_array_equal(np.concatenate(imgs),
                                  np.stack([r[0] for r in rows]))
