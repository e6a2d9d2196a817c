"""Record IO: TFRecord files + tf.train.Example codec, native-accelerated.

Component parity (SURVEY.md §2.2 ⚙): the reference vendors the
tensorflow-hadoop jar for record-level TFRecord IO and does Example⇄Row
marshalling in Scala/JNI; here a C++ library (native/tfrecord.cpp) does
framing, crc32c, and Example wire encode/decode, loaded via ctypes with a
pure-Python fallback (pyimpl.py).  No TensorFlow dependency anywhere.

API:
    with TFRecordWriter(path) as w: w.write(b"...")
    for rec in TFRecordReader(path): ...
    encode_example({"x": ("float", [1.0])}) -> bytes
    decode_example(b) -> {"x": ("float", [1.0])}
"""

from __future__ import annotations

import ctypes
import os as _os

import numpy as _np

from tensorflowonspark_tpu.recordio import fs as _fs
from tensorflowonspark_tpu.recordio import native as _native
from tensorflowonspark_tpu.recordio import pyimpl as _py


class TFRecordWriter:
    """Writes TFRecord framing to any filesystem.

    Local paths go straight through the C library's buffered FILE* writer;
    remote URLs (gs://, hdfs://, s3://, memory://) are framed in memory by
    the C codec and flushed to the object store through fsspec on close
    (objects on these stores are immutable — a single terminal PUT is the
    native write pattern, not a defect of this path).
    """

    def __init__(self, path):
        self._lib = _native.load()
        self._h = self._mh = self._f = None
        self._remote_path = None
        if _fs.is_local(path):
            lp = _fs.local_path(path)
            if self._lib is not None:
                self._h = self._lib.tfr_writer_open(str(lp).encode())
                if not self._h:
                    raise IOError(f"cannot open {lp} for writing")
            else:
                self._f = open(lp, "wb")
        elif self._lib is not None:
            self._mh = self._lib.tfr_mem_writer_new()
            self._remote_path = str(path)
        else:
            self._f = _fs.open_file(path, "wb")

    def write(self, data: bytes):
        if self._h is not None:
            if self._lib.tfr_writer_write(self._h, data, len(data)) != 0:
                raise IOError("TFRecord write failed")
        elif self._mh is not None:
            self._lib.tfr_mem_writer_write(self._mh, data, len(data))
        else:
            _py.write_record(self._f, data)

    def close(self):
        if self._h is not None:
            self._lib.tfr_writer_close(self._h)
            self._h = None
        elif self._mh is not None:
            try:
                n = ctypes.c_uint64()
                p = self._lib.tfr_mem_writer_data(self._mh, ctypes.byref(n))
                _fs.write_bytes(self._remote_path,
                                ctypes.string_at(p, n.value) if n.value else b"")
            finally:
                self._lib.tfr_mem_writer_free(self._mh)
                self._mh = None
        elif self._f is not None:
            self._f.close()
            self._f = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class TFRecordReader:
    """Iterates raw record bytes from one TFRecord file on any filesystem."""

    def __init__(self, path):
        self._path = path
        self._lib = _native.load()

    def __iter__(self):
        if _fs.is_local(self._path):
            yield from self._iter_local()
        else:
            yield from self._iter_remote()

    def _iter_local(self):
        if self._lib is not None:
            h = self._lib.tfr_reader_open(
                str(_fs.local_path(self._path)).encode()
            )
            if not h:
                raise IOError(f"cannot open {self._path}")
            try:
                buf = ctypes.POINTER(ctypes.c_uint8)()
                while True:
                    n = self._lib.tfr_reader_next(h, ctypes.byref(buf))
                    if n == -1:
                        return  # clean EOF
                    if n < -1:
                        raise IOError(f"corrupt TFRecord ({n}) in {self._path}")
                    yield ctypes.string_at(buf, n) if n else b""
            finally:
                self._lib.tfr_reader_close(h)
        else:
            with open(_fs.local_path(self._path), "rb") as f:
                yield from _py.read_records(f)

    def _iter_remote(self):
        data = _fs.read_bytes(self._path)
        if self._lib is not None:
            h = self._lib.tfr_mem_reader_new(data, len(data))
            try:
                buf = ctypes.POINTER(ctypes.c_uint8)()
                while True:
                    n = self._lib.tfr_mem_reader_next(h, ctypes.byref(buf))
                    if n == -1:
                        return
                    if n < -1:
                        raise IOError(f"corrupt TFRecord ({n}) in {self._path}")
                    yield ctypes.string_at(buf, n) if n else b""
            finally:
                self._lib.tfr_mem_reader_free(h)
        else:
            import io

            yield from _py.read_records(io.BytesIO(data))


def encode_example(features: dict) -> bytes:
    """{name: (kind, values)} → serialized tf.train.Example."""
    lib = _native.load()
    if lib is None:
        return _py.encode_example(features)
    b = lib.exb_new()
    try:
        for name in sorted(features):
            kind, values = features[name]
            cname = name.encode()
            if kind == "int64":
                arr = (ctypes.c_int64 * len(values))(*values)
                lib.exb_add_int64(b, cname, arr, len(values))
            elif kind == "float":
                arr = (ctypes.c_float * len(values))(*values)
                lib.exb_add_float(b, cname, arr, len(values))
            elif kind == "bytes":
                bufs = (ctypes.c_char_p * len(values))(*values)
                lens = (ctypes.c_uint64 * len(values))(*[len(v) for v in values])
                lib.exb_add_bytes(b, cname, bufs, lens, len(values))
            else:
                raise ValueError(f"unknown feature kind {kind!r}")
        n = ctypes.c_uint64()
        p = lib.exb_serialize(b, ctypes.byref(n))
        return ctypes.string_at(p, n.value)
    finally:
        lib.exb_free(b)


def decode_example(data: bytes) -> dict:
    """Serialized tf.train.Example → {name: (kind, values)}."""
    lib = _native.load()
    if lib is None:
        return _py.decode_example(data)
    d = lib.exd_parse(data, len(data))
    if not d:
        raise ValueError("unparseable tf.train.Example")
    try:
        out = {}
        for i in range(lib.exd_num_features(d)):
            name = lib.exd_name(d, i).decode()
            kind = lib.exd_kind(d, i)
            cnt = lib.exd_value_count(d, i)
            if kind == 2:
                # bulk-copy the C value buffer: per-element ctypes
                # indexing costs ~100ns/value (~80us for a 784-float
                # feature); one string_at + frombuffer + tolist is ~2us
                p = lib.exd_floats(d, i)
                out[name] = ("float", _np.frombuffer(
                    ctypes.string_at(p, cnt * 4), _np.float32).tolist())
            elif kind == 3:
                p = lib.exd_int64s(d, i)
                out[name] = ("int64", _np.frombuffer(
                    ctypes.string_at(p, cnt * 8), _np.int64).tolist())
            elif kind == 1:
                vals = []
                n = ctypes.c_uint64()
                for j in range(cnt):
                    p = lib.exd_bytes(d, i, j, ctypes.byref(n))
                    vals.append(ctypes.string_at(p, n.value))
                out[name] = ("bytes", vals)
            else:
                out[name] = (None, [])
        return out
    finally:
        lib.exd_free(d)


def load_columnar(path):
    """Bulk-load one TFRecord file of tf.train.Examples into dense
    per-feature columns: {name: (kind, column)} where column is an
    ndarray [n] / [n, w] for float/int64 features and a list of bytes
    (or list of lists for multi-value) for bytes features.

    One C pass over the whole file — no per-value Python objects — the
    TPU-shaped replacement for the reference's per-row Example decode
    (DFUtil.scala:119-184): columns are ready for np slicing into device
    batches.  Requires a fixed schema across records (taken from the
    first record); ragged or schema-drifting files fall back to per-row
    ``decode_example`` with identical results.
    """
    if _fs.is_local(path) and _os.path.isdir(_fs.local_path(path)):
        # fopen(dir) "succeeds" with zero reads = silent empty result;
        # a directory here is a caller mix-up (use dfutil's loaders for
        # shard dirs)
        raise IsADirectoryError(
            f"{path} is a directory; pass a shard file (or use "
            "dfutil.load_tfrecords_columnar / iter_tfrecords_columnar "
            "for a shard dir)")
    lib = _native.load()
    if lib is None:
        return _columnar_fallback(path)
    if _fs.is_local(path):
        h = lib.tfr_load_columnar(str(_fs.local_path(path)).encode())
    else:
        data = _fs.read_bytes(path)
        h = lib.tfr_load_columnar_mem(data, len(data))
    if not h:
        raise MemoryError("columnar load allocation failed")
    try:
        if not lib.colb_ok(h):
            err = lib.colb_error(h).decode()
            # IO errors use these exact fixed strings (tfrecord.cpp); all
            # other errors are schema-shaped (ragged/drifting/repeated
            # features, named inside quotes) and take the per-row fallback
            if err == "cannot open file" or err.startswith(
                    "corrupt TFRecord framing"):
                raise IOError(f"{err}: {path}")
            return _columnar_fallback(path)
        n = lib.colb_num_rows(h)
        out = {}
        for i in range(lib.colb_num_features(h)):
            name = lib.colb_name(h, i).decode()
            kind = lib.colb_kind(h, i)
            w = lib.colb_width(h, i)
            if kind == 2:
                if n * w == 0:  # empty column: C buffer may be NULL
                    a = _np.zeros((n, w), _np.float32)
                else:
                    a = _np.ctypeslib.as_array(
                        lib.colb_floats(h, i), (n, w))  # view; one copy below
                out[name] = ("float", a[:, 0].copy() if w == 1 else a.copy())
            elif kind == 3:
                if n * w == 0:
                    a = _np.zeros((n, w), _np.int64)
                else:
                    a = _np.ctypeslib.as_array(lib.colb_int64s(h, i), (n, w))
                out[name] = ("int64", a[:, 0].copy() if w == 1 else a.copy())
            elif kind == 1:
                offs = _np.frombuffer(
                    ctypes.string_at(lib.colb_bytes_offsets(h, i),
                                     (n * w + 1) * 8), _np.uint64)
                blob = ctypes.string_at(lib.colb_bytes_blob(h, i),
                                        int(offs[-1])) if n * w else b""
                vals = [blob[int(offs[j]):int(offs[j + 1])]
                        for j in range(n * w)]
                if w == 1:
                    out[name] = ("bytes", vals)
                else:
                    out[name] = ("bytes", [vals[j * w:(j + 1) * w]
                                           for j in range(n)])
            else:
                out[name] = (None, [None] * n)
        return out
    finally:
        lib.colb_free(h)


def _columnar_fallback(path):
    """Per-row decode assembled into columns (pure-python / ragged path).
    Ragged numeric features stay lists-of-lists; fixed-width ones become
    the same arrays the native path produces."""
    names = None
    cols = {}
    kinds = {}
    n = 0
    for rec in TFRecordReader(path):
        row = decode_example(rec)
        if names is None:
            names = sorted(row)
            for name in names:
                kinds[name], _ = row[name]
                cols[name] = []
        elif set(row) != set(names):
            # surfacing drift beats silently dropping the extra features
            raise ValueError(
                f"record {n} features {sorted(row)} do not match the "
                f"first record's schema {names}; use the row-level "
                "load_tfrecords for schema-drifting files")
        for name in names:
            kind, values = row.get(name, (None, None))
            if values is None:
                raise ValueError(
                    f"record {n} is missing feature {name!r}")
            cols[name].append(values[0] if len(values) == 1 else values)
        n += 1
    out = {}
    for name in (names or []):
        vals = cols[name]
        kind = kinds[name]
        if kind in ("float", "int64"):
            widths = {1 if not isinstance(v, list) else len(v) for v in vals}
            if len(widths) == 1:
                dt = _np.float32 if kind == "float" else _np.int64
                out[name] = (kind, _np.asarray(vals, dt))
                continue
        out[name] = (kind, vals)
    return out
