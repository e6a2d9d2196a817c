"""ctypes bindings for the native record-IO / shm-queue library.

``libtfos_native.so`` is built from the checkout's ``native/`` sources by
``make`` on first use — again whenever a source is newer than it — and
loaded from there, nowhere else: what runs is what the sources say.  When there is no build (no sources beside
the package, no toolchain, a compile error) :func:`load` returns None
and call sites fall back to the pure-Python implementation (pyimpl.py) —
behavior is identical, speed is not; the build's own error is logged.
A run that needs the native path (the fed ring) checks for it and fails.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess

logger = logging.getLogger(__name__)

_LIB = None
_TRIED = False
_BUILT = None

_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "native")


def _stale():
    """make's own rule, checked without starting make (every process
    that loads the library would otherwise pay for a no-op run of it):
    a library is missing, or older than a source."""
    import glob

    libs = [os.path.join(_SRC, n)
            for n in ("libtfos_native.so", "_tfos_marshal.so")]
    if not all(os.path.exists(p) for p in libs):
        return True
    srcs = glob.glob(os.path.join(_SRC, "*.c*")) + [
        os.path.join(_SRC, "Makefile")]
    return min(map(os.path.getmtime, libs)) < max(map(os.path.getmtime, srcs))


def build():
    """Bring ``native/``'s libraries up to date with its sources (once
    per process); False when that is not possible here.  A library left
    over from other sources never outlives them."""
    global _BUILT
    if _BUILT is not None:
        return _BUILT
    _BUILT = False
    makefile = os.path.join(_SRC, "Makefile")
    if not os.path.exists(makefile):
        logger.warning("no native sources at %s", _SRC)
        return False
    try:
        import fcntl

        # an exclusive flock keeps N concurrently-starting executor
        # processes from interleaving builds; the losers of the race
        # find everything up to date
        with open(makefile) as lock:
            fcntl.flock(lock, fcntl.LOCK_EX)
            if _stale():
                subprocess.run(["make", "-C", _SRC], check=True,
                               capture_output=True, text=True)
        _BUILT = True
    except subprocess.CalledProcessError as e:
        logger.warning("native build failed:\n%s", e.stderr[-2000:])
    except OSError as e:  # no make, unwritable checkout
        logger.warning("native build impossible here: %s", e)
    return _BUILT


def load():
    """Build and load the native library; None if unavailable (call
    sites then use the pure-python IO)."""
    global _LIB, _TRIED
    if _LIB is not None or _TRIED:
        return _LIB
    _TRIED = True
    if build():
        try:
            _LIB = _bind(ctypes.CDLL(
                os.path.join(_SRC, "libtfos_native.so")))
            logger.info("loaded native record-io from %s", _SRC)
        except OSError as e:
            logger.warning("cannot load the native library: %s", e)
    if _LIB is None:
        logger.warning("using pure-python record IO")
    return _LIB


def _bind(lib):
    c = ctypes
    u8p = c.POINTER(c.c_uint8)

    lib.tfr_writer_open.restype = c.c_void_p
    lib.tfr_writer_open.argtypes = [c.c_char_p]
    lib.tfr_writer_write.restype = c.c_int
    lib.tfr_writer_write.argtypes = [c.c_void_p, c.c_char_p, c.c_uint64]
    lib.tfr_writer_close.restype = c.c_int
    lib.tfr_writer_close.argtypes = [c.c_void_p]

    lib.tfr_reader_open.restype = c.c_void_p
    lib.tfr_reader_open.argtypes = [c.c_char_p]
    lib.tfr_reader_next.restype = c.c_int64
    lib.tfr_reader_next.argtypes = [c.c_void_p, c.POINTER(u8p)]
    lib.tfr_reader_close.restype = c.c_int
    lib.tfr_reader_close.argtypes = [c.c_void_p]

    lib.exb_new.restype = c.c_void_p
    lib.exb_free.argtypes = [c.c_void_p]
    lib.exb_add_int64.argtypes = [c.c_void_p, c.c_char_p,
                                  c.POINTER(c.c_int64), c.c_int]
    lib.exb_add_float.argtypes = [c.c_void_p, c.c_char_p,
                                  c.POINTER(c.c_float), c.c_int]
    lib.exb_add_bytes.argtypes = [c.c_void_p, c.c_char_p,
                                  c.POINTER(c.c_char_p),
                                  c.POINTER(c.c_uint64), c.c_int]
    lib.exb_serialize.restype = u8p
    lib.exb_serialize.argtypes = [c.c_void_p, c.POINTER(c.c_uint64)]

    lib.exd_parse.restype = c.c_void_p
    lib.exd_parse.argtypes = [c.c_char_p, c.c_uint64]
    lib.exd_free.argtypes = [c.c_void_p]
    lib.exd_num_features.restype = c.c_int
    lib.exd_num_features.argtypes = [c.c_void_p]
    lib.exd_name.restype = c.c_char_p
    lib.exd_name.argtypes = [c.c_void_p, c.c_int]
    lib.exd_kind.restype = c.c_int
    lib.exd_kind.argtypes = [c.c_void_p, c.c_int]
    lib.exd_value_count.restype = c.c_int64
    lib.exd_value_count.argtypes = [c.c_void_p, c.c_int]
    lib.exd_floats.restype = c.POINTER(c.c_float)
    lib.exd_floats.argtypes = [c.c_void_p, c.c_int]
    lib.exd_int64s.restype = c.POINTER(c.c_int64)
    lib.exd_int64s.argtypes = [c.c_void_p, c.c_int]
    lib.exd_bytes.restype = u8p
    lib.exd_bytes.argtypes = [c.c_void_p, c.c_int, c.c_int,
                              c.POINTER(c.c_uint64)]

    lib.shq_create.restype = c.c_void_p
    lib.shq_create.argtypes = [c.c_char_p, c.c_uint64]
    lib.shq_open.restype = c.c_void_p
    lib.shq_open.argtypes = [c.c_char_p, c.c_int]
    lib.shq_reserve.restype = c.c_int64
    lib.shq_reserve.argtypes = [c.c_void_p, c.c_uint64, c.c_int]
    lib.shq_commit.restype = c.c_uint64
    lib.shq_commit.argtypes = [c.c_void_p]
    lib.shq_drop.argtypes = [c.c_void_p]
    lib.shq_wait_ns.restype = c.c_uint64
    lib.shq_wait_ns.argtypes = [c.c_void_p]
    lib.shq_wait_tail.restype = c.c_int
    lib.shq_wait_tail.argtypes = [c.c_void_p, c.c_uint64, c.c_int]
    lib.shq_peek_len.restype = c.c_int64
    lib.shq_peek_len.argtypes = [c.c_void_p, c.c_int]
    lib.shq_pop_into.restype = c.c_int64
    lib.shq_pop_into.argtypes = [c.c_void_p, c.c_void_p]
    lib.shq_data.restype = c.c_void_p
    lib.shq_data.argtypes = [c.c_void_p]
    lib.shq_capacity.restype = c.c_uint64
    lib.shq_capacity.argtypes = [c.c_void_p]
    lib.shq_close_write.argtypes = [c.c_void_p]
    lib.shq_size.restype = c.c_uint64
    lib.shq_size.argtypes = [c.c_void_p]
    lib.shq_free.argtypes = [c.c_void_p]

    lib.tfr_crc32c.restype = c.c_uint32
    lib.tfr_crc32c.argtypes = [c.c_char_p, c.c_uint64]

    # columnar bulk loader
    lib.tfr_load_columnar.restype = c.c_void_p
    lib.tfr_load_columnar.argtypes = [c.c_char_p]
    lib.tfr_load_columnar_mem.restype = c.c_void_p
    lib.tfr_load_columnar_mem.argtypes = [c.c_char_p, c.c_uint64]
    lib.colb_ok.restype = c.c_int
    lib.colb_ok.argtypes = [c.c_void_p]
    lib.colb_error.restype = c.c_char_p
    lib.colb_error.argtypes = [c.c_void_p]
    lib.colb_num_rows.restype = c.c_int64
    lib.colb_num_rows.argtypes = [c.c_void_p]
    lib.colb_num_features.restype = c.c_int
    lib.colb_num_features.argtypes = [c.c_void_p]
    lib.colb_name.restype = c.c_char_p
    lib.colb_name.argtypes = [c.c_void_p, c.c_int]
    lib.colb_kind.restype = c.c_int
    lib.colb_kind.argtypes = [c.c_void_p, c.c_int]
    lib.colb_width.restype = c.c_int64
    lib.colb_width.argtypes = [c.c_void_p, c.c_int]
    lib.colb_floats.restype = c.POINTER(c.c_float)
    lib.colb_floats.argtypes = [c.c_void_p, c.c_int]
    lib.colb_int64s.restype = c.POINTER(c.c_int64)
    lib.colb_int64s.argtypes = [c.c_void_p, c.c_int]
    lib.colb_bytes_blob.restype = u8p
    lib.colb_bytes_blob.argtypes = [c.c_void_p, c.c_int]
    lib.colb_bytes_offsets.restype = c.POINTER(c.c_uint64)
    lib.colb_bytes_offsets.argtypes = [c.c_void_p, c.c_int]
    lib.colb_free.argtypes = [c.c_void_p]

    # memory-buffer framing (remote-FS path: fsspec moves the bytes,
    # the C library still does framing + crc)
    lib.tfr_mem_writer_new.restype = c.c_void_p
    lib.tfr_mem_writer_write.restype = c.c_int
    lib.tfr_mem_writer_write.argtypes = [c.c_void_p, c.c_char_p, c.c_uint64]
    lib.tfr_mem_writer_data.restype = u8p
    lib.tfr_mem_writer_data.argtypes = [c.c_void_p, c.POINTER(c.c_uint64)]
    lib.tfr_mem_writer_clear.argtypes = [c.c_void_p]
    lib.tfr_mem_writer_free.argtypes = [c.c_void_p]
    lib.tfr_mem_reader_new.restype = c.c_void_p
    lib.tfr_mem_reader_new.argtypes = [c.c_char_p, c.c_uint64]
    lib.tfr_mem_reader_next.restype = c.c_int64
    lib.tfr_mem_reader_next.argtypes = [c.c_void_p, c.POINTER(u8p)]
    lib.tfr_mem_reader_free.argtypes = [c.c_void_p]
    return lib
