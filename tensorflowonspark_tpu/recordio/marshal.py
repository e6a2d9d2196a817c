"""Row-batch ⇄ typed-column marshalling, native when available.

Parity target: the reference's JVM marshalling layer
(TFModel.scala:51-239 batch2tensors/tensors2batch), where the per-dtype
conversion between rows and dense tensors runs in compiled code.  Here
the compiled path is the ``_tfos_marshal`` CPython extension
(native/marshal.c); a numpy fallback implements identical semantics so
behavior does not depend on the native build.

Dtype codes (mirror of the reference's supported SQL type matrix):
  '?' bool  'i' int32  'l' int64  'f' float32  'd' float64  'O' object
A column spec entry is ``(code, width)``: width 0 for scalar columns,
w>0 for fixed-length sequence columns (shape [n, w]).
"""

from __future__ import annotations

import importlib.machinery
import importlib.util
import os

import numpy as np

_ext = None
_ext_tried = False

_CODE_TO_DTYPE = {"?": np.bool_, "i": np.int32, "l": np.int64,
                  "f": np.float32, "d": np.float64,
                  # narrow integer columns (image bytes!): keep the
                  # native dtype on the wire instead of upcasting to
                  # int32 — a 224x224x3 uint8 image must travel as 147KB,
                  # not 588KB
                  "b": np.int8, "B": np.uint8,
                  "h": np.int16, "H": np.uint16}

# codes the C extension's per-element fill loop understands; narrow
# codes deliberately stay on the numpy path — their columns come from
# ndarray rows where one bulk np.asarray copy beats per-element boxing
_EXT_CODES = "?ilfd"

# dtypes the C reconstruction loop (columns_to_rows) can read back —
# exactly the buffer formats its format_code/value_from switch handles
_EXT_OUT_DTYPES = frozenset(
    np.dtype(t) for t in (np.bool_, np.int8, np.int32, np.int64,
                          np.float32, np.float64))


def _load_ext():
    global _ext, _ext_tried
    if _ext_tried:
        return _ext
    _ext_tried = True
    if os.environ.get("TFOS_NATIVE_MARSHAL", "1") == "0":
        return None
    from tensorflowonspark_tpu.recordio import native

    path = os.path.join(native._SRC, "_tfos_marshal.so")
    if not native.build() or not os.path.exists(path):
        return None
    try:
        loader = importlib.machinery.ExtensionFileLoader("_tfos_marshal", path)
        spec = importlib.util.spec_from_loader("_tfos_marshal", loader)
        mod = importlib.util.module_from_spec(spec)
        loader.exec_module(mod)
        _ext = mod
    except Exception:  # noqa: BLE001 - fall back to numpy
        _ext = None
    return _ext


def native_available():
    return _load_ext() is not None


def _ndarray_code(dtype):
    """Spec code for a numpy dtype (exact-width for narrow ints so image
    bytes never upcast on the wire; int8 'b' must NOT collide with bool
    '?', uint64 does not fit int64)."""
    if dtype.kind == "b":
        return "?"
    if dtype.kind == "i":
        return {1: "b", 2: "h", 4: "i"}.get(dtype.itemsize, "l")
    if dtype.kind == "u":
        if dtype.itemsize >= 8:
            raise ValueError("uint64 columns do not fit the int64 spec")
        # unsigned widths widen one step only where exactness demands it:
        # uint8 'B' / uint16 'H' are exact; uint32 needs int64
        return {1: "B", 2: "H"}.get(dtype.itemsize, "l")
    if dtype.kind == "f":
        return "f" if dtype.itemsize <= 4 else "d"
    raise ValueError(f"unsupported ndarray dtype {dtype}")


def infer_spec(row):
    """Column spec from one example row (the schema-less path; the CLI's
    schema_hint translates to an explicit spec via schema_to_spec)."""
    spec = []
    for v in row:
        if isinstance(v, (bool, np.bool_)):
            spec.append(("?", 0))
        elif isinstance(v, (int, np.integer)):
            spec.append(("l", 0))
        elif isinstance(v, (float, np.floating)):
            spec.append(("d", 0))
        elif isinstance(v, (bytes, str)):
            spec.append(("O", 0))
        elif isinstance(v, np.ndarray):
            if v.ndim != 1:
                raise ValueError(
                    f"spec supports 1-D array columns, got shape {v.shape}"
                )
            spec.append((_ndarray_code(v.dtype), len(v)))
        elif isinstance(v, (list, tuple)):
            if not v:
                raise ValueError("cannot infer dtype of empty sequence column")
            inner = v[0]
            if isinstance(inner, (bool, np.bool_)):
                spec.append(("?", len(v)))
            elif isinstance(inner, (int, np.integer)):
                spec.append(("l", len(v)))
            elif isinstance(inner, (float, np.floating)):
                spec.append(("d", len(v)))
            elif isinstance(inner, (bytes, str)):
                spec.append(("O", len(v)))
            else:
                raise ValueError(f"unsupported sequence element: {type(inner)}")
        else:
            raise ValueError(f"unsupported column value: {type(v)}")
    return spec


def schema_to_spec(fields, widths=None):
    """(name, dtype_str) pairs (utils.schema parse output) -> spec."""
    m = {"bool": "?", "boolean": "?", "int": "i", "integer": "i",
         "bigint": "l", "long": "l", "float": "f", "double": "d",
         "string": "O", "binary": "O"}
    spec = []
    for i, (name, dt) in enumerate(fields):
        base = dt
        width = 0
        if dt.startswith("array<") and dt.endswith(">"):
            base = dt[6:-1]
            width = (widths or {}).get(name, -1)
        code = m.get(base)
        if code is None:
            raise ValueError(f"unsupported schema type {dt} for {name}")
        spec.append((code, width))
    return spec


def column_descrs(spec, n):
    """``(dtype_str, shape)`` of each column that ``n`` rows of ``spec``
    make: what ``rows_to_columns`` allocates, and what its ``out=`` has
    to bring."""
    return [(np.dtype(_CODE_TO_DTYPE.get(code, object)).str,
             (n, width) if width else (n,))
            for code, width in spec]


def _check_out(out, spec, n):
    if len(out) != len(spec):
        raise ValueError(
            f"out has {len(out)} arrays, spec has {len(spec)} columns")
    for c, (a, (dtype_str, shape)) in enumerate(
            zip(out, column_descrs(spec, n))):
        if not (isinstance(a, np.ndarray) and a.dtype == np.dtype(dtype_str)
                and a.shape == shape and a.flags.c_contiguous
                and a.flags.writeable):
            raise ValueError(
                f"out[{c}]: need a writable C-contiguous {dtype_str} array "
                f"of shape {shape}, got "
                f"{getattr(a, 'dtype', type(a).__name__)} "
                f"{getattr(a, 'shape', '')}")


def rows_to_columns(rows, spec=None, out=None):
    """Batch of row tuples -> tuple of dense per-column arrays.

    ``out`` brings the arrays to fill, one per column and exactly of the
    column's dtype and shape (``column_descrs``) — the feeder passes
    views of the feed ring, so a frame is encoded where it travels from —
    instead of fresh ones; the same arrays come back.  After a row that
    does not fit the spec they are partly written.

    Object ('O') columns always take the numpy path (the native layer
    handles the numeric matrix; strings/bytes stay python objects, like
    the reference's byte-string tensors)."""
    rows = list(rows)
    if not rows:
        return ()
    if spec is None:
        spec = infer_spec(rows[0])
    if out is not None:
        _check_out(out, spec, len(rows))
    ext = _load_ext()
    if ext is not None and all(c in _EXT_CODES for c, _ in spec):
        return ext.rows_to_columns(rows, [(c, int(w)) for c, w in spec], out)
    # numpy fallback (identical semantics)
    for i, r in enumerate(rows):
        if len(r) != len(spec):
            raise ValueError(
                f"row {i} has {len(r)} fields, spec has {len(spec)} columns"
            )
    cols = []
    for c, (code, width) in enumerate(spec):
        vals = [r[c] for r in rows]
        dst = None if out is None else out[c]
        target = np.dtype(_CODE_TO_DTYPE.get(code, object))
        if width and all(type(v) is np.ndarray and v.dtype == target
                         and v.shape == (width,) for v in vals):
            # rows that already hold the column's dtype (image bytes,
            # token ids): one memcpy per row, straight into place —
            # np.asarray over a list of 1-D arrays walks it element by
            # element and is an order of magnitude slower
            arr = (dst if dst is not None
                   else np.empty((len(vals), width), target))
            for i, v in enumerate(vals):
                arr[i] = v
            cols.append(arr)
            continue
        if code == "O":
            arr = np.empty(len(vals), dtype=object)
            arr[:] = vals
        else:
            if code in "?ilbBhH":
                # a spec inferred from an int first row must not silently
                # truncate floats that appear in later rows — reject the
                # lossy cast so callers fall back to the exact row path
                natural = np.asarray(vals)
                if natural.dtype.kind == "f" or (
                    code == "?" and natural.dtype.kind != "b"
                ):
                    raise ValueError(
                        f"column {c}: {natural.dtype} values under spec "
                        f"{code!r} (lossy cast refused)"
                    )
                if code != "?" and natural.dtype != target:
                    # narrowing (or sign-crossing) casts are checked by
                    # VALUE range, like the C fill loop's int32 guard
                    info = np.iinfo(target)
                    if (natural > info.max).any() or (natural < info.min).any():
                        raise ValueError(
                            f"column {c}: values overflow the "
                            f"{target.name} spec"
                        )
                arr = natural.astype(target, copy=False)
            else:
                arr = np.asarray(vals, dtype=target)
            if width and arr.shape[1:] != (width,):
                raise ValueError(
                    f"column {c}: shape {arr.shape[1:]} != width {width}"
                )
        if dst is not None:
            dst[...] = arr
            arr = dst
        cols.append(arr)
    return tuple(cols)


def columns_to_rows(columns):
    """Dense per-column arrays -> list of row tuples.

    1-D columns yield python scalars; 2-D columns yield python lists
    (parity: tensors2batch's scalar-vs-Seq rule, TFModel.scala:121-239).
    """
    columns = [np.ascontiguousarray(a) for a in columns]
    ext = _load_ext()
    if ext is not None and all(
        a.dtype in _EXT_OUT_DTYPES and a.ndim in (1, 2) for a in columns
    ):
        return ext.columns_to_rows(columns)
    n = len(columns[0]) if columns else 0
    cols = []
    for a in columns:
        if a.ndim <= 1:
            cols.append(a.tolist())
        else:
            # per-row nested lists; ndim>2 keeps its nesting (the ext path
            # only handles ndim<=2, so those arrays always land here)
            cols.append([row.tolist() for row in a])
    return [tuple(col[i] for col in cols) for i in range(n)]
