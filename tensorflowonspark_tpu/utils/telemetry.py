"""Cluster-wide structured telemetry: per-node event spans on one schema.

Parity target: the reference's observability is *log lines only* —
``logging.basicConfig`` at import (reference ``__init__.py:1-5``) plus
free-text records for cluster_info (``TFCluster.py:343-344``), node
registrations (``TFSparkNode.py:356``) and feed counts
(``TFSparkNode.py:497``); no metrics, no counters, no timeline
(SURVEY.md §5).  This module replaces those log lines with structured
spans so a whole federated run (reservation → rendezvous → compile →
steps → shutdown) lands on ONE timeline that
``scripts/trace_merge.py`` renders as a Perfetto-loadable Chrome trace
and a stall-attribution summary.

Design constraints (all load-bearing):

- **Zero-dep / stdlib-only** — imported by engine executors, feeder
  tasks, forked trainers and the driver; must never pull jax/numpy.
- **Two sinks, one call** — ``span`` / ``record_span`` write to the
  JSONL spool iff ``TFOS_TELEMETRY_DIR`` is set, and to a
  ``jax.profiler.TraceAnnotation`` iff jax is ALREADY imported in this
  process (``sys.modules``; this module never imports it).  Outside a
  profiler session an annotation is a no-op in the runtime, so no
  caller needs to know whether a capture is running.  With both sinks
  off every call is a cached no-op: no clock read, no files.
- **Never per record, never per token** — spans sit at chunk, batch,
  iteration and admission boundaries (docs/telemetry.md).
- **Monotonic durations** — ``dur_ms`` comes from ``perf_counter``
  deltas; ``ts`` is wall-clock (``time.time``) only to *anchor* spans
  on a shared timeline across processes of one host/run.
- **Bounded ring buffer** — records buffer in a ``deque(maxlen=...)``
  between flushes, so an unwritable sink degrades to dropped telemetry
  (counted), never to unbounded memory or a crashed trainer.
- **Safe under spawn/fork** — the recorder is keyed by pid: a fork or
  spawn child lazily opens its OWN ``<node>-<pid>.jsonl`` sink, and a
  ``multiprocessing.util.Finalize`` hook (multiprocessing children skip
  ``atexit``) flushes it at child exit.

One record per line (JSONL), one schema everywhere::

    {"ts": <epoch s>, "node_id": "worker-0", "role": "worker",
     "kind": "span"|"event", "name": "train/step",
     "dur_ms": <float>|null, "attrs": {...}}

Env vars:
  ``TFOS_TELEMETRY_DIR``    master switch + driver-side sink/run dir.
  ``TFOS_TELEMETRY_SPOOL``  node-local spool dir override (node.py sets
                            it per executor; the driver drain collects
                            spools into ``<dir>/run-<id>/``).
  ``TFOS_TELEMETRY_NODE``/``TFOS_TELEMETRY_ROLE``  identity defaults,
                            inherited by forked/spawned children.
  ``TFOS_TELEMETRY_BUFFER`` ring capacity (default 4096 records).
  ``TFOS_TELEMETRY_FLUSH``  flush threshold (default 128 records).
  ``TFOS_TRACE_PARENT``     W3C-traceparent-shaped causal parent, the
                            env channel by which spawned/forked children
                            join the minting process's request trace.
  ``TFOS_FLIGHT_RING``      flight-recorder ring capacity (default 512
                            records; see obs/flight.py).
"""

from __future__ import annotations

import atexit
import collections
import json
import logging
import os
import re
import socket
import sys
import threading
import time

logger = logging.getLogger(__name__)

DIR_ENV = "TFOS_TELEMETRY_DIR"
SPOOL_ENV = "TFOS_TELEMETRY_SPOOL"
NODE_ENV = "TFOS_TELEMETRY_NODE"
ROLE_ENV = "TFOS_TELEMETRY_ROLE"
BUFFER_ENV = "TFOS_TELEMETRY_BUFFER"
FLUSH_ENV = "TFOS_TELEMETRY_FLUSH"
TRACE_ENV = "TFOS_TRACE_PARENT"
RING_ENV = "TFOS_FLIGHT_RING"

SCHEMA_KEYS = ("ts", "node_id", "role", "kind", "name", "dur_ms", "attrs")

# -- serving SLO metric names (docs/serving.md) ----------------------------
# One span per served request with queue_ms / batch_ms / device_ms /
# batch / bucket attrs; one event per load-shed rejection.  trace_merge
# summarizes them into p50/p95/p99 and shed-rate.
SERVE_REQUEST = "serve/request"
SERVE_SHED = "serve/shed"
SERVE_BATCH = "serve/replica_batch"   # replica-side device batch span
SERVE_RELOAD = "serve/reload"         # hot-reload broadcast event
DECODE_SESSION = "decode/session"     # one autoregressive decode session
DECODE_SHED = "decode/shed"           # decode admission-control rejection
ACTOR_MESSAGE = "actor/message"       # one actor envelope handled
EVAL_RUN = "eval/run"                 # one eval-sidecar evaluation
SERVE_GENERATE = "serve/generate"     # request-root span, /v1/generate
SERVE_PREDICT = "serve/predict"       # request-root span, /v1/predict
DECODE_ADMIT = "decode/admit"         # replica-side session admission
DECODE_RETIRE = "decode/retire"       # replica-side session retirement
BENCH_REQUEST = "bench/request"       # loadgen per-request root span
CLUSTER_RUN = "cluster/run"           # cluster root-trace anchor
DATA_UNIT = "data/unit"               # one exactly-once data unit served
DEPLOY_BLESS = "deploy/bless"         # checkpoint passed gate, manifest out
DEPLOY_CANARY = "deploy/canary"       # canary arm opened on a candidate
DEPLOY_PROMOTE = "deploy/promote"     # candidate promoted fleet-wide
DEPLOY_ROLLBACK = "deploy/rollback"   # candidate rejected, blessed re-pinned

# -- hot-path spans: ``tfos/<layer>/<phase>`` (docs/telemetry.md) ------------
# The names benchmark/lib/program_trace.py and scripts/trace_merge.py read.
CLOCK = "tfos/clock"                        # opens a capture: time_ns arg
FEED_RING_WAIT = "tfos/feed/ring_wait"      # DataFeed: until a chunk is there
FEED_RING_READ = "tfos/feed/ring_read"      # copy out of the ring + decode
FEED_TO_COLUMNS = "tfos/feed/to_columns"    # next_batch(_columns) assembly
FEED_COLLATE = "tfos/feed/collate"          # the caller's collate
FEED_H2D = "tfos/feed/h2d"                  # dispatch of the transfer
FEED_STAGE_FULL = "tfos/feed/stage_full"    # prefetch worker blocked in put
FEED_NEXT = "tfos/feed/next"                # consumer blocked in get
FEED_SYNC = "tfos/feed/sync"                # synchronized(): flag all-gather
FEEDER_CHUNK = "tfos/feeder/chunk"          # feeder task, one per frame
FEEDER_HANDOFF = "tfos/feeder/handoff"      # until its last byte is taken
DECODE_IDLE = "tfos/decode/idle"
DECODE_ADMIT_SPAN = "tfos/decode/admit"
DECODE_TRIE_MATCH = "tfos/decode/trie_match"
DECODE_PREFILL = "tfos/decode/prefill"
DECODE_KV_INSERT = "tfos/decode/kv_insert"
DECODE_ALLOC_BLOCKS = "tfos/decode/alloc_blocks"  # only when the trie evicts
DECODE_ITERATE = "tfos/decode/iterate"
DECODE_BUILD_WINDOW = "tfos/decode/build_window"
DECODE_STEP_DISPATCH = "tfos/decode/step_dispatch"
DECODE_LOGITS_FETCH = "tfos/decode/logits_fetch"
DECODE_SAMPLE = "tfos/decode/sample"
DECODE_EMIT = "tfos/decode/emit"


# -- causal trace context (W3C-traceparent-shaped) -------------------------
# A TraceContext links spans ACROSS processes: the string form
# ``00-<32 hex trace_id>-<16 hex span_id>-01`` rides HTTP headers,
# dispatch blobs, actor envelopes and the TFOS_TRACE_PARENT env var;
# span records under an active context carry ``trace_id`` / ``span_id``
# / ``parent_id`` inside ``attrs`` (the 7-key record schema above never
# changes).  With no active context, attrs are left untouched.
_TRACEPARENT_RE = re.compile(
    r"^00-([0-9a-f]{32})-([0-9a-f]{16})-[0-9a-f]{2}$")


class TraceContext:
    """One node of a causal request tree.

    ``span_id`` names the span that new child records parent to;
    ``parent_id`` is where THIS context's own span (if any) links
    upward (None at the root).  Wire form is ``to_header()``; a parsed
    header yields a context whose ``span_id`` is the remote sender's
    span, so children recorded under it link across the process
    boundary."""

    __slots__ = ("trace_id", "span_id", "parent_id")

    def __init__(self, trace_id=None, span_id=None, parent_id=None):
        self.trace_id = trace_id or os.urandom(16).hex()
        self.span_id = span_id or os.urandom(8).hex()
        self.parent_id = parent_id

    def child(self):
        """A fresh context one level down (new span_id, parented here)."""
        return TraceContext(self.trace_id, None, self.span_id)

    def to_header(self):
        """W3C-traceparent-shaped string form for wires and env vars."""
        return f"00-{self.trace_id}-{self.span_id}-01"

    @classmethod
    def from_header(cls, header):
        """Parse a traceparent string; None on anything malformed."""
        if isinstance(header, TraceContext):
            return header
        m = _TRACEPARENT_RE.match(str(header or "").strip())
        if not m:
            return None
        return cls(m.group(1), m.group(2))

    def __repr__(self):
        return (f"TraceContext({self.trace_id[:8]}…, span={self.span_id}, "
                f"parent={self.parent_id})")


_TRACE_TLS = threading.local()
# env-channel parse cache: (raw header string, parsed ctx)
_ENV_PARENT = {"raw": None, "ctx": None}


def current():
    """The active TraceContext of this thread: the innermost activated
    /traced span, else the ``TFOS_TRACE_PARENT`` env channel (how
    spawned children inherit their parent), else None."""
    stack = getattr(_TRACE_TLS, "stack", None)
    if stack:
        return stack[-1]
    raw = os.environ.get(TRACE_ENV)
    if not raw:
        return None
    if _ENV_PARENT["raw"] != raw:
        _ENV_PARENT["ctx"] = TraceContext.from_header(raw)
        _ENV_PARENT["raw"] = raw
    return _ENV_PARENT["ctx"]


def _push(ctx):
    stack = getattr(_TRACE_TLS, "stack", None)
    if stack is None:
        stack = _TRACE_TLS.stack = []
    stack.append(ctx)


def _pop(ctx):
    stack = getattr(_TRACE_TLS, "stack", None)
    if stack and stack[-1] is ctx:
        stack.pop()


class _Activation:
    """CM scoping an existing context onto this thread (wire receive)."""

    __slots__ = ("_ctx",)

    def __init__(self, ctx):
        self._ctx = ctx

    def __enter__(self):
        if self._ctx is not None:
            _push(self._ctx)
        return self._ctx

    def __exit__(self, *exc):
        if self._ctx is not None:
            _pop(self._ctx)
        return False


def activate(ctx):
    """``with telemetry.activate(ctx_or_header):`` — make a context
    received over a wire (dispatch blob, envelope, queue dict) the
    active parent for spans/events in the body.  Accepts a
    TraceContext, a traceparent string, or None (no-op); also a no-op
    when telemetry is disabled."""
    if ctx is None or _get() is None:
        return _Activation(None)
    if not isinstance(ctx, TraceContext):
        ctx = TraceContext.from_header(ctx)
    return _Activation(ctx)


class Recorder:
    """Per-process span/event sink: bounded buffer -> one JSONL file."""

    def __init__(self, sink_dir, node_id=None, role=None):
        self.sink_dir = sink_dir
        self.pid = os.getpid()
        self.node_id = (node_id or os.environ.get(NODE_ENV)
                        or f"{socket.gethostname()}-{self.pid}")
        self.role = role or os.environ.get(ROLE_ENV) or "proc"
        self.path = os.path.join(
            sink_dir, f"{_safe(self.node_id)}-{self.pid}.jsonl")
        cap = int(os.environ.get(BUFFER_ENV, "4096"))
        self._flush_every = int(os.environ.get(FLUSH_ENV, "128"))
        self._buf = collections.deque(maxlen=max(cap, 1))
        # flight ring: the last N records, NOT drained by flush — the
        # black-box window obs/flight.py snapshots on supervision events
        self.ring = collections.deque(
            maxlen=max(int(os.environ.get(RING_ENV, "512")), 1))
        self._lock = threading.Lock()
        self._last_flush = time.monotonic()
        self._sink_warned = False
        self.dropped = 0
        # atexit covers plain interpreters; multiprocessing children
        # exit via os._exit in Process._bootstrap and run only the
        # util.Finalize registry — register with both so a spawned or
        # forked trainer's tail records always reach the file.
        atexit.register(self.flush)
        try:
            from multiprocessing import util as _mputil

            _mputil.Finalize(self, Recorder.flush, args=(self,),
                             exitpriority=100)
        except Exception:  # noqa: BLE001 - atexit alone is acceptable
            pass

    def record(self, kind, name, ts, dur_ms, attrs):
        rec = {
            "ts": ts,
            "node_id": self.node_id,
            "role": self.role,
            "kind": kind,
            "name": name,
            "dur_ms": dur_ms,
            "attrs": attrs or {},
        }
        with self._lock:
            if len(self._buf) == self._buf.maxlen:
                self.dropped += 1
            self._buf.append(rec)
            self.ring.append(rec)
            need = (len(self._buf) >= self._flush_every
                    or time.monotonic() - self._last_flush > 1.0)
        if need:
            self.flush()

    def flush(self):
        if os.getpid() != self.pid:
            # A fork child inherits the parent's atexit/Finalize entries
            # (and any buffered records): flushing here would duplicate
            # the parent's records under the parent's filename.
            return
        with self._lock:
            if not self._buf:
                return
            recs = list(self._buf)
            self._buf.clear()
            dropped, self.dropped = self.dropped, 0
            self._last_flush = time.monotonic()
        if dropped:
            recs.insert(0, {
                "ts": time.time(), "node_id": self.node_id,
                "role": self.role, "kind": "event",
                "name": "telemetry/dropped", "dur_ms": None,
                "attrs": {"count": dropped},
            })
        try:
            os.makedirs(self.sink_dir, exist_ok=True)
            data = "".join(
                json.dumps(r, default=str) + "\n" for r in recs)
            with open(self.path, "a", encoding="utf-8") as f:
                f.write(data)
        except OSError as e:
            if not self._sink_warned:  # degrade quietly, never crash
                self._sink_warned = True
                logger.warning("telemetry sink unwritable (%s): %s",
                               self.path, e)


def _safe(name):
    return "".join(c if (c.isalnum() or c in "-_.") else "_"
                   for c in str(name)) or "node"


# Cached per (pid, dir, spool, node, role): a fork/spawn child or an env
# change (tests, node_configure) transparently gets a fresh recorder.
_STATE = {"key": None, "rec": None}
_STATE_LOCK = threading.Lock()


def _get():
    key = (os.getpid(), os.environ.get(DIR_ENV),
           os.environ.get(SPOOL_ENV), os.environ.get(NODE_ENV),
           os.environ.get(ROLE_ENV))
    if _STATE["key"] == key:
        return _STATE["rec"]
    with _STATE_LOCK:
        if _STATE["key"] == key:
            return _STATE["rec"]
        old = _STATE["rec"]
        if old is not None and old.pid == os.getpid():
            old.flush()  # reconfigure in-process: don't strand records
        base = key[1]
        rec = Recorder(key[2] or base) if base else None
        _STATE["rec"] = rec
        _STATE["key"] = key
        return rec


def enabled():
    """True when telemetry is recording in this process."""
    return _get() is not None


def sink_path():
    """This process's JSONL sink path, or None when disabled."""
    rec = _get()
    return rec.path if rec is not None else None


def configure(node_id=None, role=None, spool=None):
    """Pin identity/sink via the env channel so forked and spawned
    children inherit them; returns the active recorder (or None)."""
    if node_id is not None:
        os.environ[NODE_ENV] = str(node_id)
    if role is not None:
        os.environ[ROLE_ENV] = str(role)
    if spool is not None:
        os.environ[SPOOL_ENV] = str(spool)
    return _get()


# The profiler sink: jax.profiler.TraceAnnotation, found in sys.modules
# and never imported.  Cached once found (a process does not un-import).
_ANNOTATION = None


def _annotation():
    global _ANNOTATION
    if _ANNOTATION is None:
        # a jax that is only half imported has no ``profiler`` yet: None
        prof = getattr(sys.modules.get("jax"), "profiler", None)
        _ANNOTATION = getattr(prof, "TraceAnnotation", None)
    return _ANNOTATION


def _scalars(attrs):
    """The attrs an annotation can carry as the event's stats."""
    return {k: v for k, v in attrs.items()
            if isinstance(v, (int, float, str))}


def active():
    """True when a span would reach a sink (spool or profiler): what a
    call site asks before it reads a clock of its own."""
    return _get() is not None or _annotation() is not None


class _NullSpan:
    """Shared no-op span: the disabled path allocates nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def add(self, **attrs):
        return self


_NULL = _NullSpan()


class Span:
    """Context manager measuring one span on the monotonic clock.

    Under an active :class:`TraceContext` the span joins the causal
    tree: it derives (or is handed) a child context, becomes the active
    parent for its body, and stamps ``trace_id``/``span_id``/
    ``parent_id`` into its attrs on exit.  With no active context the
    record is byte-identical to the pre-trace schema (attrs
    untouched)."""

    __slots__ = ("_rec", "name", "attrs", "_ts", "_t0", "_ctx",
                 "_ann_cls", "_ann")

    def __init__(self, rec, name, attrs, ctx=None, ann=None):
        self._rec = rec       # the spool, or None
        self.name = name
        self.attrs = attrs
        self._ctx = ctx
        self._ann_cls = ann   # the TraceAnnotation class, or None
        self._ann = None      # the live annotation, inside the body

    def __enter__(self):
        if self._ann_cls is not None:
            self._ann = self._ann_cls(self.name, **_scalars(self.attrs))
            self._ann.__enter__()
        if self._rec is None:
            return self       # profiler sink only: it has its own clock
        self._ts = time.time()
        self._t0 = time.perf_counter()
        if self._ctx is None:
            parent = current()
            if parent is not None:
                self._ctx = parent.child()
        if self._ctx is not None:
            _push(self._ctx)
        return self

    def add(self, **attrs):
        self.attrs.update(attrs)
        if self._ann is not None:
            self._ann.set_metadata(**_scalars(attrs))
        return self

    @property
    def ctx(self):
        """This span's TraceContext (None outside any trace)."""
        return self._ctx

    def __exit__(self, exc_type, exc, tb):
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        if self._rec is None:
            return False
        dur_ms = (time.perf_counter() - self._t0) * 1000.0
        if self._ctx is not None:
            _pop(self._ctx)
            self.attrs.setdefault("trace_id", self._ctx.trace_id)
            self.attrs.setdefault("span_id", self._ctx.span_id)
            self.attrs.setdefault("parent_id", self._ctx.parent_id)
        if exc_type is not None:
            self.attrs.setdefault("error", repr(exc)[:200])
        self._rec.record("span", self.name, self._ts, dur_ms, self.attrs)
        return False


def span(name, **attrs):
    """``with telemetry.span("phase/name", k=v) as s: ...`` — records a
    span on exit (exceptions annotate ``attrs.error`` and propagate).
    One call, two sinks: the spool and the profiler (module docstring);
    scalar attrs travel with the annotation as the event's stats."""
    rec, ann = _get(), _annotation()
    if rec is None and ann is None:
        return _NULL
    return Span(rec, name, attrs, ann=ann)


def trace_span(name, header=None, **attrs):
    """Entry-point span: like :func:`span` but ALWAYS traced — it
    continues the trace in ``header`` (traceparent string or
    TraceContext) when given, else the thread's active context, else
    mints a fresh root.  Returns the no-op span when telemetry is
    disabled (the overhead contract)."""
    rec, ann = _get(), _annotation()
    if rec is None:
        return _NULL if ann is None else Span(None, name, attrs, ann=ann)
    parent = TraceContext.from_header(header) if header else current()
    ctx = parent.child() if parent is not None else TraceContext()
    return Span(rec, name, attrs, ctx=ctx, ann=ann)


def event(name, **attrs):
    """Record an instant event (``dur_ms`` null).  Under an active
    trace the event is stamped as a leaf of the current span."""
    rec = _get()
    if rec is not None:
        ctx = current()
        if ctx is not None:
            attrs.setdefault("trace_id", ctx.trace_id)
            attrs.setdefault("parent_id", ctx.span_id)
        rec.record("event", name, time.time(), None, attrs)


def record_span(name, dur_s, **attrs):
    """Record an already-measured duration as a span whose start is
    back-dated by ``dur_s`` — for call sites that time themselves (the
    feed wait path, TrainMetrics.step) so telemetry and the counters
    report the SAME number.  The profiler cannot back-date: there the
    span is an instant annotation at its END carrying ``dur_ms``."""
    ann = _annotation()
    if ann is not None:
        with ann(name, dur_ms=dur_s * 1000.0, **_scalars(attrs)):
            pass
    rec = _get()
    if rec is not None:
        ctx = current()
        if ctx is not None:
            attrs.setdefault("trace_id", ctx.trace_id)
            attrs.setdefault("span_id", os.urandom(8).hex())
            attrs.setdefault("parent_id", ctx.span_id)
        rec.record("span", name, time.time() - dur_s, dur_s * 1000.0,
                   attrs)


def trace_root(name, export=True, **attrs):
    """Mint a root TraceContext for a long-lived scope (``cluster.run``)
    and record an instant anchor span for it so every later child's
    ``parent_id`` resolves.  ``export=True`` additionally publishes the
    context on ``TFOS_TRACE_PARENT`` so this process's later spans AND
    spawned children inherit it.  Returns the context (None when
    telemetry is disabled)."""
    rec = _get()
    if rec is None:
        return None
    ctx = TraceContext()
    attrs.setdefault("trace_id", ctx.trace_id)
    attrs.setdefault("span_id", ctx.span_id)
    attrs.setdefault("parent_id", None)
    rec.record("span", name, time.time(), 0.0, attrs)
    if export:
        os.environ[TRACE_ENV] = ctx.to_header()
    return ctx


def recent(window_s=None):
    """The flight ring: this process's last recorded spans/events (most
    recent last), optionally clipped to the trailing ``window_s``
    seconds.  Empty when telemetry is disabled."""
    rec = _get()
    if rec is None:
        return []
    with rec._lock:
        records = list(rec.ring)
    if window_s is not None:
        cutoff = time.time() - float(window_s)
        records = [r for r in records if r.get("ts", 0) >= cutoff]
    return records


def flush():
    """Flush this process's buffered records to the JSONL sink."""
    rec = _get()
    if rec is not None:
        rec.flush()


def run_dir(cluster_id):
    """The per-run collection directory under TFOS_TELEMETRY_DIR that
    the driver drain fills at shutdown, or None when disabled."""
    base = os.environ.get(DIR_ENV)
    if not base:
        return None
    return os.path.join(base, f"run-{int(cluster_id) & 0xffffffff:x}")


def register_with(mgr):
    """Advertise this process's spool dir in the executor manager's KV
    (the telemetry drain channel, manager.py) so the driver-side drain
    can collect every node file at shutdown.  Best-effort: telemetry
    must never take a worker down."""
    rec = _get()
    if rec is None:
        return
    try:
        mgr.telemetry_register(os.path.abspath(rec.sink_dir))
    except Exception as e:  # noqa: BLE001 - drain is best-effort
        logger.debug("telemetry spool registration failed: %s", e)


def read_spool(spool_dir):
    """[(filename, jsonl_text), ...] for every record file in a spool —
    the executor-side half of the drain (see node.drain_telemetry).

    Hardened against SIGKILLed writers: a process killed mid-``write``
    leaves a truncated (or garbage) trailing line; such lines are
    dropped and counted (one warning per file) instead of poisoning the
    merged run directory — and this function never raises, because the
    drain runs on live executors whose telemetry must not take them
    down."""
    out = []
    try:
        names = sorted(os.listdir(spool_dir))
    except OSError:
        return out
    for name in names:
        if not name.endswith(".jsonl"):
            continue
        try:
            # errors="replace": a record cut inside a multi-byte UTF-8
            # sequence must not abort the whole file
            with open(os.path.join(spool_dir, name),
                      encoding="utf-8", errors="replace") as f:
                raw = f.read()
        except OSError as e:
            logger.warning("telemetry drain: unreadable %s: %s", name, e)
            continue
        kept, skipped = [], 0
        for line in raw.splitlines():
            if not line.strip():
                continue
            try:
                json.loads(line)
            except ValueError:
                skipped += 1
                continue
            kept.append(line)
        if skipped:
            logger.warning(
                "telemetry drain: skipped %d truncated/corrupt line(s) "
                "in %s", skipped, name)
        if kept:
            out.append((name, "\n".join(kept) + "\n"))
    return out
