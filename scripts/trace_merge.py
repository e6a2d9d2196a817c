"""Merge per-node telemetry JSONL into one Chrome trace + summary.

Input: a directory of ``utils/telemetry.py`` JSONL files — either a
drained run directory (``$TFOS_TELEMETRY_DIR/run-<id>/``, written by
cluster shutdown) or ``$TFOS_TELEMETRY_DIR`` itself (driver files +
run dirs; scanned recursively).  Output:

  (a) a Chrome ``trace_event`` JSON (``--out``, default
      ``<dir>/trace.json``) loadable at https://ui.perfetto.dev — one
      process row per node_id, one thread row per source process;
  (b) a text summary on stdout: per-phase wall time, per-node step-time
      percentiles, infeed-stall fraction, and MFU when the ``train/step``
      spans carry ``flops_per_item``/``peak_flops`` attrs (the counting
      convention is utils/flops.py's: 2 FLOPs/MAC — TrainMetrics attaches
      both when constructed with a flops_per_item denominator);
  (c) with ``--trace <id>`` (any unique prefix of a trace_id): a
      single-request causal waterfall — every span/event carrying that
      trace_id across every node, indented by parent link — plus a
      critical-path summary decomposing the request into queue /
      prefill / decode / other milliseconds (the span tree is minted by
      ``utils/telemetry.py`` "Causal tracing").

  (d) with ``--xplane <dir>`` (a ``utils/profiler`` capture directory):
      the capture's host spans (``tfos/*``, ``bench/*``) and device
      operations join the Chrome trace as rows of their own, so a
      feeder's spool (another process, wall-clock ``ts``) and the
      trainer's capture land on ONE timeline.  The capture's timestamps
      count from its start; the offset to the wall clock comes from the
      ``tfos/clock`` annotation that ``utils/profiler.start_trace`` opens
      every capture with.  Needs jax (``jax.profiler.ProfileData``).

Parity: the reference has no timeline tooling at all — its observability
is log lines (reference ``__init__.py:1-5``, SURVEY.md §5); this is the
aggregation half the telemetry tentpole adds on top.

Usage: python scripts/trace_merge.py DIR [--out trace.json]
           [--summary-out summary.txt] [--xplane CAPTURE_DIR]
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

SCHEMA_KEYS = ("ts", "node_id", "role", "kind", "name", "dur_ms", "attrs")

# utils/telemetry.py's names for the feed's consumer (kept in step by
# tests/test_telemetry.py; this script imports nothing of the package)
FEED_FETCH_SPANS = ("tfos/feed/ring_wait", "tfos/feed/ring_read")
FEED_TO_COLUMNS = "tfos/feed/to_columns"
FEEDER_CHUNK = "tfos/feeder/chunk"
FEEDER_HANDOFF = "tfos/feeder/handoff"
FEEDER_PARTS = ("source_ms", "room_wait_ms", "write_ms", "encode_ms")
CLOCK_SPAN = "tfos/clock"
CAPTURE_SPAN_PREFIXES = ("tfos/", "bench/")

_PID_RE = re.compile(r"-(\d+)\.jsonl$")


def load_records(run_dir):
    """((record, source_basename) list sorted by ts, skipped-line count).

    Scans ``run_dir`` recursively for ``*.jsonl`` so both a drained
    ``run-<id>/`` dir and a whole ``TFOS_TELEMETRY_DIR`` (driver files +
    run dirs) merge onto one timeline.  Malformed lines are counted, not
    fatal — a crashed writer's torn tail must not sink the merge.
    """
    out = []
    skipped = 0
    for root, _dirs, files in os.walk(run_dir):
        for name in sorted(files):
            if not name.endswith(".jsonl"):
                continue
            path = os.path.join(root, name)
            try:
                with open(path, encoding="utf-8") as f:
                    lines = f.read().splitlines()
            except OSError:
                skipped += 1
                continue
            for line in lines:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                    if not all(k in rec for k in SCHEMA_KEYS):
                        raise ValueError("missing schema keys")
                except (ValueError, TypeError):
                    skipped += 1
                    continue
                out.append((rec, name))
    out.sort(key=lambda p: p[0]["ts"])
    return out, skipped


def _source_pid(src):
    m = _PID_RE.search(src)
    return int(m.group(1)) if m else abs(hash(src)) % 100000


def load_xplane(capture_dir):
    """The capture under ``capture_dir`` as ``[(plane, line, name,
    ts_epoch_s, dur_s, args)]``: the program's and the benchmark's host
    spans, and every device operation.  Raises ValueError when the
    capture has no ``tfos/clock`` annotation to place it on the wall
    clock (a capture not started by ``utils/profiler.start_trace``)."""
    import glob

    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        capture_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise ValueError(f"no *.xplane.pb under {capture_dir}")
    rows, offset_ns = [], None
    for plane in ProfileData.from_file(paths[-1]).planes:
        device = plane.name.startswith("/device:")
        for i, line in enumerate(plane.lines):
            if device and line.name != "XLA Ops":
                continue
            for ev in line.events:
                if not device and not ev.name.startswith(
                        CAPTURE_SPAN_PREFIXES):
                    continue
                args = {} if device else {
                    k: v for k, v in ev.stats
                    if isinstance(v, (int, float, str))}
                if ev.name == CLOCK_SPAN and "time_ns" in args:
                    offset_ns = int(args["time_ns"]) - int(ev.start_ns)
                name = ev.name.split(" = ", 1)[0][:80] if device \
                    else ev.name
                rows.append((plane.name, f"{line.name}#{i}", name,
                             int(ev.start_ns), ev.duration_ns, args))
    if offset_ns is None:
        raise ValueError(
            f"{paths[-1]} has no {CLOCK_SPAN} annotation: its timestamps "
            "count from the capture's start and cannot be placed on the "
            "spool's wall clock (take it with utils/profiler.start_trace)")
    return [(pl, ln, name, (start + offset_ns) / 1e9, dur / 1e9, args)
            for pl, ln, name, start, dur, args in rows]


def to_chrome_trace(pairs, capture=()):
    """Chrome ``trace_event`` dict from (record, source) pairs, and the
    rows of ``load_xplane`` if a capture rides along.

    Mapping: node_id -> trace pid (one process row per node), source
    file's OS pid -> trace tid (the executor and its forked trainer
    share a node row but get separate thread lanes, so overlapping spans
    never fake a nesting).  Spans are ``ph:"X"`` complete events, events
    are ``ph:"i"`` instants; timestamps are rebased to the earliest
    record (Perfetto handles epoch offsets, humans don't).
    """
    nodes = sorted({rec["node_id"] for rec, _ in pairs})
    pid_of = {n: i + 1 for i, n in enumerate(nodes)}
    t0 = min([rec["ts"] for rec, _ in pairs]
             + [row[3] for row in capture], default=0.0)
    events = []
    named_threads = set()
    planes = sorted({row[0] for row in capture})
    for k, plane in enumerate(planes):
        pid = len(nodes) + 1 + k
        events.append({"ph": "M", "name": "process_name", "pid": pid,
                       "tid": 0, "args": {"name": f"capture {plane}"}})
        lines = sorted({row[1] for row in capture if row[0] == plane})
        for plane_, line, name, ts, dur, args in capture:
            if plane_ == plane:
                events.append({
                    "name": name, "cat": "capture", "pid": pid,
                    "tid": lines.index(line) + 1, "args": args, "ph": "X",
                    "ts": (ts - t0) * 1e6, "dur": dur * 1e6})
    for node in nodes:
        role = next(rec["role"] for rec, _ in pairs if rec["node_id"] == node)
        events.append({
            "ph": "M", "name": "process_name", "pid": pid_of[node],
            "tid": 0, "args": {"name": f"{node} ({role})"},
        })
    for rec, src in pairs:
        pid = pid_of[rec["node_id"]]
        tid = _source_pid(src)
        if (pid, tid) not in named_threads:
            named_threads.add((pid, tid))
            events.append({
                "ph": "M", "name": "thread_name", "pid": pid, "tid": tid,
                "args": {"name": src[:-len(".jsonl")]},
            })
        dur_ms = rec["dur_ms"]
        base = {
            "name": rec["name"],
            "cat": rec["role"],
            "pid": pid,
            "tid": tid,
            "args": rec["attrs"] or {},
        }
        if rec["kind"] == "span" and dur_ms is not None:
            base.update(
                ph="X",
                ts=(rec["ts"] - t0) * 1e6,
                dur=float(dur_ms) * 1e3,
            )
        else:
            base.update(ph="i", ts=(rec["ts"] - t0) * 1e6, s="t")
        events.append(base)
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def _pct(sorted_vals, q):
    """Nearest-rank percentile over an already-sorted list."""
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, max(0, round(q * (len(sorted_vals) - 1))))
    return sorted_vals[idx]


def summarize(pairs, skipped=0):
    """(text, stats) summary: per-phase wall, per-node step percentiles,
    infeed-stall fraction, MFU (when step spans carry the denominators).
    """
    recs = [rec for rec, _ in pairs]
    phases = {}
    per_node = {}
    serve = {"totals_ms": [], "queue_ms": [], "device_ms": [],
             "batches": [], "shed": 0}
    data_stages = {}
    feeders = {}
    actors = {"msgs": {}, "respawns": 0, "lost": 0, "redispatched": 0}
    for rec in recs:
        node = per_node.setdefault(
            rec["node_id"],
            {"role": rec["role"], "steps_ms": [], "items": 0,
             "model_flops": 0.0, "peak_flops": None, "infeed_s": 0.0},
        )
        if rec["name"] == "serve/shed":
            serve["shed"] += 1
        elif rec["name"] == "actor/respawn":
            actors["respawns"] += 1
        elif rec["name"] == "actor/lost":
            actors["lost"] += 1
        elif rec["name"] == "actor/redispatch":
            actors["redispatched"] += int(
                (rec["attrs"] or {}).get("asks") or 0)
        if rec["kind"] != "span" or rec["dur_ms"] is None:
            continue
        ph = phases.setdefault(rec["name"], {"count": 0, "total_ms": 0.0,
                                             "max_ms": 0.0})
        ph["count"] += 1
        ph["total_ms"] += rec["dur_ms"]
        ph["max_ms"] = max(ph["max_ms"], rec["dur_ms"])
        attrs = rec["attrs"] or {}
        if rec["name"] == "train/step":
            node["steps_ms"].append(float(rec["dur_ms"]))
            items = attrs.get("items") or 0
            node["items"] += items
            if attrs.get("flops_per_item"):
                node["model_flops"] += items * float(attrs["flops_per_item"])
            if attrs.get("peak_flops"):
                node["peak_flops"] = float(attrs["peak_flops"])
        elif rec["name"] in FEED_FETCH_SPANS:
            # the consumer's wait for a chunk AND its read of it: the
            # stall fraction reads what ``feed/wait`` read before them
            node["infeed_s"] += float(rec["dur_ms"]) / 1e3
        elif rec["name"] in ("data/stage", FEED_TO_COLUMNS):
            # the feed's consumer is the pipeline's last stage: its span
            # covers the chunk fetches inside it, ``wait_ms`` of them
            fed = rec["name"] == FEED_TO_COLUMNS
            wait_ms = float(attrs.get("wait_ms") or 0.0)
            st = data_stages.setdefault(
                "to_columns" if fed else str(attrs.get("stage") or "?"),
                {"self_ms": [], "wait_ms": [], "records": 0})
            st["self_ms"].append(
                max(float(rec["dur_ms"]) - wait_ms, 0.0) if fed
                else float(rec["dur_ms"]))
            st["wait_ms"].append(wait_ms)
            st["records"] += int(attrs.get("records") or 0)
        elif rec["name"] in (FEEDER_CHUNK, FEEDER_HANDOFF):
            # the producer's side of the ring, one row per node: where a
            # frame's time goes, and how many were encoded in place
            fd = feeders.setdefault(rec["node_id"], {
                "frames": 0, "inplace": 0, "records": 0, "busy_ms": 0.0,
                "handoffs": 0, "handoff_ms": 0.0,
                **{k: 0.0 for k in FEEDER_PARTS}})
            fd["busy_ms"] += float(rec["dur_ms"])
            if rec["name"] == FEEDER_HANDOFF:
                fd["handoffs"] += 1
                fd["handoff_ms"] += float(rec["dur_ms"])
            else:
                fd["frames"] += 1
                fd["inplace"] += int(attrs.get("inplace") or 0)
                fd["records"] += int(attrs.get("records") or 0)
                for k in FEEDER_PARTS:
                    fd[k] += float(attrs.get(k) or 0.0)
        elif rec["name"] == "actor/message":
            key = (str(attrs.get("group") or "?"),
                   str(attrs.get("kind") or "?"))
            actors["msgs"].setdefault(key, []).append(float(rec["dur_ms"]))
        elif rec["name"] == "serve/request":
            serve["totals_ms"].append(float(rec["dur_ms"]))
            if attrs.get("queue_ms") is not None:
                serve["queue_ms"].append(float(attrs["queue_ms"]))
            if attrs.get("device_ms") is not None:
                serve["device_ms"].append(float(attrs["device_ms"]))
            if attrs.get("batch"):
                serve["batches"].append(float(attrs["batch"]))

    stats = {"records": len(recs), "skipped": skipped, "nodes": {},
             "phases": phases}
    span = ((max(r["ts"] for r in recs) - min(r["ts"] for r in recs))
            if recs else 0.0)
    lines = [
        f"telemetry summary: {len(per_node)} nodes, {len(recs)} records, "
        f"{span:.2f}s wall span"
        + (f", {skipped} unparseable lines skipped" if skipped else "")
    ]

    lines.append("")
    lines.append("-- phases (by total wall) --")
    lines.append(f"{'name':<32} {'count':>7} {'total_ms':>12} {'max_ms':>10}")
    for name, ph in sorted(phases.items(), key=lambda kv: -kv[1]["total_ms"]):
        lines.append(f"{name:<32} {ph['count']:>7} {ph['total_ms']:>12.1f} "
                     f"{ph['max_ms']:>10.1f}")

    if serve["totals_ms"] or serve["shed"]:
        # online-serving SLOs (docs/serving.md): per-request spans carry
        # queue/device decomposition; sheds are instant events
        totals = sorted(serve["totals_ms"])
        n_req = len(totals)
        shed = serve["shed"]
        stats["serving"] = {
            "requests": n_req,
            "shed": shed,
            "shed_rate": shed / (n_req + shed) if (n_req + shed) else 0.0,
            "p50_ms": _pct(totals, 0.50),
            "p95_ms": _pct(totals, 0.95),
            "p99_ms": _pct(totals, 0.99),
            "mean_queue_ms": (sum(serve["queue_ms"]) / len(serve["queue_ms"])
                              if serve["queue_ms"] else 0.0),
            "mean_device_ms": (sum(serve["device_ms"])
                               / len(serve["device_ms"])
                               if serve["device_ms"] else 0.0),
            "mean_device_batch": (sum(serve["batches"])
                                  / len(serve["batches"])
                                  if serve["batches"] else 0.0),
        }
        s = stats["serving"]
        lines.append("")
        lines.append("-- serving (serve/request spans) --")
        lines.append(
            f"requests={n_req} shed={shed} shed_rate={s['shed_rate']:.3f} "
            f"p50={s['p50_ms']:.1f}ms p95={s['p95_ms']:.1f}ms "
            f"p99={s['p99_ms']:.1f}ms")
        lines.append(
            f"mean queue={s['mean_queue_ms']:.1f}ms "
            f"device={s['mean_device_ms']:.1f}ms "
            f"device batch={s['mean_device_batch']:.1f}")

    if actors["msgs"] or actors["respawns"] or actors["lost"]:
        # supervised-actor health (docs/actors.md): per-message handler
        # latency by (group, kind); lost/respawn/redispatch counts are
        # the failover story of the run
        stats["actors"] = {
            "respawns": actors["respawns"],
            "lost": actors["lost"],
            "redispatched_asks": actors["redispatched"],
            "messages": {},
        }
        lines.append("")
        lines.append("-- actors (actor/message spans) --")
        lines.append(
            f"lost={actors['lost']} respawns={actors['respawns']} "
            f"redispatched_asks={actors['redispatched']}")
        if actors["msgs"]:
            lines.append(f"{'group':<16} {'kind':<16} {'count':>7} "
                         f"{'p50_ms':>9} {'p95_ms':>9} {'max_ms':>9}")
        for (group, kind), durs in sorted(actors["msgs"].items()):
            durs = sorted(durs)
            row = {"count": len(durs), "p50_ms": _pct(durs, 0.50),
                   "p95_ms": _pct(durs, 0.95), "max_ms": durs[-1]}
            stats["actors"]["messages"][f"{group}:{kind}"] = row
            lines.append(
                f"{group:<16} {kind:<16} {row['count']:>7} "
                f"{row['p50_ms']:>9.2f} {row['p95_ms']:>9.2f} "
                f"{row['max_ms']:>9.2f}")

    if data_stages:
        # input-pipeline stall attribution (docs/data.md): each
        # data/stage span is one produced block — dur_ms is the stage's
        # own produce time, attrs.wait_ms the time it blocked on its
        # upstream.  stall = wait / (wait + self): ~1.0 means the stage
        # starves (upstream-bound), ~0.0 means it is the bottleneck.
        stats["data"] = {}
        lines.append("")
        lines.append("-- data (data/stage spans) --")
        lines.append(
            f"{'stage':<16} {'blocks':>7} {'records':>9} {'self_p50':>9} "
            f"{'self_p95':>9} {'wait_p50':>9} {'wait_p95':>9} {'stall':>6}")
        for name in sorted(data_stages):
            st = data_stages[name]
            selfs = sorted(st["self_ms"])
            waits = sorted(st["wait_ms"])
            tot_self = sum(selfs)
            tot_wait = sum(waits)
            loop = tot_self + tot_wait
            stats["data"][name] = {
                "blocks": len(selfs), "records": st["records"],
                "self_p50_ms": _pct(selfs, 0.50),
                "self_p95_ms": _pct(selfs, 0.95),
                "wait_p50_ms": _pct(waits, 0.50),
                "wait_p95_ms": _pct(waits, 0.95),
                "stall_frac": tot_wait / loop if loop else 0.0,
            }
            d = stats["data"][name]
            lines.append(
                f"{name:<16} {d['blocks']:>7} {d['records']:>9} "
                f"{d['self_p50_ms']:>9.2f} {d['self_p95_ms']:>9.2f} "
                f"{d['wait_p50_ms']:>9.2f} {d['wait_p95_ms']:>9.2f} "
                f"{d['stall_frac']:>6.2f}")

    if feeders:
        # tfos/feeder/chunk + handoff (node.train): means per frame, the
        # hand-over per partition, and the rate the feeder task sustains
        # while it runs (records / time inside these spans)
        stats["feeder"] = {}
        lines.append("")
        lines.append("-- feeder (tfos/feeder/chunk, handoff) --")
        lines.append(
            f"{'node':<16} {'frames':>7} {'inplace':>8} {'records':>9} "
            f"{'source':>8} {'room_wait':>9} {'write':>8} {'encode':>8} "
            f"{'handoff':>8} {'rec/s':>8}")
        for name in sorted(feeders):
            fd = feeders[name]
            n = max(fd["frames"], 1)
            d = stats["feeder"][name] = {
                "frames": fd["frames"], "inplace": fd["inplace"],
                "copied": fd["frames"] - fd["inplace"],
                "records": fd["records"],
                **{k: fd[k] / n for k in FEEDER_PARTS},
                "handoff_ms": fd["handoff_ms"] / max(fd["handoffs"], 1),
                "records_per_s": (fd["records"] / fd["busy_ms"] * 1e3
                                  if fd["busy_ms"] else 0.0),
            }
            lines.append(
                f"{name:<16} {d['frames']:>7} {d['inplace']:>8} "
                f"{d['records']:>9} {d['source_ms']:>8.2f} "
                f"{d['room_wait_ms']:>9.2f} {d['write_ms']:>8.2f} "
                f"{d['encode_ms']:>8.2f} {d['handoff_ms']:>8.2f} "
                f"{d['records_per_s']:>8.0f}")

    lines.append("")
    lines.append("-- per-node train steps --")
    lines.append(
        f"{'node':<16} {'role':<10} {'steps':>6} {'p50_ms':>8} {'p90_ms':>8} "
        f"{'p99_ms':>8} {'total_s':>8} {'infeed_s':>9} {'stall':>6} "
        f"{'mfu':>6}")
    for name in sorted(per_node):
        n = per_node[name]
        steps = sorted(n["steps_ms"])
        total_s = sum(steps) / 1e3
        # fraction of the train loop spent waiting on the feed: bounded
        # to [0, 1) even when waits dwarf compute (feeder-starved runs)
        loop_s = total_s + n["infeed_s"]
        stall = n["infeed_s"] / loop_s if loop_s else 0.0
        mfu = (n["model_flops"] / total_s / n["peak_flops"]
               if total_s and n["model_flops"] and n["peak_flops"] else None)
        stats["nodes"][name] = {
            "role": n["role"], "steps": len(steps),
            "p50_ms": _pct(steps, 0.50), "p90_ms": _pct(steps, 0.90),
            "p99_ms": _pct(steps, 0.99), "step_total_s": total_s,
            "infeed_wait_s": n["infeed_s"], "infeed_stall_frac": stall,
            "mfu": mfu, "items": n["items"],
        }
        s = stats["nodes"][name]
        lines.append(
            f"{name:<16} {n['role']:<10} {s['steps']:>6} {s['p50_ms']:>8.1f} "
            f"{s['p90_ms']:>8.1f} {s['p99_ms']:>8.1f} {total_s:>8.2f} "
            f"{n['infeed_s']:>9.3f} {stall:>6.2f} "
            f"{(f'{mfu:.3f}' if mfu is not None else '-'):>6}")
    return "\n".join(lines) + "\n", stats


# -- single-request causal view (--trace) ----------------------------------


def find_trace(pairs, needle):
    """Resolve ``needle`` (a full trace_id or any unique prefix) against
    every record's ``attrs.trace_id``.  Returns ``(full_id, records)``
    with the records ts-sorted; raises ``ValueError`` when nothing (or
    more than one trace) matches."""
    by_id = {}
    for rec, _src in pairs:
        tid = (rec.get("attrs") or {}).get("trace_id")
        if tid:
            by_id.setdefault(str(tid), []).append(rec)
    matches = sorted(t for t in by_id if t.startswith(str(needle)))
    if not matches:
        raise ValueError(
            f"no records carry trace_id {needle!r} "
            f"({len(by_id)} distinct traces in this directory)")
    if len(matches) > 1:
        heads = ", ".join(m[:16] for m in matches[:6])
        raise ValueError(
            f"trace prefix {needle!r} is ambiguous: {heads}"
            + ("…" if len(matches) > 6 else ""))
    tid = matches[0]
    return tid, sorted(by_id[tid], key=lambda r: r["ts"])


def _span_tree(recs):
    """(spans_by_id, children, roots, orphans) over one trace's records.

    Span ``ts`` is the START time (telemetry writes at exit with the
    entry timestamp), so tree + offsets need no reconstruction.  A span
    whose parent_id names a span that never reached any spool (e.g. its
    writer was SIGKILLed) is an *orphan* — reported, never dropped."""
    spans = {}
    for rec in recs:
        sid = (rec.get("attrs") or {}).get("span_id")
        if rec["kind"] == "span" and sid:
            spans[sid] = rec
    children = {}
    roots, orphans = [], []
    for sid, rec in spans.items():
        parent = (rec.get("attrs") or {}).get("parent_id")
        if parent and parent in spans:
            children.setdefault(parent, []).append(sid)
        elif parent:
            orphans.append(sid)
        else:
            roots.append(sid)
    def start(sid):
        return spans[sid]["ts"]

    for kids in children.values():
        kids.sort(key=start)
    roots.sort(key=start)
    orphans.sort(key=start)
    return spans, children, roots, orphans


def _bar(off_ms, dur_ms, wall_ms, width=30):
    if wall_ms <= 0:
        return ""
    lo = int(width * off_ms / wall_ms)
    hi = max(lo + 1, int(width * (off_ms + dur_ms) / wall_ms))
    return "[" + " " * lo + "#" * (hi - lo) + " " * (width - hi) + "]"


def render_waterfall(trace_id, recs):
    """One trace's records -> (waterfall + critical path text, stats)."""
    spans, children, roots, orphans = _span_tree(recs)
    events = [r for r in recs
              if r["kind"] != "span" or not (r.get("attrs") or {}).get(
                  "span_id")]
    t0 = min(r["ts"] for r in recs)
    t1 = max(r["ts"] + (r["dur_ms"] or 0.0) / 1e3 for r in recs)
    wall = (t1 - t0) * 1e3
    nodes = sorted({r["node_id"] for r in recs})
    lines = [f"trace {trace_id}: {len(spans)} spans, "
             f"{len(events)} events, {len(nodes)} nodes "
             f"({', '.join(nodes)}), {wall:.1f}ms wall"]
    lines.append("")
    lines.append(f"{'offset':>9} {'dur_ms':>9} {'node':<16} span")

    def emit(sid, depth):
        rec = spans[sid]
        off = (rec["ts"] - t0) * 1e3
        dur = float(rec["dur_ms"] or 0.0)
        name = ("  " * depth + ("└ " if depth else "") + rec["name"])
        lines.append(f"{off:>9.1f} {dur:>9.1f} {rec['node_id']:<16} "
                     f"{name:<34} {_bar(off, dur, wall)}")
        for kid in children.get(sid, ()):
            emit(kid, depth + 1)

    for root in roots:
        emit(root, 0)
    for sid in orphans:
        emit(sid, 0)
    if orphans:
        lines.append(f"  ({len(orphans)} span(s) whose parent never "
                     f"reached a spool — a writer died before flush?)")
    if events:
        lines.append("")
        lines.append("events:")
        for rec in events:
            off = (rec["ts"] - t0) * 1e3
            hints = {k: v for k, v in (rec.get("attrs") or {}).items()
                     if k in ("queue_ms", "sid", "slot", "reason", "depth")}
            hint = " ".join(f"{k}={v}" for k, v in sorted(hints.items()))
            lines.append(f"{off:>9.1f} {'·':>9} {rec['node_id']:<16} "
                         f"{rec['name']:<34} {hint}")

    # critical path: from the first root, always descend into the child
    # that finishes last — the chain that bounded the request's latency
    path = []
    if roots:
        sid = roots[0]
        while True:
            path.append(sid)
            kids = children.get(sid, ())
            if not kids:
                break
            sid = max(kids, key=lambda s: (spans[s]["ts"]
                                           + (spans[s]["dur_ms"] or 0) / 1e3))
    crit = decompose(recs, spans[roots[0]] if roots else None)
    lines.append("")
    lines.append(f"-- critical path ({len(path)} spans) --")
    if path:
        lines.append(" -> ".join(spans[s]["name"] for s in path))
    lines.append("-- request decomposition (ms) --")
    for k in ("queue", "prefill", "decode", "other", "total"):
        if crit.get(k) is not None:
            lines.append(f"{k:<8} {crit[k]:>9.1f}")
    stats = {"trace_id": trace_id, "spans": len(spans),
             "events": len(events), "nodes": nodes, "wall_ms": wall,
             "orphans": len(orphans),
             "critical_path": [spans[s]["name"] for s in path],
             "decomposition": crit}
    return "\n".join(lines) + "\n", stats


def decompose(recs, root):
    """Queue / prefill / decode / other milliseconds for one request.

    queue   = decode/admit's queue_ms (driver->replica admission wait);
    prefill = first-token latency minus the queue (ttft_ms rides
              decode/session and serve/generate result attrs);
    decode  = generation time (decode/retire's span duration) minus
              prefill; ``other`` is whatever of the root span the three
              phases don't explain: dispatch, transfer, uninstrumented.
    Every term is None when its source attr never appeared (a predict
    request has no decode phases)."""
    total = float(root["dur_ms"]) if root and root["dur_ms"] else None
    queue = ttft = gen = None
    for rec in recs:
        attrs = rec.get("attrs") or {}
        if attrs.get("queue_ms") is not None and queue is None:
            queue = float(attrs["queue_ms"])
        if attrs.get("ttft_ms") is not None and ttft is None:
            ttft = float(attrs["ttft_ms"])
        if rec["name"] == "decode/retire" and rec["dur_ms"] is not None:
            gen = float(rec["dur_ms"])
    out = {"total": total, "queue": queue, "prefill": None,
           "decode": None, "other": None}
    if ttft is not None:
        out["prefill"] = max(0.0, ttft - (queue or 0.0))
    if gen is not None:
        out["decode"] = max(0.0, gen - (out["prefill"] or 0.0))
    if total is not None:
        known = sum(v for v in (queue, out["prefill"], out["decode"])
                    if v is not None)
        out["other"] = max(0.0, total - known)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("run_dir", help="telemetry dir (run-<id>/ or the root)")
    ap.add_argument("--out", default=None,
                    help="Chrome trace path (default <run_dir>/trace.json)")
    ap.add_argument("--summary-out", default=None,
                    help="also write the text summary to this path")
    ap.add_argument("--summary-json", default=None, metavar="OUT",
                    help="write the summary stats (the same numbers as "
                         "the text report) as JSON for CI / bench_check")
    ap.add_argument("--trace", default=None, metavar="TRACE_ID",
                    help="render one request's causal waterfall + "
                         "critical path instead of the merged summary "
                         "(full trace_id or any unique prefix)")
    ap.add_argument("--xplane", default=None, metavar="CAPTURE_DIR",
                    help="a utils/profiler capture directory: its host "
                         "spans and device operations join the Chrome "
                         "trace on the spool's wall clock (needs jax)")
    args = ap.parse_args(argv)

    if not os.path.isdir(args.run_dir):
        ap.error(f"not a directory: {args.run_dir}")
    pairs, skipped = load_records(args.run_dir)
    if not pairs:
        print(f"trace_merge: no telemetry records under {args.run_dir}",
              file=sys.stderr)
        return 1

    if args.trace:
        try:
            tid, recs = find_trace(pairs, args.trace)
        except ValueError as e:
            print(f"trace_merge: {e}", file=sys.stderr)
            return 1
        text, stats = render_waterfall(tid, recs)
        if args.summary_json:
            with open(args.summary_json, "w", encoding="utf-8") as f:
                json.dump(stats, f, indent=1, sort_keys=True, default=str)
                f.write("\n")
        sys.stdout.write(text)
        return 0

    capture = ()
    if args.xplane:
        try:
            capture = load_xplane(args.xplane)
        except ValueError as e:
            print(f"trace_merge: {e}", file=sys.stderr)
            return 1
    out = args.out or os.path.join(args.run_dir, "trace.json")
    trace = to_chrome_trace(pairs, capture)
    with open(out, "w", encoding="utf-8") as f:
        json.dump(trace, f)
    text, stats = summarize(pairs, skipped)
    if args.summary_out:
        with open(args.summary_out, "w", encoding="utf-8") as f:
            f.write(text)
    if args.summary_json:
        with open(args.summary_json, "w", encoding="utf-8") as f:
            json.dump(stats, f, indent=1, sort_keys=True, default=str)
            f.write("\n")
    sys.stdout.write(text)
    print(f"\nchrome trace: {out} ({len(trace['traceEvents'])} events) — "
          f"load at https://ui.perfetto.dev")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
