#!/usr/bin/env python
"""Perf-regression gate over the repo's ``BENCH_*.json`` lines.

Diffs the newest usable bench line against the prior round, lane by
lane (ResNet img/s, transformer tok/s, fed img/s, data rec/s, serve
p99, decode tokens/s + p99s, ...), and exits non-zero when any lane
regressed past the
tolerance — the CI-shaped check the session scripts run after a bench
step so a perf cliff is a red line in the log, not an archaeology
project (PERF.md history stays the narrative; this is the gate).

Bench files come in two shapes and both are handled:

- bare bench lines (``BENCH_session_*.json``): the one-JSON-line
  ``{"metric", "value", "unit", "extra": {lanes...}}`` record bench.py
  prints;
- driver wrappers (``BENCH_r0N.json``): ``{"n", "cmd", "rc", "tail",
  "parsed"}`` where ``parsed`` (or the last JSON object line of
  ``tail``) is the bench line.

Lines without a measurement (``"value": null`` + ``extra.error``)
carry no lane numbers and are skipped, so the gate compares
the two most recent rounds that actually measured something.  Lanes
disabled in one round (``TFOS_BENCH_*=0``) are simply absent and not
compared — only lanes present on BOTH sides count.

Exit codes: 0 OK / skip (nothing comparable), 1 regression,
2 usage error.

Usage::

    python scripts/bench_check.py [--dir REPO] [--tolerance 0.10]
    python scripts/bench_check.py --baseline OLD.json --latest NEW.json
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

TOL_ENV = "TFOS_BENCH_TOL"

# (lane label, path into the bench line, higher_is_better).
# ("value",) is the headline metric (ResNet train MFU).  NOTE: bench
# lines before round 4 counted ResNet FLOPs as GMacs (exactly half the
# 2-FLOPs/MAC convention) — mfu comparisons across that boundary are
# apples-to-oranges; throughput lanes never changed convention.
LANES = (
    ("resnet.mfu", ("value",), True),
    ("resnet.img_s", ("extra", "images_per_sec_per_chip"), True),
    ("transformer.tok_s",
     ("extra", "transformer", "tokens_per_sec_per_chip"), True),
    ("fed.img_s", ("extra", "fed", "images_per_sec_per_chip"), True),
    ("data.raw_rec_s", ("extra", "data", "raw_records_per_sec"), True),
    ("data.pipeline_rec_s",
     ("extra", "data", "pipeline_records_per_sec"), True),
    ("data.service_rec_s",
     ("extra", "data", "service_records_per_sec"), True),
    ("data.dynamic_rec_s",
     ("extra", "data", "dynamic_records_per_sec"), True),
    ("data.straggler_speedup",
     ("extra", "data", "straggler_speedup"), True),
    ("data.cache_hit_rec_s",
     ("extra", "data", "cache_hit_records_per_sec"), True),
    ("tfrecord.columnar_rec_s",
     ("extra", "tfrecord_read", "columnar_records_per_sec"), True),
    ("serve.req_s", ("extra", "serve", "req_per_sec"), True),
    ("serve.p99_ms", ("extra", "serve", "p99_ms"), False),
    ("decode.tok_s", ("extra", "decode", "tokens_per_sec"), True),
    ("decode.ttft_p50_ms", ("extra", "decode", "ttft_p50_ms"), False),
    ("decode.ttft_p99_ms", ("extra", "decode", "ttft_p99_ms"), False),
    ("decode.tok_p99_ms", ("extra", "decode", "tok_p99_ms"), False),
    ("decode.prefix_hit_rate",
     ("extra", "decode", "prefix_hit_rate"), True),
    ("decode.prefill_tok_saved",
     ("extra", "decode", "prefill_tokens_saved"), True),
    ("fabric.req_s", ("extra", "serve_fabric", "req_per_sec"), True),
    ("fabric.p99_ms", ("extra", "serve_fabric", "p99_ms"), False),
    ("fabric.dropped", ("extra", "serve_fabric", "dropped"), False),
    ("fabric.affinity_hit_rate",
     ("extra", "serve_fabric", "affinity_hit_rate"), True),
    ("fabric.scale_ups", ("extra", "serve_fabric", "scale_ups"), True),
    ("elastic.resize_ms", ("extra", "elastic", "resize_ms"), False),
    ("elastic.reshard_ms", ("extra", "elastic", "reshard_ms"), False),
    ("elastic_serve.resize_ms",
     ("extra", "elastic_serve", "resize_ms"), False),
    ("elastic_serve.degraded_p99_ms",
     ("extra", "elastic_serve", "degraded_p99_ms"), False),
    ("elastic_serve.dropped", ("extra", "elastic_serve", "dropped"), False),
    ("deploy.promote_s", ("extra", "deploy", "promote_s"), False),
    ("deploy.rollback_s", ("extra", "deploy", "rollback_s"), False),
    ("deploy.p99_ms", ("extra", "deploy", "p99_ms"), False),
    ("deploy.dropped", ("extra", "deploy", "dropped"), False),
    ("actors.ask_p50_ms", ("extra", "actors", "ask_p50_ms"), False),
    ("actors.ask_p99_ms", ("extra", "actors", "ask_p99_ms"), False),
    ("actors.respawn_resume_ms",
     ("extra", "actors", "respawn_resume_ms"), False),
)

# Absolute floors, checked on the NEWEST line alone (no baseline
# needed): lanes whose meaning is a contract, not a trend.  A
# straggler_speedup near 1.0 means dispatch regressed to static-shard
# behavior — that must fail even if the prior round was just as bad.
# fabric.scale_ups < 1 means the autoscaler provably never scaled under
# the lane's induced queueing; a zero affinity_hit_rate means session
# routing stopped landing returning sessions on their bound replica.
FLOORS = {
    "data.straggler_speedup": 1.2,
    "fabric.scale_ups": 1.0,
    "fabric.affinity_hit_rate": 0.001,
}

# Absolute ceilings, the floors' mirror: fabric.dropped is the fabric
# lane's zero-drop contract (client-visible errors across the mid-run
# SIGKILL), pinned at 0 regardless of what the prior round did.
CEILINGS = {
    "fabric.dropped": 0.0,
}

# Contract lanes whose round-over-round trend is meaningless (how MANY
# times the autoscaler stepped is load-shape, not performance): gated
# by FLOORS/CEILINGS above, excluded from the relative comparison.
FLOOR_ONLY = frozenset({"fabric.scale_ups", "fabric.affinity_hit_rate"})


def _dig(obj, path):
    for p in path:
        if not isinstance(obj, dict) or p not in obj:
            return None
        obj = obj[p]
    if isinstance(obj, bool) or not isinstance(obj, (int, float)):
        return None
    return float(obj)


def extract_line(doc):
    """The bench line dict from either file shape, or None."""
    if not isinstance(doc, dict):
        return None
    if "metric" in doc:
        return doc
    parsed = doc.get("parsed")
    if isinstance(parsed, dict) and "metric" in parsed:
        return parsed
    tail = doc.get("tail")
    if isinstance(tail, str):
        for line in reversed(tail.splitlines()):
            line = line.strip()
            if not (line.startswith("{") and line.endswith("}")):
                continue
            try:
                cand = json.loads(line)
            except ValueError:
                continue
            if isinstance(cand, dict) and "metric" in cand:
                return cand
    return None


def lanes_of(line):
    """{lane label: value} for every lane the line carries."""
    out = {}
    for label, path, _hib in LANES:
        v = _dig(line, path)
        if v is not None:
            out[label] = v
    return out


def load_bench(path):
    """(lanes dict, bench line) for one file; ({}, None) if unusable."""
    try:
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return {}, None
    line = extract_line(doc)
    if line is None:
        return {}, None
    return lanes_of(line), line


def discover(bench_dir):
    """Usable bench files, oldest -> newest.  Ordered by mtime with the
    filename as tiebreak (checkout-restored files share one mtime;
    BENCH_r01 < ... < BENCH_session_* sorts rounds correctly there)."""
    paths = sorted(glob.glob(os.path.join(bench_dir, "BENCH_*.json")),
                   key=lambda p: (os.path.getmtime(p), p))
    out = []
    for p in paths:
        lanes, line = load_bench(p)
        if lanes:
            out.append((p, lanes))
    return out


def compare(old_lanes, new_lanes, tolerance):
    """[(label, old, new, rel_change, regressed)] over shared lanes."""
    rows = []
    for label, _path, hib in LANES:
        if label in FLOOR_ONLY:
            continue
        if label not in old_lanes or label not in new_lanes:
            continue
        old, new = old_lanes[label], new_lanes[label]
        if old <= 0:
            # zero is a meaningful floor for lower-is-better lanes
            # (elastic_serve.dropped: the zero-drop contract) — any
            # departure from it regresses; ratios are undefined, so
            # report the absolute delta as the change
            if old == 0 and not hib:
                rows.append((label, old, new, new, new > tolerance))
            continue
        rel = (new - old) / old
        regressed = (rel < -tolerance) if hib else (rel > tolerance)
        rows.append((label, old, new, rel, regressed))
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--dir", default=None,
                    help="directory holding BENCH_*.json (default: the "
                         "repo root above this script)")
    ap.add_argument("--tolerance", type=float,
                    default=float(os.environ.get(TOL_ENV, "0.10")),
                    help="allowed fractional regression per lane "
                         f"(default 0.10; env {TOL_ENV})")
    ap.add_argument("--baseline", default=None,
                    help="explicit prior bench file (skips discovery)")
    ap.add_argument("--latest", default=None,
                    help="explicit newest bench file (skips discovery)")
    ap.add_argument("--quiet", action="store_true",
                    help="suppress the per-lane table (verdict only)")
    args = ap.parse_args(argv)

    if bool(args.baseline) != bool(args.latest):
        ap.error("--baseline and --latest must be given together")
    if args.baseline:
        old_path, new_path = args.baseline, args.latest
        old_lanes, _ = load_bench(old_path)
        new_lanes, _ = load_bench(new_path)
        if not new_lanes or not old_lanes:
            print("bench_check: ERROR unusable bench file "
                  f"({old_path if not old_lanes else new_path})")
            return 2
    else:
        bench_dir = args.dir or os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))
        usable = discover(bench_dir)
        if len(usable) < 2:
            print(f"bench_check: SKIP ({len(usable)} usable BENCH line(s) "
                  f"under {bench_dir}; need 2 to compare)")
            return 0
        (old_path, old_lanes), (new_path, new_lanes) = usable[-2], usable[-1]

    floor_bad = [(label, new_lanes[label], floor)
                 for label, floor in sorted(FLOORS.items())
                 if label in new_lanes and new_lanes[label] < floor]
    for label, value, floor in floor_bad:
        print(f"  {label:<24} {value:>12.2f} below floor {floor:.2f}  "
              f"REGRESSED")
    ceil_bad = [(label, new_lanes[label], ceil)
                for label, ceil in sorted(CEILINGS.items())
                if label in new_lanes and new_lanes[label] > ceil]
    for label, value, ceil in ceil_bad:
        print(f"  {label:<24} {value:>12.2f} above ceiling {ceil:.2f}  "
              f"REGRESSED")
    floor_bad += ceil_bad
    rows = compare(old_lanes, new_lanes, args.tolerance)
    if not rows and not floor_bad:
        print("bench_check: SKIP (no lane present in both "
              f"{os.path.basename(old_path)} and "
              f"{os.path.basename(new_path)})")
        return 0
    if not args.quiet:
        for label, old, new, rel, regressed in rows:
            flag = "REGRESSED" if regressed else "ok"
            print(f"  {label:<24} {old:>12.2f} -> {new:>12.2f} "
                  f"{rel:>+7.1%}  {flag}")
    bad = [r for r in rows if r[4]]
    names = (os.path.basename(new_path), os.path.basename(old_path))
    if floor_bad:
        label, value, bound = floor_bad[0]
        print(f"bench_check: REGRESSION {label} {value:.2f} outside "
              f"absolute bound {bound:.2f} newest={names[0]} "
              f"[{len(floor_bad)} floor/ceiling violation(s), "
              f"{len(bad)}/{len(rows)} lanes regressed]")
        return 1
    if bad:
        worst = max(bad, key=lambda r: abs(r[3]))
        print(f"bench_check: REGRESSION {worst[0]} {worst[3]:+.1%} "
              f"({worst[1]:.2f} -> {worst[2]:.2f}, tol "
              f"{args.tolerance:.0%}) newest={names[0]} prior={names[1]} "
              f"[{len(bad)}/{len(rows)} lanes regressed]")
        return 1
    worst = min(rows, key=lambda r: r[3] if r[4] is False else 0)
    print(f"bench_check: OK newest={names[0]} prior={names[1]} "
          f"lanes={len(rows)} worst={worst[0]} {worst[3]:+.1%} "
          f"(tol {args.tolerance:.0%})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
