"""From a run's RAW profiler capture to what the PROGRAM says about itself:
its ``tfos/<layer>/<phase>`` spans (``tensorflowonspark_tpu/utils/
telemetry.py`` writes them into the capture as TraceMe events), the
device's idle gaps put down to those spans, device time per kernel and
per program, and the collectives.  ``benchmark/README-program-trace.md``
says how a per-layer metric is added on top of it.

``lib/trace.py`` (PR23) keeps only ``bench/*`` host spans; this module
reads the same capture again, in a short-lived CHILD process (reading
needs jax's ``ProfileData``; the parent of a run never imports jax), and
memoises the reduction per run.  ``reduce`` is plain arithmetic over

    {plane: {line: [[name, start_ns, dur_ns, {arg: value}], ...]}}

and is what tests/benchmark_tests checks on a small hand-made trace.

By hand, on any capture directory (``utils/profiler``'s, the
benchmark's ``--keep-work`` traces, a ``POST /profilez`` capture):

    python3 benchmark/lib/program_trace.py <capture_dir>

What one TPU capture looks like (my chip run, PR25; jax 0.9.0): the
device plane has the lines ``XLA Modules`` (one event per program run,
named ``jit_<function>(<fingerprint>)``), ``XLA Ops`` (one event per HLO
instruction, named by its whole text; a Pallas kernel is the instruction
``%<name=>.N``) and ``Async XLA Ops`` (a start..done span per
asynchronous operation); host threads are lines named after the process
(``python3``), not after the Python thread, so a thread is known by the
spans it carries.  ``jax.named_scope`` paths sit in the events' metadata,
which ``ProfileData`` does not expose: device time is reduced per
instruction name and per program, not per scope (PERF.md, section 7).
Timestamps count from the capture's start, on one clock for host and
device.
"""

import bisect
import glob
import json
import os
import re
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if sys.path and os.path.abspath(sys.path[0] or ".") == HERE:
    sys.path.pop(0)  # run as a script: lib/trace.py is not stdlib's trace
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import trace as T  # noqa: E402

SPAN_PREFIXES = ("tfos/", "bench/")
PROGRAM_PREFIX = "tfos/"
MODULES_LINE = "XLA Modules"
ASYNC_LINE = "Async XLA Ops"
STEP_SPAN = "bench/dispatch_step"
# the thread that dispatches device work is known by these spans ...
DISPATCH_SPANS = (STEP_SPAN, "tfos/decode/step_dispatch")
# ... and the feed's consumer thread by these
FEED_THREAD_SPANS = ("tfos/feed/ring_wait", "tfos/feed/ring_read",
                     "tfos/feed/to_columns", "tfos/feed/collate",
                     "tfos/feed/h2d", "tfos/feed/stage_full")
FEED_BUSY_SPANS = ("tfos/feed/ring_read", "tfos/feed/to_columns",
                   "tfos/feed/collate", "tfos/feed/h2d")


def is_pure_wait(name):
    """A span in which the dispatching thread only waits for the feed:
    it says THAT the input had not landed, not why; the feed's own
    thread names the cause."""
    return name.startswith("bench/wait_") or name == "tfos/feed/next"


# -- reading (needs jax; runs in the child) ----------------------------------

def read_raw(path):
    """The capture at ``path`` (an ``.xplane.pb``) as plain data: the
    host lines' ``tfos/*`` and ``bench/*`` spans with their scalar
    arguments, and the device planes' operation, asynchronous-operation
    and program lines under shortened names."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(T.DEVICE_PLANE_PREFIX)
        for i, line in enumerate(plane.lines):
            if device:
                if line.name not in (T.OPS_LINE, ASYNC_LINE, MODULES_LINE):
                    continue
                short = (program_name if line.name == MODULES_LINE
                         else T.short_name)
                events = [[short(ev.name), ev.start_ns, ev.duration_ns, {}]
                          for ev in line.events]
            else:
                events = [[ev.name, ev.start_ns, ev.duration_ns,
                           {k: v for k, v in ev.stats
                            if isinstance(v, (int, float))}]
                          for ev in line.events
                          if ev.name.startswith(SPAN_PREFIXES)]
            if events:
                out.setdefault(plane.name, {})[f"{line.name}#{i}"] = events
    return out


def program_name(name):
    """``jit_tfos_prefill(8506641229564554942)`` -> ``jit_tfos_prefill``."""
    return re.sub(r"\(\d+\)$", "", name)


def kernel_name(name):
    """``%tfos_flash_fwd.1 [tpu_custom_call]`` -> ``tfos_flash_fwd``: an
    instruction's name without ``%``, its numbering and its target."""
    return re.sub(r"(\.\d+)?( \[.*\])?$", "", name.lstrip("%"))


# -- arithmetic ---------------------------------------------------------------

def self_segments(events):
    """``[(start, end, name)]``, sorted and disjoint: for every instant
    covered by the events of ONE line, the DEEPEST event open then (an
    event's self time is the sum of its segments)."""
    out = []
    stack = []  # (end, name)
    cursor = None

    def emit(until):
        nonlocal cursor
        if stack and until > cursor:
            out.append((cursor, until, stack[-1][1]))
            cursor = until

    for name, s, d, _args in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= s:
            emit(stack[-1][0])
            stack.pop()
        if stack:
            emit(s)
        cursor = s
        stack.append((s + d, name))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    return out


def overlaps(segments, starts, lo, hi):
    """``[(name, ns, from, to)]``: how much of [lo, hi) each of the
    sorted disjoint ``segments`` covers, and where (``starts`` are the
    segments' starts)."""
    out = []
    i = max(bisect.bisect_right(starts, lo) - 1, 0)
    while i < len(segments) and segments[i][0] < hi:
        s, e, name = segments[i]
        ov = min(e, hi) - max(s, lo)
        if ov > 0:
            out.append((name, ov, max(s, lo), min(e, hi)))
        i += 1
    return out


def span_table(events, segments):
    """``{name: {count, total_s, self_s, args}}`` of one host line, whose
    ``self_segments`` the caller has."""
    table = {}
    for name, _s, d, args in events:
        row = table.setdefault(name, {"count": 0, "total_s": 0.0,
                                      "self_s": 0.0, "args": {}})
        row["count"] += 1
        row["total_s"] += d / 1e9
        for k, v in args.items():
            row["args"][k] = row["args"].get(k, 0) + v
    for s, e, name in segments:
        table[name]["self_s"] += (e - s) / 1e9
    return table


def subtract(intervals, holes):
    """Total length of the merged ``intervals`` outside the merged
    ``holes``."""
    total, j = 0.0, 0
    for s, e in intervals:
        cur = s
        while j < len(holes) and holes[j][1] <= cur:
            j += 1
        k = j
        while k < len(holes) and holes[k][0] < e:
            if holes[k][0] > cur:
                total += holes[k][0] - cur
            cur = max(cur, holes[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def reduce(events):
    """Reduce one capture to plain data (see the module docstring).

    ``extent_s``   first start to last end of everything kept: the slice
                   that host-span shares are taken of
    ``threads``    per host line, ``span_table`` of its spans
    ``spans``      the same, summed over host lines
    ``dispatch_thread`` / ``feed_thread``  the lines that carry
                   ``DISPATCH_SPANS`` / ``FEED_THREAD_SPANS`` (or None)
    ``steps``      count of ``bench/dispatch_step``
    ``device``     None without a device plane (a CPU rehearsal); else
                   ``busy_s``/``window_s``/``idle_s`` of the first device,
                   ``kernels`` (self seconds per instruction name, averaged
                   over devices), ``programs`` (seconds and runs per jitted
                   program, first device), ``idle_by`` (the idle gaps of
                   the first device put down to spans, seconds per name,
                   "unnamed" for the rest), ``idle_named_frac`` (the share
                   of idle time put down to a ``tfos/*`` span),
                   ``collective_s`` and ``collective_exposed_s``
    """
    devices = sorted(p for p in events
                     if p.startswith(T.DEVICE_PLANE_PREFIX))
    hosts = {f"{p}|{ln}": evs for p in events if p not in devices
             for ln, evs in events[p].items()}
    everything = [e for p in events for evs in events[p].values()
                  for e in evs]
    if not everything:
        return None
    lo = min(e[1] for e in everything)
    hi = max(e[1] + e[2] for e in everything)
    segments = {ln: self_segments(evs) for ln, evs in hosts.items()}
    threads = {ln: span_table(evs, segments[ln])
               for ln, evs in hosts.items()}
    spans = {}
    for table in threads.values():
        for name, row in table.items():
            tot = spans.setdefault(name, {"count": 0, "total_s": 0.0,
                                          "self_s": 0.0, "args": {}})
            for k in ("count", "total_s", "self_s"):
                tot[k] += row[k]
            for k, v in row["args"].items():
                tot["args"][k] = tot["args"].get(k, 0) + v

    def thread_with(names):
        best = max(threads, default=None, key=lambda ln: sum(
            threads[ln].get(n, {"count": 0})["count"] for n in names))
        if best is None or not any(n in threads[best] for n in names):
            return None
        return best

    out = {"extent_s": (hi - lo) / 1e9, "threads": threads, "spans": spans,
           "dispatch_thread": thread_with(DISPATCH_SPANS),
           "feed_thread": thread_with(FEED_THREAD_SPANS),
           "steps": spans.get(STEP_SPAN, {"count": 0})["count"],
           "device": None}
    if not devices:
        return out

    def lines(plane, kind):
        return [e for ln, evs in events[plane].items()
                if ln.split("#")[0] == kind for e in evs]

    kernels = {}
    for p in devices:
        for name, sec in T.self_seconds(
                [e[:3] for e in lines(p, T.OPS_LINE)]).items():
            k = kernel_name(name)
            kernels[k] = kernels.get(k, 0.0) + sec / len(devices)
    first = devices[0]
    ops = lines(first, T.OPS_LINE)
    merged = T._merge((s, s + d) for _n, s, d, _a in ops)
    if not merged:
        return out
    busy = sum(e - s for s, e in merged)
    window = merged[-1][1] - merged[0][0]
    programs = {}
    for name, _s, d, _a in lines(first, MODULES_LINE):
        row = programs.setdefault(name, {"runs": 0, "seconds": 0.0})
        row["runs"] += 1
        row["seconds"] += d / 1e9

    # idle gaps -> the dispatching thread's deepest span; where that is a
    # pure wait, the feed thread's deepest span over the same stretch
    def segs_of(ln):
        segs = segments[ln] if ln else []
        return segs, [s for s, _e, _n in segs]

    d_segs, d_starts = segs_of(out["dispatch_thread"])
    f_segs, f_starts = segs_of(out["feed_thread"]
                               if out["feed_thread"]
                               != out["dispatch_thread"] else None)
    idle_by = {}

    def put(name, ns):
        idle_by[name] = idle_by.get(name, 0.0) + ns / 1e9

    for (_s0, g0), (g1, _e1) in zip(merged, merged[1:]):
        left = g1 - g0
        for name, ov, a, b in overlaps(d_segs, d_starts, g0, g1):
            left -= ov
            if not is_pure_wait(name):
                put(name, ov)
                continue
            inner = ov
            for fname, fov, _a, _b in overlaps(f_segs, f_starts, a, b):
                put(fname, fov)
                inner -= fov
            if inner > 0:
                put(name, inner)  # a wait nobody explains
        if left > 0:
            put("unnamed", left)
    idle = sum(idle_by.values())
    named = sum(v for k, v in idle_by.items()
                if k.startswith(PROGRAM_PREFIX) and not is_pure_wait(k))

    # collectives: their union (asynchronous spans included) and the part
    # of it during which nothing else runs.  A loop or a call that HOLDS a
    # collective is not "something else".
    each = sorted((s, s + d) for kind in (T.OPS_LINE, ASYNC_LINE)
                  for n, s, d, _a in lines(first, kind)
                  if T.is_collective_op(n))
    coll = T._merge(each)
    c_starts = [s for s, _e in each]

    def holds_collective(s, e):
        i = bisect.bisect_left(c_starts, s)
        while i < len(each) and each[i][0] < e:
            if each[i][1] <= e:
                return True
            i += 1
        return False

    other = T._merge(
        (s, s + d) for n, s, d, _a in ops
        if not T.is_collective_op(n) and not holds_collective(s, s + d))
    out["device"] = {
        "busy_s": busy / 1e9, "window_s": window / 1e9,
        "idle_s": idle, "kernels": kernels, "programs": programs,
        "idle_by": idle_by,
        "idle_named_frac": named / idle if idle else None,
        "collective_s": sum(e - s for s, e in coll) / 1e9,
        "collective_exposed_s": subtract(coll, other) / 1e9,
    }
    return out


# -- finding a run's capture, and the memo ------------------------------------

def find_capture(facts):
    """The raw capture directory of the run that left ``facts``, or None.
    Training facts carry it (``nodes[i].trace_dir`` of process 0); the
    serving runner leaves it at ``.bench_work/<cell>/trace-replica``
    beside the facts file ``run.py`` is reading."""
    for node in facts.get("nodes") or ():
        if node.get("trace_dir") and node.get("process_index", 0) == 0:
            return node["trace_dir"]
    if facts.get("nodes"):
        return None
    for root in dict.fromkeys((os.getcwd(), ROOT)):
        for cap in glob.glob(os.path.join(root, ".bench_work", "*",
                                          "trace-replica")):
            try:
                with open(os.path.join(os.path.dirname(cap),
                                       "facts.json")) as f:
                    theirs = json.load(f)
            except (OSError, ValueError):
                continue
            if all(theirs.get(k) == facts.get(k)
                   for k in ("setup_s", "window_s", "attempted")):
                return cap
    return None


_MEMO = {}


def load(facts):
    """The reduction of this run's capture, or None when the run took
    none (``--trace 0``) or it cannot be read.  Read once per run, in a
    child process; every reader of the run shares the result."""
    cap = find_capture(facts)
    if cap is None:
        return None
    if cap not in _MEMO:
        _MEMO[cap] = _reduce_in_child(cap)
        if _MEMO[cap] is not None:
            print("[bench:program_trace] " + describe(_MEMO[cap]),
                  flush=True)
    return _MEMO[cap]


def _reduce_in_child(capture_dir):
    path = T.find_xplane(capture_dir)
    if path is None:
        return None
    env = dict(os.environ, JAX_PLATFORMS="cpu")  # never reach for a chip
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--json", path],
            env=env, capture_output=True, text=True, timeout=600)
        return json.loads(proc.stdout.splitlines()[-1]) \
            if proc.returncode == 0 else None
    except (subprocess.SubprocessError, ValueError, IndexError):
        return None


def describe(reduced):
    """One earlier-line sentence: where the idle time went."""
    dev = reduced.get("device")
    if not dev:
        return (f"program spans over {reduced['extent_s']:.3f}s, no device "
                f"plane: {sorted(reduced['spans'])}")
    top = sorted(dev["idle_by"].items(), key=lambda kv: -kv[1])[:6]
    progs = sorted(dev["programs"].items(),
                   key=lambda kv: -kv[1]["seconds"])[:4]
    return (f"device idle {dev['idle_s']:.3f}s of {dev['window_s']:.3f}s; "
            f"share of idle time put down to a named tfos/* span "
            f"{dev['idle_named_frac']}; idle by span "
            f"{[[k, round(v, 4)] for k, v in top]}; programs "
            f"{[[k, v['runs'], round(v['seconds'], 4)] for k, v in progs]}")


# -- what the metric readers share --------------------------------------------

def span(reduced, name):
    """The summed row of span ``name``, or None when the capture has
    none: the program under test does not write it."""
    return (reduced or {}).get("spans", {}).get(name)


def feed_thread_self_frac(facts, names):
    """Self time of ``names`` on the feed's consumer thread over the
    slice.  None without a capture or where the program writes no feed
    spans at all (the parent of PR25); 0.0 where it writes them and none
    of ``names`` fell into the slice."""
    reduced = load(facts)
    if not reduced or not reduced["feed_thread"] or not reduced["extent_s"]:
        return None
    table = reduced["threads"][reduced["feed_thread"]]
    return sum(table[n]["self_s"] for n in names if n in table) \
        / reduced["extent_s"]


def main(argv):
    if len(argv) == 2 and argv[0] == "--json":
        print(json.dumps(reduce(read_raw(argv[1]))))
        return 0
    if len(argv) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    path = argv[0] if argv[0].endswith(".pb") else T.find_xplane(argv[0])
    if not path:
        print(f"no *.xplane.pb under {argv[0]}", file=sys.stderr)
        return 1
    print(json.dumps(reduce(read_raw(path)), indent=1, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
