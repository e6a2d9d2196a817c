"""A serving capture on ONE clock: the device's timestamps moved onto the
host's by the clock probes the program writes while a capture records
(``tfos/clock/probe`` spans, each around one run of
``jit_tfos_clock_probe``: ``tensorflowonspark_tpu/utils/profiler.py``),
then, per decode iteration of the engine thread, the step's launch and the
ids' return, and the device's idle gaps put down to the span the host was
really in.  ``benchmark/README-aligned-trace.md`` says why.

``lib/program_trace.py`` takes host and device stamps as one clock; in a
serving capture on the chip the device's run 1-3 ms off the host's, as
much as the gaps it names.  This module reads the same RAW capture again,
in a short-lived CHILD process (reading needs jax; the parent of a run
never imports it), keeps the host spans and the device's ``XLA Modules``
line only (not the ``XLA Ops`` lines, the bulk of a capture), and
memoises the result per capture.  ``align`` is plain arithmetic over

    {plane: {line: [[name, start_ns, dur_ns, {arg: value}], ...]}}

and is what tests/benchmark_tests checks on a hand-made capture.

The offset: host = device + theta.  A probe's program ran inside its span,
so each probe bounds it:

    span begin - program start  <=  theta  <=  span end - program end

theta is the intersection of the bounds over all probes (its midpoint; its
width says how well theta is known).  Where the intersection is empty a
linear drift, theta = a + b * device time, is fitted to the bounds'
midpoints and the intersection taken again about it.  Where that is empty
too, or there are fewer than ``MIN_PROBES`` probes (a program that writes
none), there is no alignment: None.

By hand, on any capture directory of a serving replica:

    python3 benchmark/lib/aligned_trace.py <capture_dir or file.xplane.pb>
"""

import bisect
import json
import os
import statistics
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if sys.path and os.path.abspath(sys.path[0] or ".") == HERE:
    sys.path.pop(0)  # run as a script: lib/trace.py is not stdlib's trace
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import program_trace as P  # noqa: E402
from benchmark.lib import trace as T  # noqa: E402

PROBE_SPAN = "tfos/clock/probe"
PROBE_PROGRAM = "jit_tfos_clock_probe"
STEP_PROGRAM = "jit_tfos_decode_step_paged"
DISPATCH_SPAN = "tfos/decode/step_dispatch"
FETCH_SPAN = "tfos/decode/logits_fetch"
MIN_PROBES = 8
# probes come in bursts (``clock_probe``'s rounds, back to back) at least
# half a second apart: a pause this long on either clock starts a burst
BURST_GAP_NS = 100_000_000


# -- reading (needs jax; runs in the child) ----------------------------------

def read_raw(path):
    """The capture at ``path`` (an ``.xplane.pb``) as plain data: the host
    lines' ``tfos/*`` and ``bench/*`` spans and the device planes'
    ``XLA Modules`` line, programs under their short names."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(T.DEVICE_PLANE_PREFIX)
        for i, line in enumerate(plane.lines):
            if device and line.name != P.MODULES_LINE:
                continue
            events = [[P.program_name(ev.name) if device else ev.name,
                       ev.start_ns, ev.duration_ns, {}]
                      for ev in line.events
                      if device or ev.name.startswith(P.SPAN_PREFIXES)]
            if events:
                out.setdefault(plane.name, {})[f"{line.name}#{i}"] = events
    return out


# -- arithmetic ---------------------------------------------------------------

def _bursts(items):
    """Sorted ``[(start, end)]`` cut where one starts ``BURST_GAP_NS``
    after the one before."""
    out = []
    for item in items:
        if out and item[0] - out[-1][-1][0] < BURST_GAP_NS:
            out[-1].append(item)
        else:
            out.append([item])
    return out


def pair_probes(spans, runs):
    """``[((span begin, end), (run start, end))]``: each probe span with
    the program run it made.  A burst of spans goes with the burst of runs
    nearest it, item by item, where both hold as many (a capture's edge
    may cut one)."""
    pairs = []
    run_bursts = _bursts(runs)
    for burst in _bursts(spans):
        near = min(run_bursts, default=None,
                   key=lambda b: abs(b[0][0] - burst[0][0]))
        if near is not None and len(near) == len(burst) \
                and abs(near[0][0] - burst[0][0]) < BURST_GAP_NS / 2:
            pairs.extend(zip(burst, near))
    return pairs


def offset(pairs):
    """``(a, b, width)`` with host = device + a + b * device, or None where
    the probes' bounds admit no such line (module docstring)."""
    lo = [hb - ds for (hb, _he), (ds, _de) in pairs]
    hi = [he - de for (_hb, he), (_ds, de) in pairs]
    slope = 0.0
    if max(lo) > min(hi):
        t = [ds for _span, (ds, _de) in pairs]
        mid = [(x + y) / 2 for x, y in zip(lo, hi)]
        t0, m0 = statistics.fmean(t), statistics.fmean(mid)
        var = sum((x - t0) ** 2 for x in t)
        if not var:
            return None
        slope = sum((x - t0) * (y - m0) for x, y in zip(t, mid)) / var
        lo = [x - slope * s for x, s in zip(lo, t)]
        hi = [y - slope * s for y, s in zip(hi, t)]
        if max(lo) > min(hi):
            return None
    return (max(lo) + min(hi)) / 2, slope, min(hi) - max(lo)


def iterations(events):
    """``[(dispatch begin, dispatch end, fetch end)]`` of one host line's
    decode iterations: each ``step_dispatch`` with the ``logits_fetch``
    that follows it before the next dispatch."""
    disp = sorted((s, s + d) for n, s, d, _a in events if n == DISPATCH_SPAN)
    fetch = sorted((s, s + d) for n, s, d, _a in events if n == FETCH_SPAN)
    out, j = [], 0
    for k, (b, e) in enumerate(disp):
        nxt = disp[k + 1][0] if k + 1 < len(disp) else float("inf")
        while j < len(fetch) and fetch[j][0] < e:
            j += 1
        if j < len(fetch) and fetch[j][0] < nxt:
            out.append((b, e, fetch[j][1]))
    return out


def idle_by_span(runs, segments):
    """``{span: seconds}``: the gaps between the merged program ``runs``
    ``[(start, end)]``, each put down to the deepest span open then in
    ``segments`` (``program_trace.self_segments`` of one host line);
    "unnamed" for the rest."""
    starts = [s for s, _e, _n in segments]
    merged = T._merge(runs)
    out = {}
    for (_s0, g0), (g1, _e1) in zip(merged, merged[1:]):
        left = g1 - g0
        for name, ov, _a, _b in P.overlaps(segments, starts, g0, g1):
            out[name] = out.get(name, 0.0) + ov / 1e9
            left -= ov
        if left > 0:
            out["unnamed"] = out.get("unnamed", 0.0) + left / 1e9
    return out


def _median_ms(values):
    return statistics.median(values) / 1e6 if values else None


def align(events):
    """The capture on the host's clock (module docstring), or None.

    ``probes``, ``theta_ms`` (at device time 0), ``drift_ppm``,
    ``width_ms``   the offset and how well the probes pin it
    ``violations_raw`` / ``violations``  programs of an iteration (its
                   step and those after it: the pick) that start before
                   its ``step_dispatch`` began or end after its
                   ``logits_fetch`` returned, on the device's own stamps /
                   on the aligned clock
    ``iterations`` iterations whose step program is in the capture;
                   over them the medians ``launch_ms`` (step start -
                   dispatch begin: the dispatch call included),
                   ``dispatch_call_ms``, ``launch_after_call_ms``,
                   ``return_ms`` (fetch end - end of the iteration's last
                   program, the pick) and ``host_between_ms`` (fetch end
                   to the next dispatch)
    ``idle_by`` / ``idle_by_raw``  the gaps between programs put down to
                   the engine thread's spans, aligned / raw, seconds
    ``probe_share`` the probes' host time over the capture's extent
    """
    devices = sorted(p for p in events if p.startswith(T.DEVICE_PLANE_PREFIX))
    if not devices:
        return None
    runs = sorted((s, s + d, n) for ln, evs in events[devices[0]].items()
                  if ln.split("#")[0] == P.MODULES_LINE
                  for n, s, d, _a in evs)
    hosts = {f"{p}|{ln}": evs for p in events if p not in devices
             for ln, evs in events[p].items()}
    spans = sorted((s, s + d) for evs in hosts.values()
                   for n, s, d, _a in evs if n == PROBE_SPAN)
    pairs = pair_probes(spans, [(s, e) for s, e, n in runs
                                if n == PROBE_PROGRAM])
    if len(pairs) < MIN_PROBES:
        return None
    got = offset(pairs)
    if got is None:
        return None
    a, slope, width = got
    aligned = [(s + a + slope * s, e + a + slope * e, n) for s, e, n in runs]
    mids = [(s + e) / 2 for s, e, _n in aligned]

    engine = max(hosts, key=lambda ln: sum(
        ev[0] == DISPATCH_SPAN for ev in hosts[ln]))
    its = iterations(hosts[engine])
    launch, after_call, call, ret, between = [], [], [], [], []
    raw_bad = bad = 0
    for k, (b, e, f) in enumerate(its):
        window = range(bisect.bisect_left(mids, b),
                       bisect.bisect_right(mids, f))
        step = next((i for i in window if aligned[i][2] == STEP_PROGRAM),
                    None)
        if step is None:
            continue
        # the iteration's programs: its step and what follows it (the
        # pick); an admission's insert, dispatched without a wait, may
        # still run when the step is dispatched and is not the iteration's
        mine = range(step, window.stop)
        launch.append(aligned[step][0] - b)
        after_call.append(aligned[step][0] - e)
        call.append(e - b)
        ret.append(f - max(aligned[i][1] for i in mine))
        if k + 1 < len(its):
            between.append(its[k + 1][0] - f)
        for i in mine:
            raw_bad += runs[i][0] < b or runs[i][1] > f
            bad += aligned[i][0] < b or aligned[i][1] > f

    segments = P.self_segments(hosts[engine])
    everything = [x for evs in hosts.values() for _n, s, d, _a in evs
                  for x in (s, s + d)] + [x for s, e, _n in runs
                                          for x in (s, e)]
    extent = max(everything) - min(everything)
    return {
        "probes": len(pairs), "theta_ms": a / 1e6,
        "drift_ppm": slope * 1e6, "width_ms": width / 1e6,
        "violations_raw": raw_bad, "violations": bad,
        "iterations": len(launch),
        "launch_ms": _median_ms(launch),
        "dispatch_call_ms": _median_ms(call),
        "launch_after_call_ms": _median_ms(after_call),
        "return_ms": _median_ms(ret),
        "host_between_ms": _median_ms(between),
        "idle_by": idle_by_span([(s, e) for s, e, _n in aligned], segments),
        "idle_by_raw": idle_by_span([(s, e) for s, e, _n in runs], segments),
        "probe_share": sum(e - s for s, e in spans) / extent,
    }


# -- the memo -----------------------------------------------------------------

_MEMO = {}


def load(facts):
    """This run's capture aligned, or None: no capture (``--trace 0``),
    one that cannot be read, or one without enough probes (a program
    that writes none).  Read once per run, in a child process."""
    cap = P.find_capture(facts)
    if cap is None:
        return None
    if cap not in _MEMO:
        _MEMO[cap] = _align_in_child(cap)
        if _MEMO[cap] is not None:
            print("[bench:aligned_trace] " + describe(_MEMO[cap]), flush=True)
    return _MEMO[cap]


def _align_in_child(capture_dir):
    path = T.find_xplane(capture_dir)
    if path is None:
        return None
    env = dict(os.environ, JAX_PLATFORMS="cpu")  # never reach for a chip
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--json", path],
            env=env, capture_output=True, text=True, timeout=300)
        return json.loads(proc.stdout.splitlines()[-1]) \
            if proc.returncode == 0 else None
    except (subprocess.SubprocessError, ValueError, IndexError):
        return None


def describe(got):
    """One earlier-line sentence: the offset, the causality check, the
    aligned idle time and the iteration's medians."""
    top = sorted(got["idle_by"].items(), key=lambda kv: -kv[1])[:6]
    med = {k: None if got[k] is None else round(got[k], 4)
           for k in ("launch_ms", "dispatch_call_ms", "launch_after_call_ms",
                     "host_between_ms", "return_ms")}
    return (f"theta {got['theta_ms']:.4f} ms (drift {got['drift_ppm']:.2f} "
            f"ppm), interval width {got['width_ms']:.4f} ms from "
            f"{got['probes']} probes (share of the capture "
            f"{got['probe_share']:.5f}); causality violations "
            f"{got['violations_raw']} before alignment, "
            f"{got['violations']} after; aligned idle by span "
            f"{[[k, round(v, 4)] for k, v in top]}; medians over "
            f"{got['iterations']} iterations {json.dumps(med)}")


def main(argv):
    if len(argv) == 2 and argv[0] == "--json":
        print(json.dumps(align(read_raw(argv[1]))))
        return 0
    if len(argv) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    path = argv[0] if argv[0].endswith(".pb") else T.find_xplane(argv[0])
    if not path:
        print(f"no *.xplane.pb under {argv[0]}", file=sys.stderr)
        return 1
    print(json.dumps(align(read_raw(path)), indent=1, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
