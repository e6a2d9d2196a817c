"""From a profiler trace to device busy time, top operations and gaps.

``read_xplane`` needs jax (``jax.profiler.ProfileData``); ``reduce`` is
plain arithmetic over ``{plane: {line: [(name, start_ns, dur_ns), ...]}}``
and is what tests/benchmark_tests checks on a small recorded trace.

Busy time is the UNION of the intervals in which an operation ran on the
device: overlapped asynchronous operations (copy-start/copy-done, a
collective over compute) are not counted twice.  Summing durations, as
``scripts/profile_resnet.py`` does, double-counts them.
"""

import glob
import os
import re

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
# host-side spans the benchmark's own code writes (TraceAnnotation)
BENCH_SPAN_PREFIX = "bench/"


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def read_xplane(path):
    """``{plane: {line: [(name, start_ns, dur_ns)]}}`` of the device
    planes' operation lines and of every host line that carries one of
    the benchmark's own spans."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        is_device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        for i, line in enumerate(plane.lines):
            if is_device and line.name != OPS_LINE:
                continue
            events = [(ev.name, float(ev.start_ns), float(ev.duration_ns))
                      for ev in line.events]
            if is_device:
                events = [(short_name(n), s, d) for n, s, d in events]
            else:
                events = [e for e in events
                          if e[0].startswith(BENCH_SPAN_PREFIX)]
            if events:
                out.setdefault(plane.name, {})[f"{line.name}#{i}"] = events
    return out


def short_name(name):
    """The profiler names a device event by its whole HLO instruction,
    thousands of characters for a loop.  Keep the instruction's own name
    and, for a custom call, its target (``tpu_custom_call`` is a Pallas
    kernel): until the program gives kernels and steps stable names
    (PERF.md, Open questions) this is all that tells them apart."""
    base = name.split(" = ", 1)[0].strip()[:80]
    m = re.search(r'custom_call_target="([^"]+)"', name)
    return f"{base} [{m.group(1)}]" if m else base


def self_seconds(ops):
    """``{name: seconds}`` of SELF time: an event's duration minus what
    the events nested inside it on the same line cover (a ``while`` holds
    its body's operations; counting both would double the loop)."""
    out = {}
    stack = []  # (end, name)
    for name, s, d in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack and s + d <= stack[-1][0] + 1:
            parent = stack[-1][1]
            out[parent] = out.get(parent, 0.0) - d
        out[name] = out.get(name, 0.0) + d
        stack.append((s + d, name))
    return {n: max(v, 0.0) / 1e9 for n, v in out.items()}


def _merge(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def reduce(events, top=10, is_collective=None):
    """Reduce one trace.  Returns None when no operation ran on a device.

    ``busy_s``      union of device-op intervals, averaged over devices
    ``window_s``    first device-op start to last device-op end, the
                    widest over devices
    ``device_ops``  the ``top`` operations by summed SELF time (seconds,
                    averaged over devices; see ``self_seconds``)
    ``idle_gaps``   the ``top`` longest gaps between device operations on
                    the first device, each named by the benchmark span
                    that covers most of it on the host (else by the
                    operations on either side)
    ``collective_s`` union of the intervals of operations that
                    ``is_collective(name)`` accepts, first device
    """
    devices = sorted(p for p in events if p.startswith(DEVICE_PLANE_PREFIX))
    if not devices:
        return None
    busy, windows, op_s = [], [], {}
    for p in devices:
        ops = [e for evs in events[p].values() for e in evs]
        merged = _merge((s, s + d) for _n, s, d in ops)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        windows.append((merged[-1][1] - merged[0][0]) / 1e9)
        for name, sec in self_seconds(ops).items():
            op_s[name] = op_s.get(name, 0.0) + sec / len(devices)
    first = devices[0]
    ops = sorted((e for evs in events[first].values() for e in evs),
                 key=lambda e: e[1])
    merged = _merge((s, s + d) for _n, s, d in ops)
    spans = [e for p in events if p not in devices
             for evs in events[p].values() for e in evs]
    gaps = []
    for (_s0, e0), (s1, _e1) in zip(merged, merged[1:]):
        gaps.append((s1 - e0, e0, s1))
    gaps.sort(reverse=True)
    named = {}
    for length, g0, g1 in gaps[:200]:
        best, cover = None, 0.0
        for name, s, d in spans:
            ov = min(g1, s + d) - max(g0, s)
            if ov > cover:
                best, cover = name, ov
        if best is None or cover < 0.5 * length:
            before = max((o for o in ops if o[1] + o[2] <= g0 + 1),
                         key=lambda o: o[1] + o[2], default=None)
            best = "after " + (before[0] if before else "start")
        named[best] = named.get(best, 0.0) + length / 1e9
    out = {
        "busy_s": sum(busy) / len(busy),
        "window_s": max(windows),
        "devices": len(devices),
        "device_ops": sorted(([n, s] for n, s in op_s.items()),
                             key=lambda x: -x[1])[:top],
        "idle_gaps": sorted(([n, s] for n, s in named.items()),
                            key=lambda x: -x[1])[:top],
    }
    if is_collective is not None:
        coll = _merge((s, s + d) for n, s, d in ops if is_collective(n))
        out["collective_s"] = sum(e - s for s, e in coll) / 1e9
    return out


def describe(reduced):
    """One earlier-line sentence about a reduced trace."""
    return (f"trace: busy {reduced['busy_s']:.3f}s of "
            f"{reduced['window_s']:.3f}s on {reduced['devices']} device "
            f"plane(s); top ops {reduced['device_ops'][:5]}; gaps "
            f"{reduced['idle_gaps'][:5]}")


def is_collective_op(name):
    n = name.lower()
    return any(k in n for k in ("all-reduce", "all-gather", "reduce-scatter",
                                "all-to-all", "collective-permute"))
