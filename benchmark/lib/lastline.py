"""The last line of standard output: one JSON object, the contract's keys."""

import json
import math

REQUIRED = ("correct", "attempted", "failed", "metrics", "device")
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")


def compose(facts, metrics, trace):
    """``facts`` is what a runner returns; ``metrics`` is
    ``{name: {"value", "unit"}}``."""
    dev = facts["device"]
    device = {k: dev[k] for k in DEVICE_KEYS}
    line = {"correct": bool(facts["correct"]),
            "attempted": int(facts["attempted"]),
            "failed": int(facts["failed"]),
            "metrics": metrics, "device": device}
    if trace:
        reduced = facts.get("trace")
        if reduced:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            line["breakdown"] = {"device_ops": reduced["device_ops"][:10],
                                 "idle_gaps": reduced["idle_gaps"][:10]}
    return line


def problems(line, trace=False):
    bad = [f"missing key {k}" for k in REQUIRED if k not in line]
    if bad:
        return bad
    for k in DEVICE_KEYS:
        if k not in line["device"]:
            bad.append(f"device lacks {k}")
    if trace:
        for k in ("busy_s", "window_s"):
            v = line["device"].get(k)
            if not isinstance(v, (int, float)) or not v > 0:
                bad.append(f"device {k} is {v!r}: a traced run in which "
                           "no operation ran on the device")
    if not line["metrics"]:
        bad.append("no metric")
    for name, m in line["metrics"].items():
        v = m.get("value")
        if not isinstance(v, (int, float)) or isinstance(v, bool) \
                or not math.isfinite(v):
            bad.append(f"metric {name}: value {v!r}")
        if not isinstance(m.get("unit"), str):
            bad.append(f"metric {name}: no unit")
    return bad


def dumps(line):
    return json.dumps(line, allow_nan=False)
