"""Reads ``BENCHMARK.json`` and finds, BY NAME, the files that belong to
a cell: nothing here (or anywhere in the harness) knows a cell, a
configuration, a traffic mix or a metric by name.

    configuration  -> the ``file`` its ``configs`` entry names
    traffic mix    -> benchmark/traffic/<traffic>.json  (names the runner)
    runner         -> benchmark/runners/<runner>.py
    model adapter  -> benchmark/models/<adapter>.py  (named by the config)
    metric         -> benchmark/end_to_end/<name>.py or
                      benchmark/layer_metrics/<name>.py, one ``read(facts)``
"""

import importlib
import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def load(root=ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_module(path):
    """Import one file of the benchmark by path (a new file is found
    without any registry being edited).  A file of THIS checkout is
    imported under its dotted name, so that what it defines can be
    pickled by reference and found again in an executor; a file of
    another tree (the tests' temporary copies) is loaded from its path."""
    path = os.path.abspath(path)
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    rel = os.path.relpath(path, ROOT)
    if not rel.startswith(".."):
        return importlib.import_module(
            rel[:-len(".py")].replace(os.sep, "."))
    name = "benchmark_file_" + re.sub(r"\W", "_", path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell(manifest, workload, root=ROOT):
    """Everything one run needs, as plain data."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in manifest["configs"]}[w["config"]]
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "benchmark", "traffic",
                           w["traffic"] + ".json")) as f:
        mix = json.load(f)
    return {"workload": w, "config": config, "mix": mix}


def rehearsed(block, rehearse):
    """A configuration or traffic mix as a run uses it: in a rehearsal its
    ``rehearse`` block of tiny sizes is laid over it."""
    out = dict(block)
    if rehearse:
        out.update(out.get("rehearse", {}))
    return out


def metrics_of(manifest, workload, group):
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
    return [m for m in manifest[group]
            if "workloads" not in m or workload in m["workloads"]]


def reader_path(group, name, root=ROOT):
    sub = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}[group]
    return os.path.join(root, "benchmark", sub, name + ".py")


def read_metrics(manifest, workload, group, facts, root=ROOT):
    """``{name: {"value", "unit"}}``: each metric's own reader applied to
    the run's facts.  A reader that finds nothing to read returns None and
    its metric is left out of the line."""
    out = {}
    for m in metrics_of(manifest, workload, group):
        value = load_module(reader_path(group, m["name"], root)).read(facts)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def problems(manifest, root=ROOT):
    """The contract's static rules, as a list of complaints (empty: fine).
    The driver checks them too; this is for the tests and for a builder
    who adds a cell."""
    bad = []
    keys = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(manifest) != keys:
        bad.append(f"top-level keys {sorted(manifest)} != {sorted(keys)}")
        return bad

    def name_ok(s, what):
        if not isinstance(s, str) or not NAME.match(s):
            bad.append(f"{what}: bad name {s!r}")

    def text_ok(s, what):
        if not isinstance(s, str) or not 1 <= len(s) <= 200 \
                or "\n" in s or "\t" in s:
            bad.append(f"{what}: not 1..200 characters on one line")

    paths = manifest["paths"]
    under = lambda p: any(p == d or p.startswith(d + "/") for d in paths)
    for word in manifest["command"]:
        text_ok(word, "command")
        if word.startswith("/") or ".." in word.split("/"):
            bad.append(f"command word {word!r} leaves the repo")
        if os.path.exists(os.path.join(root, word)) and not under(word):
            bad.append(f"command names {word!r} outside paths")
    if not 1 <= manifest["run_seconds"] <= 51:
        bad.append("run_seconds outside 1..51")
    cfgs = {}
    for c in manifest["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            bad.append(f"config {c.get('name')}: keys {sorted(c)}")
            continue
        name_ok(c["name"], "config")
        text_ok(c["source"], f"config {c['name']} source")
        text_ok(c["why"], f"config {c['name']} why")
        for k in c["reduced"]:
            name_ok(k, f"config {c['name']} reduced")
        if not under(c["file"]):
            bad.append(f"config {c['name']}: file outside paths")
        if not os.path.exists(os.path.join(root, c["file"])):
            bad.append(f"config {c['name']}: no file {c['file']}")
        if c["name"] in cfgs:
            bad.append(f"config {c['name']} twice")
        cfgs[c["name"]] = c
    files = [c["file"] for c in cfgs.values()]
    if len(set(files)) != len(files):
        bad.append("two configurations share a file")
    cells, pairs = {}, set()
    for w in manifest["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            bad.append(f"workload {w.get('name')}: keys {sorted(w)}")
            continue
        for k in ("name", "config", "traffic"):
            name_ok(w[k], f"workload {k}")
        text_ok(w["why"], f"workload {w['name']} why")
        if w["chips"] not in (1, 4):
            bad.append(f"workload {w['name']}: chips {w['chips']}")
        if w["config"] not in cfgs:
            bad.append(f"workload {w['name']}: no config {w['config']}")
        if (w["config"], w["traffic"]) in pairs or w["name"] in cells:
            bad.append(f"workload {w['name']}: duplicate")
        pairs.add((w["config"], w["traffic"]))
        cells[w["name"]] = w
        mix_path = os.path.join(root, "benchmark", "traffic",
                                w["traffic"] + ".json")
        if not os.path.exists(mix_path):
            bad.append(f"workload {w['name']}: no traffic file")
            continue
        with open(mix_path) as f:
            runner = json.load(f).get("runner", "")
        if not os.path.exists(os.path.join(root, "benchmark", "runners",
                                           runner + ".py")):
            bad.append(f"workload {w['name']}: no runner {runner!r}")
    for c in cfgs:
        if not any(w["config"] == c for w in cells.values()):
            bad.append(f"config {c} is used by no cell")
    four = sum(1 for w in cells.values() if w["chips"] == 4)
    if four > max(1, len(cells) // 4):
        bad.append(f"{four} of {len(cells)} cells ask for 4 chips")
    e2e = {}
    for m in manifest["end_to_end"]:
        if not {"name", "unit", "better", "bound", "source"} <= set(m) \
                or set(m) - {"name", "unit", "better", "bound", "source",
                             "workloads"}:
            bad.append(f"end_to_end {m.get('name')}: keys {sorted(m)}")
            continue
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"end_to_end {m['name']}: source {m['source']}")
        if not 0 < m["bound"] <= 0.1:
            bad.append(f"end_to_end {m['name']}: bound {m['bound']}")
        e2e[m["name"]] = m
    if "setup_s" not in e2e or "workloads" in e2e.get("setup_s", {}):
        bad.append("setup_s must be an end-to-end metric of every cell")
    layer = {}
    for m in manifest["per_layer"]:
        if not {"name", "unit", "better", "source", "layer", "moves"} \
                <= set(m) or set(m) - {"name", "unit", "better", "source",
                                       "layer", "moves", "workloads"}:
            bad.append(f"per_layer {m.get('name')}: keys {sorted(m)}")
            continue
        text_ok(m["layer"], f"per_layer {m['name']} layer")
        if m["source"] not in SOURCES:
            bad.append(f"per_layer {m['name']}: source {m['source']}")
        if m["moves"] not in e2e:
            bad.append(f"per_layer {m['name']}: moves {m['moves']!r}, "
                       "which is no end-to-end metric")
            continue
        moved = e2e[m["moves"]]
        for w in m.get("workloads", cells):
            if w not in cells:
                bad.append(f"per_layer {m['name']}: no workload {w}")
            elif "workloads" in moved and w not in moved["workloads"]:
                bad.append(f"per_layer {m['name']} moves {m['moves']}, "
                           f"which cell {w} does not report")
        layer[m["name"]] = m
    for group, ms in (("end_to_end", e2e), ("per_layer", layer)):
        for name, m in ms.items():
            name_ok(name, group)
            if not UNIT.match(m["unit"]):
                bad.append(f"{group} {name}: unit {m['unit']!r}")
            if m["better"] not in ("lower", "higher"):
                bad.append(f"{group} {name}: better {m['better']!r}")
            if name in e2e and name in layer:
                bad.append(f"metric {name} is in both groups")
            for w in m.get("workloads", ()):
                if w not in cells:
                    bad.append(f"{group} {name}: no workload {w}")
            if not os.path.exists(reader_path(group, name, root)):
                bad.append(f"{group} {name}: no reader file")
    for w in cells:
        mine = [m for m in e2e.values()
                if "workloads" not in m or w in m["workloads"]]
        if len(mine) < 2:
            bad.append(f"cell {w} reports no end-to-end metric but setup_s")
        if not any("workloads" not in m or w in m["workloads"]
                   for m in layer.values()):
            bad.append(f"cell {w} reports no per-layer metric")
    return bad
