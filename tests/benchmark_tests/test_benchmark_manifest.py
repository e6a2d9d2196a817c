"""BENCHMARK.json against the contract's static rules, and the last line."""

import copy
import json
import os

import pytest

from benchmark.lib import lastline, manifest as M

MAN = M.load()


def test_manifest_meets_the_contract():
    assert M.problems(MAN) == []


def test_every_file_a_cell_names_exists():
    for w in MAN["workloads"]:
        cell = M.cell(MAN, w["name"])
        assert os.path.exists(os.path.join(
            M.ROOT, "benchmark", "runners", cell["mix"]["runner"] + ".py"))
        assert os.path.exists(os.path.join(
            M.ROOT, "benchmark", "models", cell["config"]["adapter"] + ".py"))
    for group in ("end_to_end", "per_layer"):
        for m in MAN[group]:
            assert os.path.exists(M.reader_path(group, m["name"]))


def test_reduced_names_no_width():
    width = ("hidden_size", "intermediate_size", "latent", "state_size", "head_dim",
             "_dim", "_rank", "expansion", "experts_per")
    for c in MAN["configs"]:
        for k in c["reduced"]:
            assert not any(w in k for w in width), (c["name"], k)
        with open(os.path.join(M.ROOT, c["file"])) as f:
            assert json.load(f)["reduced"] == c["reduced"]


def test_the_two_decoder_files_share_the_published_widths():
    files = {c["name"]: json.load(open(os.path.join(M.ROOT, c["file"])))
             for c in MAN["configs"] if c["name"].startswith("pythia")}
    for key, want in (("hidden_size", 2048), ("num_attention_heads", 16),
                      ("intermediate_size", 8192), ("vocab_size", 50304),
                      ("max_position_embeddings", 2048)):
        for name, cfg in files.items():
            assert cfg[key] == want, (name, key)


def _broken(change):
    man = copy.deepcopy(MAN)
    change(man)
    return M.problems(man)


@pytest.mark.parametrize("change,complaint", [
    (lambda m: m["workloads"][0].update(name="has space"), "bad name"),
    (lambda m: m["end_to_end"][1].update(unit="tokens per second"), "unit"),
    (lambda m: m["per_layer"][0].update(moves="nothing"), "moves"),
    (lambda m: m["end_to_end"][1].update(bound=0.5), "bound"),
    (lambda m: m["workloads"][0].update(traffic="no-such-mix"),
     "no traffic file"),
    (lambda m: m["per_layer"].append(dict(m["per_layer"][1],
                                          name="no_reader_here")),
     "no reader file"),
    (lambda m: m["per_layer"][0].update(why="a why on a metric"), "keys"),
    (lambda m: m["workloads"][0].update(chips=2), "chips"),
    (lambda m: [w.update(chips=4) for w in m["workloads"]], "4 chips"),
    (lambda m: m["end_to_end"][0].update(workloads=["resnet50-fed"]),
     "setup_s"),
    (lambda m: m["command"].append("../elsewhere"), "leaves the repo"),
    (lambda m: m["command"].append("bench.py"), "outside paths"),
])
def test_problems_sees_a_broken_manifest(change, complaint):
    assert any(complaint in p for p in _broken(change)), _broken(change)


def test_layer_metric_must_move_a_metric_its_cells_report():
    def change(m):
        # a serving-only end-to-end metric moved by a metric of every cell
        serve_only = next(e for e in m["end_to_end"] if "workloads" in e)
        m["per_layer"][0].update(moves=serve_only["name"])
        m["per_layer"][0].pop("workloads", None)
    if not any("workloads" in e for e in MAN["end_to_end"]):
        pytest.skip("no end-to-end metric is limited to some cells")
    assert any("does not report" in p for p in _broken(change))


FACTS = {"correct": True, "attempted": 10, "failed": 0,
         "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                    "memory_peak_bytes": 123, "extra": "dropped"},
         "trace": {"busy_s": 2.5, "window_s": 3.0,
                   "device_ops": [[f"op{i}", 1.0] for i in range(12)],
                   "idle_gaps": [["bench/wait_batch", 0.2]]}}
METRICS = {"setup_s": {"value": 12.5, "unit": "s"}}


def test_last_line_has_exactly_the_contracts_keys():
    line = lastline.compose(FACTS, METRICS, trace=False)
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert lastline.problems(line) == []
    assert json.loads(lastline.dumps(line)) == line


def test_traced_last_line_carries_busy_window_and_breakdown():
    line = lastline.compose(FACTS, METRICS, trace=True)
    assert line["device"]["busy_s"] == 2.5
    assert line["device"]["window_s"] == 3.0
    assert len(line["breakdown"]["device_ops"]) == 10
    assert lastline.problems(line, trace=True) == []


def test_traced_run_with_an_idle_device_is_refused():
    line = lastline.compose(dict(FACTS, trace=None), METRICS, trace=True)
    assert any("busy_s" in p for p in lastline.problems(line, trace=True))


@pytest.mark.parametrize("value", [float("nan"), None, True, "3"])
def test_a_metric_value_must_be_a_finite_number(value):
    line = lastline.compose(
        FACTS, {"x": {"value": value, "unit": "s"}}, trace=False)
    assert lastline.problems(line)
