"""ISSUE 27's entries in the manifest and what stands behind them: the
configuration file against the catalog's published sizes, the traffic
mix's parameters, the three new per-layer metrics with their readers,
the adapter's floors, and the bytes function at the published sizes
against the table of the issue."""

import json
import os

import pytest

from benchmark.layer_metrics import (
    expert_tokens_mean, latent_step_ms, latent_step_roofline_frac)
from benchmark.lib import latent_bytes as B
from benchmark.lib import manifest as M
from benchmark.models import latent_moe_decoder as adapter

MAN = M.load()
CELL = "sarvam-105b-serve-c16-ctx2k"


def config():
    with open(os.path.join(M.ROOT, "benchmark", "configs",
                           "sarvam-105b-serve.json")) as f:
        return json.load(f)


def test_the_contract_holds_with_issue_27s_entries():
    assert M.problems(MAN) == []


def test_the_cell_and_its_configuration_are_declared_as_the_issue_names_them():
    cell = next(w for w in MAN["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "sarvam-105b-serve", "closed-c16-ctx2k", 1)
    entry = next(c for c in MAN["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size", "max_position_embeddings"]
    assert entry["reduced"] == config()["reduced"]
    assert entry["source"] == config()["source"]
    assert MAN["workloads"][-1] == cell and MAN["configs"][-1] == entry


@pytest.mark.parametrize("name", [
    "serve_tok_per_s", "batch_occupancy", "iter_ms", "ttft_p50_ms",
    "token_gap_p95_ms", "iter_host_ms", "admit_frac"])
def test_the_cell_joins_the_serving_metrics(name):
    entry = next(m for g in ("end_to_end", "per_layer") for m in MAN[g]
                 if m["name"] == name)
    assert entry["workloads"] == ["pythia-1.4b-serve-c8", CELL]


@pytest.mark.parametrize("name,unit,source,better", [
    ("latent_step_ms", "ms", "device_trace", "lower"),
    ("latent_step_roofline_frac", "ratio", "device_trace", "higher"),
    ("expert_tokens_mean", "tokens", "program_counter", "higher")])
def test_each_new_metric_is_declared(name, unit, source, better):
    entry = next(m for m in MAN["per_layer"] if m["name"] == name)
    assert (entry["unit"], entry["source"], entry["better"], entry["layer"],
            entry["moves"], entry["workloads"]) == (
        unit, source, better, "step", "serve_tok_per_s", [CELL])
    assert os.path.exists(M.reader_path("per_layer", name))
    assert [m["name"] for m in MAN["per_layer"][-3:]] == [
        "latent_step_ms", "latent_step_roofline_frac", "expert_tokens_mean"]


@pytest.mark.parametrize("key,want", [
    ("hidden_size", 4096), ("num_attention_heads", 64),
    ("kv_lora_rank", 512), ("qk_nope_head_dim", 128),
    ("qk_rope_head_dim", 64), ("v_head_dim", 128), ("q_head_dim", 192),
    ("head_dim", 576), ("moe_intermediate_size", 2048),
    ("intermediate_size", 16384), ("num_experts_per_tok", 8),
    ("router_width", 128), ("num_experts_published", 128),
    ("routed_scaling_factor", 2.5), ("first_k_dense_replace", 1),
    ("num_shared_experts", 1), ("rope_theta", 10000),
    ("num_hidden_layers", 6), ("num_experts", 32), ("vocab_size", 65536),
    ("max_position_embeddings", 8704), ("param_dtype", "bfloat16")])
def test_the_configuration_keeps_every_published_width(key, want):
    assert config()[key] == want


def test_the_configuration_matches_the_catalog_row_outside_reduced():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog on this machine")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "sarvam-105b")
    cfg = config()
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    for key in ("deployment", "assumed", "reduced_why", "rehearse"):
        assert cfg[key]
    assert cfg["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "deepseek_yarn"}


def test_the_traffic_mix_has_the_issues_parameters():
    with open(os.path.join(M.ROOT, "benchmark", "traffic",
                           "closed-c16-ctx2k.json")) as f:
        mix = json.load(f)
    assert (mix["runner"], mix["loop"], mix["clients"], mix["slots"],
            mix["block_size"], mix["prefill_tokens"],
            mix["preroll_seconds"]) == (
        "serve_model", "closed", 16, 16, 16, 16384, 8)
    assert mix["prompt_len"] == {"median": 2048, "sigma": 0.7, "min": 512,
                                 "max": 8192}
    assert mix["output_len"] == {"median": 128, "sigma": 0.7, "min": 16,
                                 "max": 512}
    # the pool: sentinel + 16 x 544 blocks
    cfg = config()
    assert -(-cfg["max_position_embeddings"] // mix["block_size"]) == 544
    assert cfg["max_position_embeddings"] >= 8192 + 512


@pytest.mark.parametrize("change,complaint", [
    ({"num_hidden_layers": 4}, "below the floor"),
    ({"num_experts": 4}, "below the floor of 8"),
    ({"vocab_size": 16384}, "less than an eighth")])
def test_the_adapter_refuses_a_cut_below_the_floors(change, complaint):
    ctx = {"config": dict(config(), **change), "rehearse": False}
    with pytest.raises(ValueError, match=complaint):
        adapter.sizes(ctx)
    assert adapter.sizes({"config": config(), "rehearse": False})


def test_bytes_at_the_published_sizes_are_the_issues_table():
    """ISSUE 27's table (it rounds its parts, so to 0.1 M): attention
    94.6 M, dense layer 295.9 M, expert layer 925.7 M, embedding + head
    536.9 M, 5.46 B parameters = 10.92 GB, pool 0.96 GB; the step's bytes
    from the counters a run would report."""
    c = config()
    assert round(B.attention_params(c) / 1e6, 1) == 94.6
    assert abs((B.attention_params(c) + 3 * 4096 * 16384) / 1e6 - 295.9) < 0.1
    expert_layer = B.attention_params(c) + 33 * B.expert_params(c) \
        + 4096 * 128 + 128
    assert abs(expert_layer / 1e6 - 925.7) < 0.1
    assert round(2 * 65536 * 4096 / 1e6, 1) == 536.9
    assert round(B.held_params(c) / 1e9, 2) == 5.46
    assert round(B.held_params(c) * 2 / 1e9, 2) == 10.92
    assert B.cache_row_bytes(c) == 6 * 576 * 2
    assert round(16 * 8704 * B.cache_row_bytes(c) / 1e9, 2) == 0.96
    # non-expert weights a step reads: 2.3 GB; 20 touched experts in each
    # of 5 layers: 5.0 GB; 16 sessions of 2.6 k live rows: 0.29 GB
    base = B.step_bytes(c, 0, 0, 16)
    assert round(base / 1e9, 1) == 2.3
    assert round((B.step_bytes(c, 100, 0, 16) - base) / 1e9, 2) == 5.03
    assert round((B.step_bytes(c, 0, 16 * 2600, 16) - base) / 1e9, 2) == 0.29
    # touched experts only: all 32 held would read 8.05 GB, 60% more
    assert B.step_bytes(c, 160, 0, 16) > 1.5 * B.step_bytes(c, 100, 0, 16) \
        - base


def test_readers_return_nothing_where_the_program_has_nothing():
    """The parent's facts (no capture, no counters): every new reader
    returns None and raises nothing."""
    facts = {"window_s": 1.0, "slots": 16, "device": {"kind": "TPU v5 lite"}}
    assert latent_step_ms.read(facts) is None
    assert latent_step_roofline_frac.read(facts) is None
    assert expert_tokens_mean.read(facts) is None
    assert expert_tokens_mean.read(
        {"engine_moe": {"tokens_per_expert_mean": 1.5}}) == 1.5
