"""ISSUE 32's entries in the manifest and what stands behind them, by
MEMBERSHIP and never by position (the next configuration lengthens every
list): the cell and its configuration, the serving metrics' lists, the
two new per-layer metrics with their readers, the configuration file
against the catalog's published sizes, the traffic mix's parameters, the
adapter's floors and its whole-period rule, and the bytes functions at
the published sizes against the table of the issue."""

import json
import os

import pytest

from benchmark.layer_metrics import (
    hybrid_step_roofline_frac, prefill_ms_per_ktok)
from benchmark.lib import hybrid_bytes as B
from benchmark.lib import manifest as M
from benchmark.models import hybrid_linear_decoder as adapter

MAN = M.load()
CELL = "kimi-linear-48b-serve-c64-out512"
CONFIG = "kimi-linear-48b-serve"
REDUCED = ["num_hidden_layers", "linear_attn_config", "num_experts",
           "vocab_size", "max_position_embeddings"]


def config():
    with open(os.path.join(M.ROOT, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        return json.load(f)


def mix():
    with open(os.path.join(M.ROOT, "benchmark", "traffic",
                           "closed-c64-out512.json")) as f:
        return json.load(f)


def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog on this machine")
    with open(path) as f:
        return next(r for r in map(json.loads, f)
                    if r["name"] == "Kimi-Linear-48B-A3B-Instruct")


def test_the_contract_holds_with_issue_32s_entries():
    assert M.problems(MAN) == []


def test_the_cell_and_its_configuration_are_declared_as_the_issue_names_them():
    cell = next(w for w in MAN["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "closed-c64-out512", 1)
    assert "tokens a touched expert (deployment: 8)" in cell["why"]
    entry = next(c for c in MAN["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == REDUCED == config()["reduced"]
    assert entry["source"] == config()["source"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert config()["adapter"] == "hybrid_linear_decoder"
    # the earlier cells keep their chips: still one cell on four
    assert sum(w["chips"] == 4 for w in MAN["workloads"]) == 1


@pytest.mark.parametrize("name", [
    "serve_tok_per_s", "batch_occupancy", "iter_ms", "ttft_p50_ms",
    "token_gap_p95_ms", "iter_host_ms", "admit_frac", "latent_step_ms",
    "expert_tokens_mean"])
def test_the_cell_joins_the_serving_metrics(name):
    entry = next(m for g in ("end_to_end", "per_layer") for m in MAN[g]
                 if m["name"] == name)
    assert CELL in entry["workloads"]
    assert "sarvam-105b-serve-c16-ctx2k" in entry["workloads"]
    assert len(set(entry["workloads"])) == len(entry["workloads"])


def test_the_cell_stays_out_of_the_other_configurations_roofline():
    """``latent_step_roofline_frac`` counts the sarvam block's bytes; this
    cell has a bytes function and a share of its own."""
    entry = next(m for m in MAN["per_layer"]
                 if m["name"] == "latent_step_roofline_frac")
    assert CELL not in entry["workloads"]
    reported = {m["name"] for m in M.metrics_of(MAN, CELL, "per_layer")}
    assert {"boot_s", "hybrid_step_roofline_frac", "prefill_ms_per_ktok",
            "latent_step_ms", "expert_tokens_mean"} <= reported
    assert {m["name"] for m in M.metrics_of(MAN, CELL, "end_to_end")} == {
        "setup_s", "serve_tok_per_s"}


@pytest.mark.parametrize("name,unit,better", [
    ("hybrid_step_roofline_frac", "ratio", "higher"),
    ("prefill_ms_per_ktok", "ms", "lower")])
def test_each_new_metric_is_declared(name, unit, better):
    entry = next(m for m in MAN["per_layer"] if m["name"] == name)
    assert (entry["unit"], entry["source"], entry["better"], entry["layer"],
            entry["moves"]) == (unit, "device_trace", better, "step",
                                "serve_tok_per_s")
    assert CELL in entry["workloads"]
    assert os.path.exists(M.reader_path("per_layer", name))
    # after every entry that was there before this issue
    names = [m["name"] for m in MAN["per_layer"]]
    assert names.index(name) > names.index("expert_tokens_mean")


@pytest.mark.parametrize("key,want", [
    ("hidden_size", 2304), ("num_attention_heads", 32),
    ("num_key_value_heads", 32), ("head_dim", 72), ("kv_lora_rank", 512),
    ("qk_nope_head_dim", 128), ("qk_rope_head_dim", 64),
    ("v_head_dim", 128), ("q_lora_rank", None), ("mla_use_nope", True),
    ("rope_scaling", None), ("moe_intermediate_size", 1024),
    ("intermediate_size", 9216), ("num_experts_per_token", 8),
    ("router_width", 256), ("num_experts_published", 256),
    ("routed_scaling_factor", 2.446), ("first_k_dense_replace", 1),
    ("num_shared_experts", 1), ("rms_norm_eps", 1e-05),
    ("model_max_length", 1048576), ("kda_low_rank", 128),
    ("num_hidden_layers", 8), ("num_hidden_layers_published", 27),
    ("num_experts", 64), ("vocab_size", 40960),
    ("vocab_size_published", 163840), ("max_position_embeddings", 2048),
    ("param_dtype", "bfloat16"), ("compute_dtype", "bfloat16"),
    ("cache_dtype", "bfloat16"), ("state_dtype", "float32")])
def test_the_configuration_keeps_every_published_width(key, want):
    assert config()[key] == want


def test_the_configuration_matches_the_catalog_row_outside_reduced():
    row, cfg = catalog_row(), config()
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    for key in ("deployment", "assumed", "reduced_why", "rehearse"):
        assert cfg[key]
    for word in ("Four chips share each layer", "64 of the 256",
                 "40,960 of 163,840", "8 of the 27 layers = two whole "
                 "periods"):
        assert word in cfg["deployment"], word


def test_linear_attn_config_is_cut_in_its_layer_lists_only():
    """The group is in ``reduced`` for its two lists; its widths are the
    published ones, and the lists are the published lists' first entries."""
    published = catalog_row()["config"]["linear_attn_config"]
    mine = config()["linear_attn_config"]
    assert set(mine) == set(published)
    for key in ("head_dim", "num_heads", "short_conv_kernel_size"):
        assert mine[key] == published[key], key
    assert (mine["head_dim"], mine["num_heads"],
            mine["short_conv_kernel_size"]) == (128, 32, 4)
    assert mine["kda_layers"] == [1, 2, 3, 5, 6, 7] \
        == published["kda_layers"][:6]
    assert mine["full_attn_layers"] == [4, 8] \
        == published["full_attn_layers"][:2]
    assert "head_dim 128, num_heads 32 and short_conv_kernel_size 4 are " \
        "as published" in config()["reduced_why"]


def test_the_traffic_mix_has_the_issues_parameters():
    m = mix()
    assert (m["runner"], m["loop"], m["clients"], m["slots"],
            m["block_size"], m["prefill_tokens"], m["max_tokens"],
            m["block_requests"], m["preroll_seconds"], m["trace_seconds"],
            m["verify_requests"], m["drain_timeout_s"]) == (
        "serve_model", "closed", 64, 64, 16, 2048, 1024, 64, 8, 4, 8, 120)
    assert m["prompt_len"] == {"median": 512, "sigma": 0.5, "min": 256,
                               "max": 1024}
    assert m["output_len"] == {"median": 512, "sigma": 0.6, "min": 128,
                               "max": 1024}
    assert m["verify"]["lower_precision"] == "float8_e4m3fn"
    # the pool: sentinel + 64 x 128 blocks, and room for the longest session
    cfg = config()
    assert -(-cfg["max_position_embeddings"] // m["block_size"]) == 128
    assert cfg["max_position_embeddings"] >= 1024 + 1024
    # nine prefill programs under the bound: 256 x {1..8}, 512 x {1..4},
    # 1,024 x {1, 2} (the issue's 4,096 gave twelve and a cold set-up
    # over its own 300 s: the mix's ``prefill_why``)
    programs = [(t, rows) for t in (256, 512, 1024)
                for rows in (1, 2, 4, 8, 16, 32, 64)
                if rows * t <= m["prefill_tokens"]]
    assert len(programs) == 9 and "318.95" in m["prefill_why"]
    # the limits lie between the two readings the mix records
    v = m["verify"]
    assert 0.035 < v["rel_err_median_max"] < 0.448
    assert 0.397 < v["rel_err_p99_max"] < 0.571
    assert 0.303 < v["exact_share_min"] < 0.803
    # the list cannot run out: three times two requests a second for a
    # minute, behind the first 64
    assert m["blocks"] * m["block_requests"] >= 64 + 3 * 2 * 60


def lists(depth):
    return {"full_attn_layers": list(range(4, depth + 1, 4)),
            "head_dim": 128, "num_heads": 32, "short_conv_kernel_size": 4,
            "kda_layers": [i for i in range(1, depth + 1) if i % 4]}


@pytest.mark.parametrize("change,complaint", [
    ({"num_hidden_layers": 4, "linear_attn_config": lists(4)},
     "below the floor"),
    ({"num_hidden_layers": 7, "linear_attn_config": lists(7)},
     "not whole periods of 4"),
    ({"num_hidden_layers": 10, "linear_attn_config": lists(10)},
     "not whole periods of 4"),
    ({"num_hidden_layers": 8, "linear_attn_config": dict(
        lists(8), kda_layers=[1, 2, 3, 4, 5, 6], full_attn_layers=[7, 8])},
     "not a pattern of one latent layer"),
    ({"num_experts": 4}, "below the floor of 8"),
    ({"vocab_size": 16384}, "less than an eighth")])
def test_the_adapter_refuses_a_cut_below_the_floors_or_off_a_period(
        change, complaint):
    ctx = {"config": dict(config(), **change), "rehearse": False}
    with pytest.raises(ValueError, match=complaint):
        adapter.sizes(ctx)


@pytest.mark.parametrize("depth", [8, 12])
def test_the_adapter_takes_whole_periods(depth):
    cfg = dict(config(), num_hidden_layers=depth,
               linear_attn_config=lists(depth))
    assert adapter.sizes({"config": cfg, "rehearse": False})
    assert adapter.period(cfg) == 4


@pytest.mark.parametrize("what,got,want,places", [
    ("KDA attention, M", lambda c: B.kda_params(c) / 1e6, 39.51, 2),
    ("latent attention, M", lambda c: B.latent_params(c) / 1e6, 29.11, 2),
    ("one expert, M", lambda c: B.expert_params(c) / 1e6, 7.078, 3),
    ("layer 1, M", lambda c: B.layer_params(c, 1) / 1e6, 103.2, 1),
    ("a KDA expert layer, M", lambda c: B.layer_params(c, 2) / 1e6, 500.2, 1),
    ("a latent expert layer, M", lambda c: B.layer_params(c, 4) / 1e6,
     489.8, 1),
    ("embedding + head, M", lambda c: B.vocabulary_params(c) / 1e6, 188.7, 1),
    ("all held, B", lambda c: B.held_params(c) / 1e9, 3.772, 3),
    ("all held in bf16, GB", lambda c: B.held_params(c) * 2 / 1e9, 7.54, 2),
    ("a session's state, MB", lambda c: B.state_row_bytes(c) / 1e6, 13.03, 2),
    ("64 sessions' state, GB", lambda c: 64 * B.state_row_bytes(c) / 1e9,
     0.83, 2),
    ("latent rows a token, B", B.cache_row_bytes, 2304, 0),
    ("the latent pool, GB",
     lambda c: 64 * 2048 * B.cache_row_bytes(c) / 1e9, 0.30, 2),
    ("non-expert weights a step, GB",
     lambda c: B.step_bytes(c, 0, 0, 0, 0) / 1e9, 1.01, 2),
    ("55 of 64 experts touched in 7 layers, GB",
     lambda c: (B.step_bytes(c, 55 * 7, 0, 0, 0)
                - B.step_bytes(c, 0, 0, 0, 0)) / 1e9, 5.45, 2),
    ("state of 64 sessions read and written, GB",
     lambda c: (B.step_bytes(c, 0, 0, 64, 0)
                - B.step_bytes(c, 0, 0, 0, 0)) / 1e9, 1.67, 2),
    ("1,024 live rows of 64 sessions, GB",
     lambda c: (B.step_bytes(c, 0, 64 * 1024, 0, 0)
                - B.step_bytes(c, 0, 0, 0, 0)) / 1e9, 0.15, 2),
    ("the whole step, GB",
     lambda c: B.step_bytes(c, 55 * 7, 64 * 1024, 64, 64) / 1e9, 8.3, 1),
    ("its floor at 819 GB/s, ms",
     lambda c: B.step_bytes(c, 55 * 7, 64 * 1024, 64, 64) / 819e9 * 1e3,
     10.1, 1)])
def test_bytes_at_the_published_sizes_are_the_issues_table(what, got, want,
                                                           places):
    """ISSUE 32's table and its "what does the work here", to 0.1 M /
    0.01 GB (the issue rounds its parts; its 10.2 ms floor is 10.1 from
    the unrounded bytes)."""
    assert round(got(config()), places) == want, what


def test_the_share_of_each_part_of_a_step():
    c = config()
    whole = B.step_bytes(c, 55 * 7, 64 * 1024, 64, 64)
    state = 2 * 64 * B.state_row_bytes(c)
    assert round(state / whole, 2) == 0.20
    assert round(6 * B.kda_params(c) * 2 / 1e9, 2) == 0.47
    # state is 0.83 of the cache's 1.13 GB: what a session costs, not a token
    pool = 64 * 2048 * B.cache_row_bytes(c)
    assert round(pool + 64 * B.state_row_bytes(c), -7) == 1.14e9
    # touched experts only: all 64 held would read 0.9 GB more
    assert B.step_bytes(c, 64 * 7, 0, 0, 0) - B.step_bytes(c, 55 * 7, 0, 0, 0) \
        == 9 * 7 * B.expert_params(c) * 2


def test_readers_return_nothing_where_the_program_has_nothing():
    """The parent's facts (no capture, no counters; or counters without
    the state's): every new reader returns None and raises nothing."""
    facts = {"window_s": 1.0, "slots": 64, "device": {"kind": "TPU v5 lite"}}
    assert hybrid_step_roofline_frac.read(facts) is None
    assert prefill_ms_per_ktok.read(facts) is None
    facts.update(engine_moe={"experts_touched": 55.0},
                 engine_cache={"live_tokens": 65536.0, "row_bytes": 2304},
                 model_sizes=config(), window_iterations=600,
                 window_decode_tokens=38000)
    assert hybrid_step_roofline_frac.read(facts) is None
    assert prefill_ms_per_ktok.read(facts) is None
