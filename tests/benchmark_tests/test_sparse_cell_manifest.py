"""The sparse cell's entries in the manifest and what stands behind them,
by MEMBERSHIP and never by position: the cell and its configuration, the
serving metrics' lists, the two per-layer metrics with their readers, the
configuration file's published sizes, the traffic mix's parameters, the
adapter's floors and its whole-period rule, and the bytes functions at the
published sizes against the parameter table of PERF.md section 4."""

import json
import os

import pytest

from benchmark.layer_metrics import dsa_selected_frac, sparse_step_roofline_frac
from benchmark.lib import manifest as M
from benchmark.lib import sparse_hybrid_bytes as B
from benchmark.models import sparse_hybrid_decoder as adapter

MAN = M.load()
CELL = "glm-5.3-flash-serve-c16-ctx8k"
CONFIG = "glm-5.3-flash-serve"
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "layer_types",
           "mlp_layer_types", "indexer_types", "linear_attn_config",
           "n_routed_experts", "vocab_size", "num_nextn_predict_layers",
           "max_position_embeddings"]


def config():
    with open(os.path.join(M.ROOT, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        return json.load(f)


def mix():
    with open(os.path.join(M.ROOT, "benchmark", "traffic",
                           "closed-c16-ctx8k.json")) as f:
        return json.load(f)


def test_the_contract_holds_with_the_sparse_cells_entries():
    assert M.problems(MAN) == []


def test_the_cell_and_its_configuration_are_declared():
    cell = next(w for w in MAN["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "closed-c16-ctx8k", 1)
    for words in ("DSA selects in every step", "~1/4 of 8k",
                  "deployment ~8", "no chunked prefill"):
        assert words in cell["why"], words
    entry = next(c for c in MAN["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == REDUCED == config()["reduced"]
    assert entry["source"] == config()["source"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert config()["adapter"] == "sparse_hybrid_decoder"
    assert sum(w["chips"] == 4 for w in MAN["workloads"]) == 1
    # the new entries come after every entry that was there before
    assert [w["name"] for w in MAN["workloads"]][-1] == CELL
    assert [c["name"] for c in MAN["configs"]][-1] == CONFIG


@pytest.mark.parametrize("name", [
    "serve_tok_per_s", "batch_occupancy", "iter_ms", "ttft_p50_ms",
    "token_gap_p95_ms", "iter_host_ms", "admit_frac", "latent_step_ms",
    "expert_tokens_mean", "prefill_ms_per_ktok", "step_launch_ms",
    "step_return_ms"])
def test_the_cell_joins_the_serving_metrics(name):
    entry = next(m for g in ("end_to_end", "per_layer") for m in MAN[g]
                 if m["name"] == name)
    assert entry["workloads"][-1] == CELL
    assert len(set(entry["workloads"])) == len(entry["workloads"])


@pytest.mark.parametrize("name", ["latent_step_roofline_frac",
                                  "hybrid_step_roofline_frac"])
def test_the_cell_stays_out_of_the_other_blocks_rooflines(name):
    entry = next(m for m in MAN["per_layer"] if m["name"] == name)
    assert CELL not in entry["workloads"]


@pytest.mark.parametrize("name,source,better", [
    ("sparse_step_roofline_frac", "device_trace", "higher"),
    ("dsa_selected_frac", "program_counter", "lower")])
def test_each_new_metric_is_declared(name, source, better):
    entry = next(m for m in MAN["per_layer"] if m["name"] == name)
    assert (entry["unit"], entry["source"], entry["better"], entry["layer"],
            entry["moves"], entry["workloads"]) == (
        "ratio", source, better, "step", "serve_tok_per_s", [CELL])
    assert os.path.exists(M.reader_path("per_layer", name))
    names = [m["name"] for m in MAN["per_layer"]]
    assert names.index(name) > names.index("step_return_ms")


@pytest.mark.parametrize("key,want", [
    ("hidden_size", 4096), ("num_attention_heads", 64),
    ("num_key_value_heads", 64), ("q_lora_rank", 1536),
    ("kv_lora_rank", 512), ("qk_nope_head_dim", 256),
    ("qk_rope_head_dim", 0), ("v_head_dim", 256), ("qk_head_dim", 256),
    ("index_n_heads", 32), ("index_head_dim", 128), ("index_topk", 2048),
    ("index_kpool", 4), ("hc_mult", 4), ("hc_sinkhorn_iters", 20),
    ("hc_eps", 1e-06), ("intermediate_size", 12288),
    ("moe_intermediate_size", 2048), ("num_experts_per_tok", 8),
    ("n_shared_experts", 1), ("routed_scaling_factor", 2.5),
    ("swiglu_limit", 10), ("rms_norm_eps", 1e-05), ("router_width", 288),
    ("n_routed_experts_published", 288), ("n_routed_experts", 36),
    ("num_hidden_layers", 5), ("num_hidden_layers_published", 45),
    ("first_k_dense_replace", 1), ("vocab_size", 19360),
    ("vocab_size_published", 154880), ("num_nextn_predict_layers", 0),
    ("max_position_embeddings", 17408), ("kda_low_rank", 128),
    ("index_rope_dim", 64), ("param_dtype", "bfloat16"),
    ("compute_dtype", "bfloat16"), ("cache_dtype", "bfloat16"),
    ("state_dtype", "float32")])
def test_the_configuration_keeps_every_published_width(key, want):
    assert config()[key] == want


def test_the_configuration_states_its_cut():
    cfg = config()
    for key in ("deployment", "assumed", "reduced_why", "rehearse"):
        assert cfg[key]
    for word in ("Eight chips share each layer", "36 of the 288",
                 "19,360 of 154,880", "one whole period DSA, KDA, KDA, KDA",
                 "MTP module"):
        assert word in cfg["deployment"], word


def test_the_layer_lists_are_the_published_pattern_cut():
    """Published: 45 layers, 3 dense, every 4th from layer 3 on DSA
    (0-based); the cut keeps layer 0 and one period after the dense
    layers, layers 3-6."""
    kinds = ["deepseek_sparse_attention" if i % 4 == 3 else
             "linear_attention" for i in range(45)]
    cfg = config()
    lin = cfg["linear_attn_config"]
    assert (lin["head_dim"], lin["num_heads"], lin["short_conv_kernel_size"],
            lin["gate_lower_bound"]) == (128, 64, 4, -5)
    assert cfg["layer_types"] == kinds[:1] + kinds[3:7]
    assert cfg["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert cfg["indexer_types"] == ["full"] * 5
    assert (lin["kda_layers"], lin["full_attn_layers"]) == ([0, 2, 3, 4],
                                                              [1])


def test_the_traffic_mix_has_the_cells_parameters():
    m = mix()
    assert (m["runner"], m["loop"], m["clients"], m["slots"],
            m["block_size"], m["max_tokens"], m["block_requests"],
            m["preroll_seconds"], m["trace_seconds"]) == (
        "serve_model", "closed", 16, 16, 16, 1024, 16, 8, 4)
    assert m["prompt_len"] == {"median": 8192, "sigma": 0.4, "min": 4096,
                               "max": 16384}
    assert m["output_len"] == {"median": 256, "sigma": 0.6, "min": 64,
                               "max": 1024}
    assert m["verify"]["lower_precision"] == "float8_e4m3fn"
    # one row a prefill and three programs: 4,096 / 8,192 / 16,384 x 1
    programs = [(t, rows) for t in (4096, 8192, 16384)
                for rows in (1, 2, 4) if rows == 1
                or rows * t <= m["prefill_tokens"]]
    assert programs == [(4096, 1), (8192, 1), (16384, 1)]
    # the pools hold the longest session
    assert config()["max_position_embeddings"] >= 16384 + 1024
    # every query past 2,048 positions selects: the shortest prompt too
    assert m["prompt_len"]["min"] > config()["index_topk"]
    # the median's limit lies between the program's readings and the
    # float8 control's; the others bound the program with room
    v = m["verify"]
    assert 0.0165 < v["rel_err_median_max"] < 0.083
    assert 0.299 < v["rel_err_p99_max"]
    assert v["exact_share_min"] < 0.930
    assert "0.083-0.153" in v["readings"]


def test_the_adapter_refuses_a_cut_below_the_floors_or_off_a_period():
    cfg = config()
    lin = cfg["linear_attn_config"]
    for change, complaint in [
            ({"n_routed_experts": 4}, "below the floor of 8"),
            ({"vocab_size": 16384}, "less than an eighth"),
            ({"layer_types": cfg["layer_types"][::-1]}, "not 1 leading"),
            ({"num_hidden_layers": 3, "layer_types": cfg["layer_types"][:3],
              "linear_attn_config": dict(lin, kda_layers=[0, 2])},
             "below the floor")]:
        with pytest.raises(ValueError, match=complaint):
            adapter.sizes({"config": dict(cfg, **change), "rehearse": False})
    assert adapter.period(cfg) == 4


@pytest.mark.parametrize("what,got,want,places", [
    ("KDA mixer, M", lambda c: B.kda_params(c) / 1e6, 137.7, 1),
    ("DSA mixer with its indexer, M", lambda c: B.dsa_params(c) / 1e6,
     124.4, 1),
    ("one expert, M", lambda c: B.expert_params(c) / 1e6, 25.2, 1),
    ("router, M", lambda c: B.router_params(c) / 1e6, 1.2, 1),
    ("mHC, all sublayers, M",
     lambda c: 2 * c["num_hidden_layers"] * B.hc_params(c) / 1e6, 3.9, 1),
    ("vocabulary slice, M", lambda c: B.vocabulary_params(c) / 1e6, 158.6,
     1),
    ("all held, B", lambda c: B.held_params(c) / 1e9, 4.72, 2),
    ("all held in bf16, GB", lambda c: B.held_params(c) * 2 / 1e9, 9.44, 2),
    ("a session's state, MB", lambda c: B.state_row_bytes(c) / 1e6, 17.4,
     1),
    ("16 sessions' state, GB", lambda c: 16 * B.state_row_bytes(c) / 1e9,
     0.28, 2)])
def test_bytes_at_the_published_sizes_are_the_table(what, got, want,
                                                           places):
    assert round(got(config()), places) == want, what


def test_the_step_reads_the_selected_rows_not_the_context():
    c = config()
    base = B.step_bytes(c, 0, 0, 16, 0, 0)
    # 16 slots at 8,192 positions: 2,048 rows read of each, a quarter
    sel = B.step_bytes(c, 0, 0, 16, 0, 16 * 2048) - base
    assert sel == 16 * 2048 * 512 * 2
    keys = B.step_bytes(c, 0, 0, 16, 16 * 2047, 0) - base
    assert keys == 16 * 2047 * 4 * 128 * 2
    assert round(B.non_expert_params(c) * 2 / 1e9, 2) == 2.03


def test_readers_return_nothing_where_the_program_has_nothing():
    facts = {"window_s": 1.0, "slots": 16, "device": {"kind": "TPU v5 lite"}}
    assert sparse_step_roofline_frac.read(facts) is None
    assert dsa_selected_frac.read(facts) is None
    facts.update(engine_moe={"experts_touched": 14.0}, model_sizes=config(),
                 window_iterations=600, window_decode_tokens=9000,
                 trace={"spans": {"tfos/decode/iterate": {
                     "count": 10, "args": {"tokens": 160}}}})
    assert sparse_step_roofline_frac.read(facts) is None
    assert dsa_selected_frac.read(facts) is None
