"""The yardstick's arithmetic: percentiles and spreads, the traffic
generator, FLOPs and peaks against the program's, the trace reduction."""

import json
import os
import statistics
import types

import pytest

from benchmark.lib import flops as F
from benchmark.lib import loadgen, peaks, stats
from benchmark.lib import trace as T

HERE = os.path.dirname(os.path.abspath(__file__))
MIX = json.load(open(os.path.join(HERE, "..", "..", "benchmark", "traffic",
                                  "closed-c8.json")))


# -- stats --------------------------------------------------------------------

@pytest.mark.parametrize("q,want", [(0.5, 50), (0.95, 95), (0.99, 99),
                                    (1.0, 100), (0.0, 1)])
def test_percentile_is_nearest_rank(q, want):
    assert stats.percentile(list(range(100, 0, -1)), q) == want


def test_percentile_of_nothing_is_nothing():
    assert stats.percentile([], 0.5) is None


def test_samples_beyond_a_percentile():
    assert stats.samples_beyond(64, 0.95) == 3      # a p95 of 64 is a maximum
    assert stats.samples_beyond(4000, 0.95) == 200


def test_spread_is_the_contracts():
    vals = [10.0, 10.2, 9.9, 10.1, 10.4, 9.8]
    q1, _, q3 = statistics.quantiles(vals, n=4)
    assert stats.spread(vals) == (q3 - q1) / statistics.median(vals)


# -- traffic ------------------------------------------------------------------

def test_requests_are_deterministic_in_the_seed():
    a = loadgen.requests_from_mix(MIX, 2**31 + 11, 50304)
    b = loadgen.requests_from_mix(MIX, 2**31 + 11, 50304)
    c = loadgen.requests_from_mix(MIX, 12, 50304)
    assert a == b
    assert a != c
    assert len(a) == MIX["block_requests"] * MIX["blocks"]


def test_every_seed_offers_the_same_work_block_by_block():
    def blocks(seed):
        reqs = loadgen.requests_from_mix(MIX, seed, 50304)
        n = MIX["block_requests"]
        return [sorted((len(r["prompt"]), r["max_tokens"])
                       for r in reqs[i:i + n])
                for i in range(0, len(reqs), n)]
    a, b = blocks(1), blocks(3000000019)
    assert a == b and all(x == a[0] for x in a)


def test_lengths_follow_the_mix():
    reqs = loadgen.requests_from_mix(MIX, 5, 50304)
    p, o = MIX["prompt_len"], MIX["output_len"]
    plens = [len(r["prompt"]) for r in reqs]
    olens = [r["max_tokens"] for r in reqs]
    assert min(plens) >= p["min"] and max(plens) <= p["max"]
    assert min(olens) >= o["min"] and max(olens) <= o["max"]
    assert abs(statistics.median(plens) - p["median"]) <= 0.1 * p["median"]
    assert abs(statistics.median(olens) - o["median"]) <= 0.1 * o["median"]
    assert all(0 < t < 50304 for r in reqs for t in r["prompt"])


def test_poisson_schedule_is_seeded_and_matches_the_programs():
    import random

    from tensorflowonspark_tpu.serving.decode import loadgen as theirs  # noqa: F401

    a = loadgen.poisson_arrivals(50, 4.0, 9)
    assert a == loadgen.poisson_arrivals(50, 4.0, 9)
    # the program's run_open_loop draws its schedule exactly so
    rng, t, want = random.Random(9), 0.0, []
    for _ in range(50):
        want.append(t)
        t += rng.expovariate(4.0)
    assert a == want


def test_closed_loop_sends_next_only_after_the_reply():
    import threading
    import time

    live, peak, lock = [0], [0], threading.Lock()

    def send(req):
        with lock:
            live[0] += 1
            peak[0] = max(peak[0], live[0])
        time.sleep(0.005)
        with lock:
            live[0] -= 1
        return {"tokens": [1] * req["max_tokens"]}

    reqs = [{"id": i, "prompt": [1], "max_tokens": 2} for i in range(40)]
    loop = loadgen.ClosedLoop(reqs, 3, send).start()
    time.sleep(0.05)
    assert loop.stop(timeout=5)
    assert peak[0] <= 3
    ids = [r["id"] for r in loop.records]
    assert len(ids) == len(set(ids)) and ids
    assert all(r["sent"] >= r["due"] and r["done"] >= r["sent"]
               for r in loop.records)


# -- FLOPs and peaks: the benchmark's own copies -----------------------------

def test_resnet_macs_agree_with_the_programs_table():
    from tensorflowonspark_tpu.models import resnet

    ours = 2 * F.resnet_forward_macs(50, 224, 1000)
    assert abs(ours - resnet.flops_per_image(50, 224)) \
        <= 0.01 * resnet.flops_per_image(50, 224)
    assert F.resnet_train_flops_per_image() == 3 * ours


@pytest.mark.parametrize("causal", [False, True])
def test_copy_of_the_programs_decoder_formula_is_exact(causal):
    from tensorflowonspark_tpu.utils import metrics

    cfg = types.SimpleNamespace(vocab_size=50304, dim=2048, n_layers=10,
                                max_seq=2048, mlp_ratio=4)
    assert F.program_decoder_flops_per_token(
        2048, 10, 50304, 2048, causal=causal) \
        == metrics.transformer_flops_per_token(cfg, causal=causal)


def test_own_decoder_count_differs_on_purpose():
    """No FLOPs for the embedding lookup, causal half of attention: at
    Pythia widths and 6 layers 2.58 against the program's 3.35 GFLOP."""
    ours = F.decoder_train_flops_per_token(2048, 6, 50304, 2048)
    theirs = F.program_decoder_flops_per_token(2048, 6, 50304, 2048)
    assert round(ours / 1e9, 2) == 2.58
    assert round(theirs / 1e9, 2) == 3.35
    lookup = 6 * 50304 * 2048
    dense_half = 6 * 6 * 2048 * 2048
    assert theirs - ours == lookup + dense_half


def test_param_count_is_pythias():
    assert round(F.decoder_param_count(2048, 24, 50304) / 1e9, 2) == 1.41


def test_peaks_agree_with_the_programs_and_unknown_is_an_error():
    from tensorflowonspark_tpu.utils import metrics

    dev = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert peaks.peak("TPU v5 lite") == metrics.peak_flops(dev) == 197e12
    assert peaks.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(ValueError, match="no published peak"):
        peaks.peak("cpu")
    with pytest.raises(ValueError):
        peaks.peak("TPU v9 imaginary")


# -- trace reduction ----------------------------------------------------------

def test_overlapping_device_ops_are_not_double_counted():
    ev = {"/device:TPU:0": {"XLA Ops#0": [
        ("fusion.1", 0.0, 4e9), ("copy-start.2", 1e9, 2e9),
        ("all-reduce.3", 3e9, 2e9), ("fusion.4", 7e9, 1e9)]}}
    r = T.reduce(ev, is_collective=T.is_collective_op)
    assert r["busy_s"] == 6.0           # [0,5] and [7,8]; durations sum to 9
    assert r["window_s"] == 8.0
    assert r["collective_s"] == 2.0
    # self time: copy-start.2 ran wholly inside fusion.1 and is taken out
    # of it; all-reduce.3 only overlaps it and is not
    assert dict(map(tuple, r["device_ops"])) == {
        "fusion.1": 2.0, "copy-start.2": 2.0, "all-reduce.3": 2.0,
        "fusion.4": 1.0}
    assert r["idle_gaps"] == [["after all-reduce.3", 2.0]]


def test_a_loop_is_not_counted_with_its_body():
    ops = [("while.1", 0.0, 10e9), ("fusion.a", 0.0, 4e9),
           ("fusion.b", 4e9, 5e9), ("fusion.a", 20e9, 1e9)]
    assert T.self_seconds(ops) == {"while.1": 1.0, "fusion.a": 5.0,
                                   "fusion.b": 5.0}


def test_device_event_names_are_shortened():
    hlo = ('%closed_call.9 = (bf16[128,2048,128]{2,1,0}) custom-call(bf16[1] '
           '%x), custom_call_target="tpu_custom_call", operand_layout={}')
    assert T.short_name(hlo) == "%closed_call.9 [tpu_custom_call]"
    assert T.short_name("%fusion.3 = f32[8] fusion(f32[8] %p)") == "%fusion.3"
    assert len(T.short_name("x" * 5000)) <= 80


def test_a_gap_is_named_by_the_benchmark_span_that_covers_it():
    ev = {"/device:TPU:0": {"XLA Ops#0": [("a", 0.0, 1e9), ("b", 3e9, 1e9)]},
          "/host:CPU": {"python3#1": [("bench/wait_batch", 1.1e9, 1.8e9)]}}
    assert T.reduce(ev)["idle_gaps"] == [["bench/wait_batch", 2.0]]


def test_busy_is_averaged_over_devices():
    ev = {"/device:TPU:0": {"XLA Ops#0": [("a", 0.0, 2e9)]},
          "/device:TPU:1": {"XLA Ops#0": [("a", 0.0, 1e9), ("b", 3e9, 1e9)]}}
    r = T.reduce(ev)
    assert r["busy_s"] == 2.0 and r["window_s"] == 4.0 and r["devices"] == 2


def test_no_device_plane_reduces_to_nothing():
    assert T.reduce({"/host:CPU": {"python3#0": [("x", 0.0, 1.0)]}}) is None


def test_recorded_chip_trace_reduces_within_its_window():
    path = os.path.join(HERE, "data", "trace_small.json")
    with open(path) as f:
        ev = {p: {ln: [tuple(e) for e in evs] for ln, evs in lines.items()}
              for p, lines in json.load(f)["events"].items()}
    r = T.reduce(ev, is_collective=T.is_collective_op)
    summed = sum(d for p, lines in ev.items() if p.startswith("/device")
                 for evs in lines.values() for _n, _s, d in evs) / 1e9
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["busy_s"] <= summed + 1e-9
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10
    assert all(s > 0 for _n, s in r["device_ops"])
