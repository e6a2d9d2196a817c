"""The latent block (latent attention + expert layers with a shared
expert) against its plain reference, at a tiny size on the CPU with
seeded random weights.  Everything compares LOGITS.

Tolerances.  Program and reference both compute in float32 here, so the
only differences are the order of float32 sums (a scan against a Python
loop, absorbed against expanded attention, a grouped product against a
per-expert loop): a few 1e-6 on logits of size ~5 (measured: 7e-6 at
most).  ``TOL = 2e-4`` is thirty times that and still fifty times under
what the nearest lower precision does to the same logits: a bfloat16
cache moves them by 1e-2, a float8 cache by 1e-1 (``test_negative_
controls`` checks both, and that dropping part of the mathematics fails
too).
"""

import dataclasses
import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.models import latent_moe_decoder as adapter
from benchmark.reference import latent_moe_decoder as ref
from tensorflowonspark_tpu import ops
from tensorflowonspark_tpu.models import latent_attention as latent
from tensorflowonspark_tpu.models import moe
from tensorflowonspark_tpu.models import transformer as T
from tensorflowonspark_tpu.serving.decode import kvcache

TOL = 2e-4
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def sizes(**over):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "sarvam-105b-serve.json")) as f:
        cfg = json.load(f)
    cfg.update(cfg["rehearse"])
    cfg.update(over)
    return cfg


@pytest.fixture(scope="module")
def all_held():
    """3 layers (1 dense + 2 expert), all 8 experts held, top-2."""
    cfg = sizes(num_experts=8)
    model = dataclasses.replace(adapter.model_config(cfg),
                                attn_impl="reference")
    return cfg, model, adapter.init_params(model, 11)


@pytest.fixture(scope="module")
def share():
    """The same sizes, experts [0, 4) of 8 held: the rehearse block."""
    cfg = sizes()
    model = dataclasses.replace(adapter.model_config(cfg),
                                attn_impl="reference")
    return cfg, model, adapter.init_params(model, 12)


def tokens(n, seed=0, vocab=128):
    return np.random.default_rng(seed).integers(1, vocab, (n,)).astype(
        np.int32)


def reference_logits(params, toks, cfg, **kw):
    with jax.default_matmul_precision("highest"):
        return np.asarray(adapter.reference_forward(
            params, toks, cfg, q_block=16, **kw)[0])


# -- 1. full forward ------------------------------------------------------

@pytest.mark.parametrize("which", ["all_held", "share"])
def test_apply_matches_the_reference(which, request):
    cfg, model, params = request.getfixturevalue(which)
    toks = tokens(40)
    got = np.asarray(T.apply(params, toks[None], model)[0])
    np.testing.assert_allclose(got, reference_logits(params, toks, cfg),
                               atol=TOL)


def test_apply_with_the_flash_kernel_matches(share):
    """q/k width 24 against v width 16 through the pallas kernel."""
    cfg, model, params = share
    toks = tokens(40, seed=3)
    flash = dataclasses.replace(model, attn_impl="flash")
    got = np.asarray(T.apply(params, toks[None], flash)[0])
    np.testing.assert_allclose(got, reference_logits(params, toks, cfg),
                               atol=TOL)


# -- 2. prefill, then decode through the paged latent cache ---------------

def paged_decode(model, params, seq, prompt_len, window, cache_dtype=None,
                 fns=None, extend_from=None):
    """Logits at every position from ``prompt_len - 1`` on: prefill the
    prompt (or, with ``extend_from``, only its tail over that many
    already-cached tokens), then feed ``seq``'s tokens ``window`` at a
    time through ``decode_step_paged``."""
    fns = fns or model.decode_fns()
    cache = kvcache.PagedKVCache(model, slots=2, block_size=4,
                                 dtype=cache_dtype, prefix_sharing=False)
    slot = cache.alloc()
    own = cache.alloc_blocks(-(-prompt_len // 4))
    cache.map_session(slot, [], own, prompt_len)
    prompt = seq[None, :prompt_len]
    if extend_from is None:
        logits, rows = fns.prefill(params, prompt,
                                   np.asarray([prompt_len], np.int32))
        cache.insert_tail(slot, *rows, 0, prompt_len, row=0)
    else:
        m = extend_from
        _lg, rows = fns.prefill(params, prompt[:, :m],
                                np.asarray([m], np.int32))
        cache.insert_tail(slot, *rows, 0, m, row=0)
        ptab = cache.block_tables[slot:slot + 1, :m // 4]
        logits, rows = fns.prefill_extend(
            params, prompt[:, m:], cache.pools, ptab,
            np.asarray([m], np.int32),
            np.asarray([prompt_len - m], np.int32))
        cache.insert_tail(slot, *rows, m, prompt_len - m, row=0)
    out = [np.asarray(logits[0])]
    step = jax.jit(fns.decode_step_paged)
    pos = prompt_len
    while pos < len(seq):
        w = min(window, len(seq) - pos)
        win = np.zeros((cache.slots, window), np.int32)
        win[slot, :w] = seq[pos:pos + w]
        cache.ensure_capacity(slot, pos + window)
        logits, cache.pools, _c = step(params, win, cache.pools,
                                       cache.block_tables,
                                       cache.lengths.copy())
        out.extend(np.asarray(logits[slot, :w]))
        cache.lengths[slot] += w
        pos += w
    return np.stack(out)


@pytest.mark.parametrize("window", [1, 4])
def test_prefill_then_paged_decode_matches_the_reference(share, window):
    cfg, model, params = share
    seq = tokens(12 + 28, seed=5)
    want = reference_logits(params, seq, cfg)[11:]
    got = paged_decode(model, params, seq, 12, window)
    assert got.shape[0] == 29 >= 24
    np.testing.assert_allclose(got, want, atol=TOL)


def test_prefill_extend_on_a_cached_prefix_matches_the_reference(share):
    cfg, model, params = share
    seq = tokens(14 + 26, seed=6)
    want = reference_logits(params, seq, cfg)[13:]
    got = paged_decode(model, params, seq, 14, 1, extend_from=8)
    np.testing.assert_allclose(got, want, atol=TOL)


# -- 3. the two attention paths agree ---------------------------------------

def test_absorbed_path_equals_expanded_path(share):
    _cfg, model, params = share
    p = jax.tree_util.tree_map(lambda a: a[0], params["layers"])["attn"]
    y = jax.random.normal(jax.random.PRNGKey(2), (2, 20, model.dim))
    cos, sin = latent.rope_tables(model, 20)
    q, rows = latent.project(p, y, model, cos, sin)
    expanded = latent.attend_expanded(
        p, q, rows, model,
        lambda q, k, v, scale: ops.mha_reference(q, k, v, causal=True,
                                                 scale=scale))
    causal = jnp.tril(jnp.ones((20, 20), bool))[None, None]
    absorbed = latent.attend_absorbed(
        p, q, rows, jnp.broadcast_to(causal, (2, 1, 20, 20)), model)
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(expanded),
                               atol=2e-5)


# -- 4. the share ties to the model -----------------------------------------

def test_four_shares_and_the_shared_expert_once_make_the_whole_layer():
    """E = 32 in 4 shares of 8: the routed parts of the 4 shares plus the
    shared expert counted once equal the uncut reference's layer."""
    dim, hidden, e, k = 32, 16, 32, 8
    whole = moe.init(jax.random.PRNGKey(4), dim, hidden, e, num_shared=1)
    h = jax.random.normal(jax.random.PRNGKey(5), (3, 11, dim))
    sz = {"num_experts_per_tok": k, "routed_scaling_factor": 2.5}
    with jax.default_matmul_precision("highest"):
        want, _ = ref.expert_ffn(whole, h.reshape(-1, dim), sz, (0, e))
    routed = dict((n, w) for n, w in whole.items()
                  if not n.startswith("shared_"))
    total = moe.swiglu(h, whole["shared_wg"], whole["shared_wu"],
                       whole["shared_wd"])
    held_sum = 0
    for s in range(4):
        part = dict(routed, **{n: routed[n][8 * s:8 * s + 8]
                               for n in ("wg", "wu", "wd")})
        y, stats = moe.apply(part, h, top_k=k, routed_scale=2.5,
                             expert_offset=8 * s)
        total = total + y
        held_sum += int(stats["picks_held"])
        assert int(stats["dropped"]) == 0
    assert held_sum == 3 * 11 * k       # every pick is held by one share
    np.testing.assert_allclose(np.asarray(total).reshape(-1, dim),
                               np.asarray(want), atol=2e-5)


def test_the_bias_picks_and_the_score_gates():
    """A case where ``b`` flips a choice: expert 2 scores below expert 1
    but its bias lifts it; the gates are still of the raw scores."""
    dim = 4
    p = moe.init(jax.random.PRNGKey(0), dim, 8, 3)
    # router columns give logits (2, 1, 0.9) for h = e_0
    p["router"] = jnp.zeros((dim, 3)).at[0].set(jnp.array([2.0, 1.0, 0.9]))
    h = jnp.zeros((1, dim)).at[0, 0].set(1.0)
    s = jax.nn.sigmoid(jnp.array([2.0, 1.0, 0.9]))
    p["router_bias"] = jnp.zeros((3,))
    idx, gates, _ = moe.route(p, h, 2, routed_scale=2.5)
    assert sorted(np.asarray(idx[0]).tolist()) == [0, 1]
    p["router_bias"] = jnp.array([0.0, 0.0, 0.05])
    idx, gates, _ = moe.route(p, h, 2, routed_scale=2.5)
    assert sorted(np.asarray(idx[0]).tolist()) == [0, 2]
    order = np.argsort(np.asarray(idx[0]))
    np.testing.assert_allclose(
        np.asarray(gates[0])[order],
        2.5 * np.asarray([s[0], s[2]]) / float(s[0] + s[2]), rtol=1e-6)


# -- 5. dropless under imbalance ---------------------------------------------

def test_every_token_on_the_same_experts_is_still_computed():
    dim, hidden, e, k = 32, 16, 16, 8
    p = moe.init(jax.random.PRNGKey(7), dim, hidden, e, num_shared=1)
    # the bias decides: every token picks experts 0..7
    p["router_bias"] = jnp.where(jnp.arange(e) < k, 10.0, 0.0)
    h = jax.random.normal(jax.random.PRNGKey(8), (64, dim))
    y, stats = moe.apply(p, h, top_k=k, routed_scale=2.5)
    assert int(stats["experts_touched"]) == k
    assert int(stats["tokens_per_expert_max"]) == 64
    assert int(stats["dropped"]) == 0
    sz = {"num_experts_per_tok": k, "routed_scaling_factor": 2.5}
    with jax.default_matmul_precision("highest"):
        want, _ = ref.expert_ffn(p, h, sz, (0, e))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-5)
    # and through the chunked dispatch of long inputs
    y2, _ = moe.apply(p, h, top_k=k, routed_scale=2.5, chunk=24)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(want), atol=2e-5)


# -- 6. YaRN ------------------------------------------------------------------

def test_yarn_frequencies_and_softmax_factor_by_hand():
    """factor 40 over 4096, beta 32 / 1, base 10000, 64 rope dimensions:
    the ramp runs from dimension 10 (floor of 64 ln(4096 / (32 * 2 pi)) /
    (2 ln 10000) = 10.46) to 23 (ceil of 22.50): pairs below 10 keep
    their frequency, pairs from 23 on are divided by 40."""
    import math

    sc = ops.YarnScaling(factor=40.0, original_max_seq=4096, beta_fast=32.0,
                         beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0)
    f = np.asarray(ops.yarn_frequencies(64, 10000.0, sc))
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(f[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(f[23:], plain[23:] / 40.0, rtol=1e-6)
    # half way up the ramp, pair 16: (16 - 10) / 13 interpolated
    r = (16 - 10) / 13
    np.testing.assert_allclose(f[16], plain[16] * (r / 40 + 1 - r),
                               rtol=1e-5)
    m = 0.1 * math.log(40.0) + 1.0
    assert abs(m - 1.3688879) < 1e-6
    assert abs(ops.yarn_softmax_scale(192, sc) - m * m / math.sqrt(192)) \
        < 1e-9
    assert abs(ops.yarn_softmax_scale(192, sc) - 0.135234) < 1e-6
    # cos and sin are scaled by mscale(40, 1) / mscale(40, 1) = 1
    cos, sin = ops.rope_angles(8, 64, 10000.0, scaling=sc)
    np.testing.assert_allclose(np.asarray(cos[0]), np.ones(32), atol=1e-7)
    np.testing.assert_allclose(
        np.asarray(sin[3]), np.sin(3 * f), atol=1e-6)
    # the reference computes the same table from the config's own group
    group = sizes(num_experts=8)["rope_scaling"]
    np.testing.assert_allclose(
        np.asarray(ref.yarn_inv_freq(64, 10000.0, group)), f, rtol=1e-6)


# -- 7. negative controls -----------------------------------------------------

def _no_rope_scores(fns_cfg):
    """The model's seam with the rope part of the score dropped (the
    rotary key columns of every cached row zeroed on the way in)."""
    real = fns_cfg.decode_fns()

    def step(p, toks, pools, tables, lens):
        r = fns_cfg.kv_lora_rank
        logits, new, c = real.decode_step_paged(p, toks, pools, tables, lens)
        return logits, tuple(x.at[..., r:].set(0) for x in new), c

    return dataclasses.replace(real, decode_step_paged=step)


@pytest.mark.parametrize("broken", [
    "no_rope_in_the_score", "no_shared_expert", "float8_cache", "int8_cache",
    "bfloat16_cache"])
def test_negative_controls(share, broken):
    """The comparison of test 2 FAILS when part of the mathematics is
    dropped, or the cache is kept in less than the configuration states."""
    cfg, model, params = share
    seq = tokens(12 + 28, seed=5)
    want = reference_logits(params, seq, cfg)[11:]
    kw = {}
    if broken == "no_rope_in_the_score":
        kw["fns"] = _no_rope_scores(model)
    elif broken == "no_shared_expert":
        layers = dict(params["layers"])
        layers["moe"] = {k: (jnp.zeros_like(v) if k == "shared_wd" else v)
                         for k, v in layers["moe"].items()}
        params = dict(params, layers=layers)
    else:
        kw["cache_dtype"] = {"float8_cache": jnp.float8_e4m3fn,
                             "int8_cache": jnp.int8,
                             "bfloat16_cache": jnp.bfloat16}[broken]
    got = paged_decode(model, params, seq, 12, 1, **kw)
    assert np.max(np.abs(got - want)) > 10 * TOL, broken


# -- 8. end to end through serving.Server -------------------------------------

def test_server_serves_the_latent_model_and_leaks_no_block(share, tmp_path):
    from tensorflowonspark_tpu import serving
    from tensorflowonspark_tpu.serving.decode import scheduler
    from tensorflowonspark_tpu.utils import checkpoint as ckpt

    cfg, model, params = share
    export = ckpt.export_model(str(tmp_path / "export"), params, metadata={})
    spec = serving.DecodeSpec(model, slots=4, block_size=4, max_tokens=12,
                              prefill_tokens=64)
    prompts = [tokens(n, seed=20 + n).tolist() for n in (9, 17, 30, 17)]
    prompts.append(prompts[1][:16] + [5, 6, 7])     # shares 4 blocks
    with serving.Server(serving.ModelSpec(export_dir=export, decode=spec),
                        num_replicas=1, request_timeout=300,
                        env={"JAX_PLATFORMS": "cpu"}) as srv:
        replies = [None] * 5
        threads = [threading.Thread(
            target=lambda i=i: replies.__setitem__(
                i, srv.generate(prompts[i], max_tokens=12, timeout=300)))
            for i in range(4)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        replies[4] = srv.generate(prompts[4], max_tokens=12, timeout=300)
        st = next(iter(srv.pool.stats().values()))["decode"]
    for prompt, rep in zip(prompts, replies):
        # greedy decoding by the reference: under a causal mask one pass
        # over prompt + served tokens gives every step's logits
        assert len(rep["tokens"]) == 12
        seq = np.asarray(list(prompt) + rep["tokens"], np.int32)
        rows = reference_logits(params, seq, cfg)[len(prompt) - 1:-1]
        for row, tok in zip(rows, rep["tokens"]):
            assert row[tok] >= np.max(row) - TOL    # a gap under TOL: a tie
    assert st["prefix_hits"] >= 1
    assert st["moe"]["dropped"] == 0 and 0 < st["moe"]["picks_held"] < 1
    assert st["cache"]["row_bytes"] == 3 * 40 * 4
    assert st["blocks_in_use"] >= 0

    # the same engine, in process: every block is accounted for at the end
    out = []
    eng = scheduler.DecodeEngine(params, spec, out.extend)
    eng.start(timeout=300)
    try:
        for i, p in enumerate(prompts):
            eng.submit(f"s{i}", p, max_tokens=6)
        deadline = time.monotonic() + 300
        while eng.retired < len(prompts) and time.monotonic() < deadline:
            time.sleep(0.05)
        assert eng.retired == len(prompts)
        assert eng._cache.leaked_blocks() == []
        assert eng._cache.occupancy == 0
    finally:
        eng.stop()


def test_the_engine_names_the_prefill_and_counts_for_an_interval(share):
    """A session's result names the row bucket of the prefill program
    that admitted it, and ``stats()`` holds the raw totals that two
    snapshots are differenced from (the summaries beside them are means
    since the engine started)."""
    from tensorflowonspark_tpu import serving
    from tensorflowonspark_tpu.serving.decode import scheduler

    _cfg, model, params = share
    spec = serving.DecodeSpec(model, slots=4, block_size=4, max_tokens=8)
    done = {}

    def emit(events):
        for kind, sid, *payload in events:
            if kind == "done":
                done[sid] = payload[1]
    eng = scheduler.DecodeEngine(params, spec, emit)

    def wait_for(sids):
        deadline = time.monotonic() + 300
        while not set(sids) <= set(done) and time.monotonic() < deadline:
            time.sleep(0.05)
        assert set(sids) <= set(done)
    # queued before the engine runs: ONE wave of three, padded to four rows
    for i, sid in enumerate("bcd"):
        eng.submit(sid, tokens(11, seed=i).tolist(), max_tokens=8)
    eng.start(timeout=300)
    try:
        wait_for("bcd")
        s0 = eng.stats()
        eng.submit("a", tokens(9, seed=9).tolist(), max_tokens=8)
        wait_for("a")
        s1 = eng.stats()
    finally:
        eng.stop()
    assert {done[k]["prefill_rows"] for k in "bcd"} == {4}
    assert done["a"]["prefill_rows"] == 1
    # the interval: one session's 7 decode steps, 9 + i tokens at the i-th
    assert s1["iterations"] - s0["iterations"] == 7
    assert s1["cache"]["live_token_steps"] \
        - s0["cache"]["live_token_steps"] == sum(9 + i for i in range(7))
    delta = {k: v - s0["step_counters"].get(k, 0)
             for k, v in s1["step_counters"].items()}
    # a free slot runs and is not counted: 1 live token x top-2 x 2 expert
    # layers a step
    assert delta["moe_picks"] == 7 * 2 * 2
    assert delta["moe_dropped"] == 0
    assert s1["moe"]["dropped"] == 0


def test_the_classic_programs_and_a_gated_mha_config_refuse_clearly(share):
    _cfg, model, params = share
    with pytest.raises(ValueError, match="classic block"):
        T.prefill(params, np.zeros((1, 8), np.int32), model)
    with pytest.raises(ValueError, match="attn_kind='latent'"):
        T.Config(ffn_kind="swiglu")


def test_bfloat16_weights_survive_export_and_load(tmp_path):
    from tensorflowonspark_tpu.utils import checkpoint as ckpt

    cfg = sizes(param_dtype="bfloat16", compute_dtype="bfloat16")
    model = adapter.model_config(cfg)
    params = adapter.init_params(model, 1)
    assert all(a.dtype == jnp.bfloat16 or a.ndim == 2 and a.shape[-1] == 8
               for a in jax.tree_util.tree_leaves(params))
    ckpt.export_model(str(tmp_path / "e"), params, metadata={})
    back, _meta = ckpt.load_exported(str(tmp_path / "e"))
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(back)):
        assert b.dtype == a.dtype
        np.testing.assert_array_equal(np.asarray(a).view(np.uint8),
                                      np.asarray(b).view(np.uint8))


def test_the_prefill_bound_cuts_a_wave():
    from tensorflowonspark_tpu import serving
    from tensorflowonspark_tpu.serving.decode import scheduler

    spec = serving.DecodeSpec(T.Config(), slots=16, prefill_tokens=16384)
    eng = scheduler.DecodeEngine(None, spec, lambda *a: None)
    members = list(range(5))
    assert [len(w) for w in eng._waves(members, 8192)] == [2, 2, 1]
    assert [len(w) for w in eng._waves(members, 1024)] == [5]
    assert [len(w) for w in eng._waves(members, 16384)] == [1] * 5
    unbounded = scheduler.DecodeEngine(
        None, serving.DecodeSpec(T.Config(), slots=16), lambda *a: None)
    assert unbounded._waves(members, 8192) == [members]
