"""The sparse hybrid block (KDA with a bounded decay or latent attention
that reads what a DeepSeek sparse-attention indexer selects; four
hyper-connected residual streams; expert layers with a shared expert and
clamped SwiGLUs) against its plain reference, at a tiny size on the CPU
with seeded random weights: the full forward, prefill then decode through
``PagedKVCache`` (paged latent rows and index keys beside per-session
state) where the selection prunes, the step's counters, the expert share,
the clamp and the bound, and the engine.  Everything compares LOGITS.

Tolerances.  Program and reference both compute in float32 here, so the
differences are the order of float32 sums (chunked against token-by-token
recurrence, absorbed against expanded attention over the same selected
positions, a grouped product against a per-expert loop): measured
2e-5 to 9e-5 on logits of size ~5.  ``TOL = 5e-4`` is five times the
largest and thousands of times under what ``test_negative_controls``
measures when a part of the mathematics is dropped.
"""

import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.models import sparse_hybrid_decoder as adapter
from benchmark.reference import sparse_hybrid_decoder as ref
from tensorflowonspark_tpu import ops
from tensorflowonspark_tpu.models import hyper_connections as hc
from tensorflowonspark_tpu.models import linear_attention as linear
from tensorflowonspark_tpu.models import moe
from tensorflowonspark_tpu.models import sparse_attention as sparse
from tensorflowonspark_tpu.models import transformer as T
from tensorflowonspark_tpu.serving.decode import kvcache

TOL = 5e-4
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BS = 4
ROWS = 32               # the reference's chunk at this size


def sizes(**over):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "glm-5.3-flash-serve.json")) as f:
        cfg = json.load(f)
    cfg.update(cfg["rehearse"])
    cfg.update(over)
    return cfg


def build(seed, **over):
    cfg = sizes(**over)
    model = dataclasses.replace(adapter.model_config(cfg),
                                attn_impl="reference")
    return cfg, model, adapter.init_params(model, seed)


@pytest.fixture(scope="module")
def all_held():
    """5 layers (K | D K K K, layer 0 dense), all 8 experts held."""
    return build(11, n_routed_experts=8)


@pytest.fixture(scope="module")
def share():
    """The same sizes, experts [0, 4) of 8 held: the rehearse block."""
    return build(12)


def tokens(n, seed=0, vocab=128):
    return np.random.default_rng(seed).integers(1, vocab, (n,)).astype(
        np.int32)


def reference_logits(params, toks, cfg):
    n = -(-len(toks) // ROWS) * ROWS
    padded = np.zeros((n,), np.int32)
    padded[:len(toks)] = toks
    with jax.default_matmul_precision("highest"):
        logits, _ = adapter.reference_forward(
            params, padded, cfg, q_block=16, rows=ROWS)
    return np.asarray(logits)[:len(toks)]


# -- 1. full forward ------------------------------------------------------------

@pytest.mark.parametrize("which", ["all_held", "share"])
def test_apply_matches_the_reference(which, request):
    cfg, model, params = request.getfixturevalue(which)
    toks = tokens(96)           # 24 groups of 4 against a top-16 selection
    got = np.asarray(T.apply(params, toks[None], model)[0])
    np.testing.assert_allclose(got, reference_logits(params, toks, cfg),
                               atol=TOL)


def test_the_layout_keeps_index_keys_beside_the_latent_rows(share):
    _cfg, model, _params = share
    fns = model.decode_fns()
    assert model.mixers == ("kda", "latent", "kda", "kda", "kda")
    assert [(e.name, e.layers, e.paged) for e in fns.rows] == [
        ("kv", 1, True), ("index", 1, True), ("state", 4, False),
        ("conv", 4, False)]
    assert fns.span_counters == sparse.COUNTERS
    cache = kvcache.PagedKVCache(model, slots=3, block_size=BS)
    assert cache.kv.shape == (cache.num_blocks, 1, BS, 16)
    assert cache.index.shape == (cache.num_blocks, 1, BS, 16)
    assert cache.row_bytes == (16 + 16) * 4
    assert cache.entry_row_bytes("index") == 16 * 4
    assert cache.state_row_bytes == 4 * (2 * 8 * 8 + 3 * 48) * 4


# -- 2. prefill, then decode through the cache ----------------------------------

class Sessions:
    """Sessions through ONE ``PagedKVCache`` by the model's seam."""

    def __init__(self, model, params, slots=3):
        self.fns = model.decode_fns()
        self.params = params
        self.cache = kvcache.PagedKVCache(model, slots=slots, block_size=BS)
        self.prefill = jax.jit(self.fns.prefill)
        self.step_fn = jax.jit(self.fns.decode_step_paged,
                               donate_argnums=(2,))
        self.counters = []

    def admit(self, prompt, bucket, rows=1):
        cache, n = self.cache, len(prompt)
        slot = cache.alloc()
        cache.map_session(slot, [], cache.alloc_blocks(-(-n // BS)), n)
        toks = np.zeros((rows, bucket), np.int32)
        toks[:, :n] = prompt
        toks[:-1, :] = 7                # another row's content
        lens = np.full((rows,), bucket, np.int32)
        lens[-1] = n
        logits, kept = self.prefill(self.params, toks, lens)
        cache.insert_tail(slot, *kept, 0, n, row=rows - 1)
        return slot, np.asarray(logits[-1])

    def step(self, feed):
        cache = self.cache
        win = np.zeros((cache.slots, 1), np.int32)
        for slot, tok in feed.items():
            win[slot, 0] = tok
            cache.ensure_capacity(slot, int(cache.lengths[slot]) + 1)
        lengths = cache.lengths.copy()
        logits, cache.pools, counters = self.step_fn(
            self.params, win, cache.pools, cache.block_tables, lengths)
        self.counters.append((lengths, {k: int(v)
                                        for k, v in counters.items()}))
        logits = np.asarray(logits)
        for slot in feed:
            cache.lengths[slot] += 1
        return {slot: logits[slot, 0] for slot in feed}


def paged_decode(model, params, seq, prompt_len, bucket=64):
    s = Sessions(model, params)
    s.cache.alloc()                 # not slot 0
    slot, first = s.admit(seq[:prompt_len], bucket, rows=2)
    out = [first]
    for tok in seq[prompt_len:]:
        out.append(s.step({slot: int(tok)})[slot])
    return np.stack(out), s


def test_prefill_then_paged_decode_matches_the_reference(share):
    """A 40-token prompt and 40 steps against a top-16 selection: the
    prefill selects for every query past the 16th, every step selects."""
    cfg, model, params = share
    seq = tokens(40 + 40, seed=5)
    want = reference_logits(params, seq, cfg)[39:]
    got, _s = paged_decode(model, params, seq, 40)
    assert got.shape[0] == 41
    np.testing.assert_allclose(got, want, atol=TOL)


def test_sessions_come_and_go_and_a_slot_is_reused(share):
    cfg, model, params = share
    seqs = [tokens(30 + 12, seed=31), tokens(50 + 20, seed=32),
            tokens(9 + 25, seed=33)]
    plens = [30, 50, 9]
    want = [reference_logits(params, s, cfg)[p - 1:]
            for s, p in zip(seqs, plens)]
    s = Sessions(model, params, slots=2)
    got = [[], [], []]
    slot_of, pos = {}, {}
    slot_of[0], first = s.admit(seqs[0][:30], 32)
    got[0].append(first)
    pos[0] = 30
    for it in range(60):
        if it == 4:
            slot_of[1], first = s.admit(seqs[1][:50], 64)
            got[1].append(first)
            pos[1] = 50
        out = s.step({slot_of[i]: int(seqs[i][pos[i]]) for i in pos})
        for i in list(pos):
            got[i].append(out[slot_of[i]])
            pos[i] += 1
            if pos[i] == len(seqs[i]):
                s.cache.retire(slot_of[i])
                del pos[i]
                if i == 0:
                    slot_of[2], first = s.admit(seqs[2][:9], 16)
                    assert slot_of[2] == slot_of[0]
                    got[2].append(first)
                    pos[2] = 9
        if not pos:
            break
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.stack(g), w, atol=TOL)
    assert s.cache.occupancy == 0 and s.cache.leaked_blocks() == []


def test_the_steps_counters_count_what_it_read(share):
    """Per step, over the live slots of the one DSA layer: positions up to
    the query, the positions read (every one while a query has at most 4
    groups of 4, then 3 whole groups and its own up to itself), and the
    whole groups before its own that were scored."""
    _cfg, model, params = share
    seq = tokens(12 + 20, seed=9)
    _got, s = paged_decode(model, params, seq, 12, bucket=16)
    for lengths, c in s.counters:
        t = int(lengths[1])         # the one live slot
        assert lengths[0] == 0 and lengths[2] == 0
        assert c["dsa_live_positions"] == t + 1
        assert c["dsa_scored_groups"] == t // 4
        assert c["dsa_selected_positions"] \
            == sparse.selected_positions([t + 1], model) \
            - sparse.selected_positions([t], model)
        assert c["dsa_selected_positions"] == (
            t + 1 if t < 16 else 12 + t % 4 + 1)
    summary = model.decode_fns().summarize(
        {k: sum(c[k] for _l, c in s.counters) for k in s.counters[0][1]})
    assert summary["dsa"]["selected_positions"] \
        < summary["dsa"]["live_positions"]


# -- 3. the expert share, the clamp and the bound ---------------------------------

def test_eight_36_expert_shares_and_the_shared_expert_once_make_the_layer():
    """An expert layer of 288 experts, top-8, in 8 shares of 36 (the
    deployment's cut, at a tiny width): the shared expert counted once
    plus the routed parts of the 8 shares equal the uncut reference's
    FFN, clamp included."""
    cfg, model, params = build(4, n_routed_experts=288, router_width=288,
                               num_experts_per_tok=8)
    m = jax.tree_util.tree_map(lambda a: a[0], params["kda_layers"])["moe"]
    h = 3.0 * jax.random.normal(jax.random.PRNGKey(5), (70, model.dim))
    d = ref.dims_of(cfg)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.expert_ffn(m, jnp.ones((model.dim,)), h, (d, (0, 288)),
                                 None)
    y = ops.rmsnorm_reference(h, jnp.ones((model.dim,)), model.norm_eps)
    total = moe.swiglu(y, m["shared_wg"], m["shared_wu"], m["shared_wd"],
                       model.swiglu_limit)
    routed = {k: v for k, v in m.items() if not k.startswith("shared_")}
    held = 0
    for s in range(8):
        part = dict(routed, **{k: routed[k][36 * s:36 * (s + 1)]
                               for k in ("wg", "wu", "wd")})
        out, stats = moe.apply(part, y, top_k=8,
                               routed_scale=model.routed_scale,
                               expert_offset=36 * s,
                               limit=model.swiglu_limit)
        total = total + out
        held += int(stats["picks_held"])
        assert int(stats["dropped"]) == 0
    assert held == 70 * 8
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=5e-5)


def test_the_clamp_and_the_bound_hold_past_their_limits(share):
    cfg, model, params = share
    x = 50.0 * jax.random.normal(jax.random.PRNGKey(3), (64, model.dim))
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(4), 3)
    wg, wu = (jax.random.normal(k, (model.dim, 16)) for k in (k1, k2))
    wd = jax.random.normal(k3, (16, model.dim))
    g, u = x @ wg, x @ wu
    assert float(jnp.max(g)) > 100 and float(jnp.min(u)) < -100
    want = (jax.nn.silu(jnp.minimum(g, 10.0)) * jnp.clip(u, -10, 10)) @ wd
    got = moe.swiglu(x, wg, wu, wd, 10.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-3)
    assert float(jnp.max(jnp.abs(moe.swiglu(x, wg, wu, wd) - got))) > 100
    # the decay: whatever the input, alpha = e^g with -5 < g < 0, as the
    # reference computes it
    p = jax.tree_util.tree_map(lambda a: a[0], params["kda_layers"])
    y = 1e3 * jax.random.normal(jax.random.PRNGKey(6), (1, 32, model.dim))
    window = jnp.zeros((1, 3 + 32, 3 * 16))
    _q, _k, _v, gd, _b = linear._inputs(p["kda"], y, window, model)
    assert float(jnp.min(gd)) >= -5.0 and float(jnp.max(gd)) <= 0.0
    assert float(jnp.min(gd)) < -4.9        # the bound is reached
    d = ref.dims_of(cfg)
    with jax.default_matmul_precision("highest"):
        _raw, want_g, _beta, _z = ref.kda_in(
            p["kda"], jnp.ones((model.dim,)), y[0], d, None)
    h = ops.rmsnorm_reference(y, jnp.ones((model.dim,)), model.norm_eps)
    _q, _k, _v, gd, _b = linear._inputs(p["kda"], h, window, model)
    np.testing.assert_allclose(np.asarray(gd[0]), np.asarray(want_g),
                               atol=1e-5)


# -- 4. negative controls ---------------------------------------------------------

@pytest.mark.parametrize("broken", ["selection_off", "decay_unbounded",
                                    "streams_collapsed"])
def test_negative_controls(share, broken):
    """The comparison of test 2 FAILS when part of the mathematics is
    dropped: every position read (no selection), the softplus decay
    instead of the bounded one, one plain residual instead of four
    streams (every map's coefficients forced to a plain residual's)."""
    cfg, model, params = share
    seq = tokens(40 + 40, seed=5)
    want = reference_logits(params, seq, cfg)[39:]
    if broken == "selection_off":
        model = dataclasses.replace(model, index_topk=4096)
    elif broken == "decay_unbounded":
        model = dataclasses.replace(model, linear_gate_lower_bound=0.0)
    else:
        def plain(tree):
            return dict(tree, alpha=jnp.zeros_like(tree["alpha"]),
                        bias=jnp.zeros_like(tree["bias"]))
        params = dict(params, **{stack: dict(
            params[stack], hc_attn=plain(params[stack]["hc_attn"]),
            hc_ffn=plain(params[stack]["hc_ffn"]))
            for stack in ("dense_layers", "layers", "kda_layers")})
    got, _s = paged_decode(model, params, seq, 40)
    assert np.max(np.abs(got - want)) > 10 * TOL, broken


# -- 5. the engine ------------------------------------------------------------------

def test_the_engine_serves_the_model_and_counts_its_selection(share):
    from tensorflowonspark_tpu import serving
    from tensorflowonspark_tpu.serving.decode import scheduler

    cfg, model, params = share
    spec = serving.DecodeSpec(model, slots=3, block_size=BS, max_tokens=10,
                              prefill_tokens=64)
    assert spec.prefix_sharing is False
    prompts = [tokens(n, seed=40 + n).tolist() for n in (30, 50, 21, 9)]
    out = []
    eng = scheduler.DecodeEngine(params, spec, out.extend)
    eng.start(timeout=300)
    try:
        for i, p in enumerate(prompts):
            eng.submit(f"s{i}", p, max_tokens=10)
        deadline = time.monotonic() + 300
        while eng.retired < len(prompts) and time.monotonic() < deadline:
            time.sleep(0.05)
        assert eng.retired == len(prompts)
        st = eng.stats()
        assert eng._cache.leaked_blocks() == []
        assert eng._cache.occupancy == 0 and eng._cache.blocks_in_use == 0
    finally:
        eng.stop()
    dsa = st["dsa"]
    assert 0 < dsa["selected_positions"] < dsa["live_positions"]
    assert dsa["scored_groups"] > 0
    assert st["cache"]["index_row_bytes"] == 16 * 4
    assert st["step_counters"]["dsa_live_positions"] \
        == dsa["live_positions"]
    tokens_of = {ev[1]: ev[2] for ev in out if ev[0] == "done"}
    for i, prompt in enumerate(prompts):
        served = tokens_of.get(f"s{i}") or []
        assert len(served) == 10, (i, out[:4])
        seq = np.asarray(prompt + served, np.int32)
        rows = reference_logits(params, seq, cfg)[len(prompt) - 1:-1]
        for row, tok in zip(rows, served):
            assert row[tok] >= np.max(row) - TOL


def test_the_config_refuses_a_selection_it_cannot_run(share):
    _cfg, model, _params = share
    with pytest.raises(ValueError, match="a sparse selection needs"):
        dataclasses.replace(model, index_topk=18)   # not groups of 4
    with pytest.raises(ValueError, match="a sparse selection needs"):
        dataclasses.replace(model, index_rope_dim=7)
    with pytest.raises(ValueError, match="written for the hybrid block"):
        dataclasses.replace(model, linear_layers=())
    with pytest.raises(ValueError, match="even qk_rope_dim"):
        dataclasses.replace(model, qk_rotary=True)  # a rotary part of 0
    assert hc.widen(jnp.ones((2, 3)), 4).shape == (2, 4, 3)
