"""The hybrid block (a mixer per layer: Kimi Delta Attention or latent
attention without rotary; expert layers with a shared expert) against its
plain reference, at a tiny size on the CPU with seeded random weights:
the full forward, prefill then decode through ``PagedKVCache`` (paged
latent rows beside per-session state), sessions coming and going, and
``serving.Server``.  Everything compares LOGITS.

Tolerances.  Program and reference both compute in float32 here, so the
differences are the order of float32 sums: the chunked delta rule against
the token-by-token recurrence, absorbed against expanded attention, a
grouped product against a per-expert loop, through 8 layers.  Measured:
2e-5 to 1e-4 on logits of size ~5 (ten times the latent block's, whose
sums are shorter: a recurrent state is a sum over the whole sequence).
``TOL = 5e-4`` is five times the largest and thousands of times under
what ``test_negative_controls`` measures: a bfloat16 state moves the same
logits by 1.3-3.8 (it changes expert choices), dropping the decay, the
write strength or the convolution's history by 6.5-10, rotating the latent
layers by 3.5-4.2.
"""

import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.models import hybrid_linear_decoder as adapter
from benchmark.reference import hybrid_linear_decoder as ref
from tensorflowonspark_tpu import ops
from tensorflowonspark_tpu.models import latent_attention as latent
from tensorflowonspark_tpu.models import linear_attention as linear
from tensorflowonspark_tpu.models import moe
from tensorflowonspark_tpu.models import transformer as T
from tensorflowonspark_tpu.serving.decode import kvcache

TOL = 5e-4
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BS = 4


def sizes(**over):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "kimi-linear-48b-serve.json")) as f:
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
    """8 layers (K K K M K K K M, layer 1 dense), all 8 experts held."""
    return build(11, num_experts=8)


@pytest.fixture(scope="module")
def share():
    """The same sizes, experts [0, 4) of 8 held: the rehearse block."""
    return build(12)


def tokens(n, seed=0, vocab=128):
    return np.random.default_rng(seed).integers(1, vocab, (n,)).astype(
        np.int32)


def reference_logits(params, toks, cfg, **kw):
    with jax.default_matmul_precision("highest"):
        return np.asarray(adapter.reference_forward(
            params, toks, cfg, q_block=16, **kw)[0])


# -- 1. full forward ------------------------------------------------------------

@pytest.mark.parametrize("which", ["all_held", "share"])
def test_apply_matches_the_reference(which, request):
    cfg, model, params = request.getfixturevalue(which)
    toks = tokens(150)                  # three chunks of the delta rule
    got = np.asarray(T.apply(params, toks[None], model)[0])
    np.testing.assert_allclose(got, reference_logits(params, toks, cfg),
                               atol=TOL)


def test_the_layout_has_rows_of_two_layers_and_state_of_six(share):
    _cfg, model, _params = share
    fns = model.decode_fns()
    assert model.mixers == ("kda",) * 3 + ("latent",) + ("kda",) * 3 \
        + ("latent",)
    assert [(e.name, e.layers, e.paged) for e in fns.rows] == [
        ("kv", 2, True), ("state", 6, False), ("conv", 6, False)]
    assert fns.has_state and fns.prefill_extend is None and fns.donate
    cache = kvcache.PagedKVCache(model, slots=3, block_size=BS)
    assert cache.trie is None           # the default resolves to no trie
    assert cache.kv.shape == (cache.num_blocks, 2, BS, 40)
    assert cache.state.shape == (6, 3, 4, 16, 16) \
        and cache.state.dtype == jnp.float32
    assert cache.conv.shape == (6, 3, 3, 192)
    assert cache.row_bytes == 2 * 40 * 4
    assert cache.state_row_bytes == 6 * (4 * 16 * 16 + 3 * 192) * 4
    # the classic and the latent block: rows only, nothing per session
    for other in (T.Config(vocab_size=32, dim=16, n_layers=2, n_heads=2),
                  dataclasses.replace(model, linear_layers=())):
        rows = other.decode_fns().rows
        assert all(e.paged and e.layers == other.n_layers for e in rows)
        small = kvcache.PagedKVCache(other, slots=2, block_size=BS)
        assert small.state_row_bytes == 0 and small.trie is not None


# -- 2. prefill, then decode through the cache ----------------------------------

class Sessions:
    """Sessions through ONE ``PagedKVCache`` by the model's seam: admit
    (bucketed prefill + insert), step every slot together, retire."""

    def __init__(self, model, params, slots=3, fns=None):
        self.fns = fns or model.decode_fns()
        self.params = params
        self.cache = kvcache.PagedKVCache(model, slots=slots, block_size=BS)
        self.prefill = jax.jit(self.fns.prefill)
        self.step_fn = jax.jit(self.fns.decode_step_paged,
                               donate_argnums=(2,))

    def admit(self, prompt, bucket, rows=2):
        """Returns ``(slot, logits after the prompt)``; the prompt sits in
        the LAST row of a ``rows`` x ``bucket`` prefill, behind padding."""
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
        """``feed``: {slot: token}; returns {slot: logits}."""
        cache = self.cache
        win = np.zeros((cache.slots, 1), np.int32)
        for slot, tok in feed.items():
            win[slot, 0] = tok
            cache.ensure_capacity(slot, int(cache.lengths[slot]) + 1)
        logits, cache.pools, _c = self.step_fn(
            self.params, win, cache.pools, cache.block_tables,
            cache.lengths.copy())
        logits = np.asarray(logits)
        for slot in feed:
            cache.lengths[slot] += 1
        return {slot: logits[slot, 0] for slot in feed}


def paged_decode(model, params, seq, prompt_len, **kw):
    """Logits at every position from ``prompt_len - 1`` on."""
    s = Sessions(model, params, **kw)
    s.cache.alloc()                 # not slot 0: a state row is its slot's
    slot, first = s.admit(seq[:prompt_len], 32)
    out = [first]
    for tok in seq[prompt_len:]:
        out.append(s.step({slot: int(tok)})[slot])
    return np.stack(out)


def test_prefill_then_paged_decode_matches_the_reference(share):
    cfg, model, params = share
    seq = tokens(21 + 52, seed=5)
    want = reference_logits(params, seq, cfg)[20:]
    got = paged_decode(model, params, seq, 21)
    assert got.shape[0] == 53 >= 48
    np.testing.assert_allclose(got, want, atol=TOL)


def test_sessions_come_and_go_and_a_slot_is_reused(share):
    """Two sessions of different lengths admitted at different iterations;
    the first retires and its slot goes to a third, whose logits are the
    reference's from its first token on: nothing of the retired session's
    state reaches its successor."""
    cfg, model, params = share
    seqs = [tokens(9 + 20, seed=31), tokens(30 + 40, seed=32),
            tokens(5 + 30, seed=33)]
    plens = [9, 30, 5]
    want = [reference_logits(params, s, cfg)[p - 1:]
            for s, p in zip(seqs, plens)]
    s = Sessions(model, params, slots=2)
    got = [[], [], []]
    slot_a, first = s.admit(seqs[0][:9], 16)
    got[0].append(first)
    pos = {0: 9}
    slot_of = {0: slot_a}
    for it in range(60):
        if it == 6:                     # b joins six iterations later
            slot_of[1], first = s.admit(seqs[1][:30], 32)
            got[1].append(first)
            pos[1] = 30
        feed = {slot_of[i]: int(seqs[i][pos[i]]) for i in pos}
        out = s.step(feed)
        for i in list(pos):
            got[i].append(out[slot_of[i]])
            pos[i] += 1
            if pos[i] == len(seqs[i]):
                s.cache.retire(slot_of[i])
                del pos[i]
                if i == 0:              # a is done: c takes its slot
                    slot_of[2], first = s.admit(seqs[2][:5], 8, rows=1)
                    assert slot_of[2] == slot_a
                    got[2].append(first)
                    pos[2] = 5
        if not pos:
            break
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.stack(g), w, atol=TOL)
    assert s.cache.occupancy == 0 and s.cache.leaked_blocks() == []


def test_admission_overwrites_all_of_a_slots_state(share):
    """A free slot's state is whatever the last step left (here: 7.0 in
    every state entry, finite as all a step leaves is: a free slot's rows
    reach the sentinel block, which live slots read under a zero
    weight).  The insert writes all of it."""
    cfg, model, params = share
    seq = tokens(12 + 10, seed=8)
    s = Sessions(model, params, slots=2)
    s.cache.pools = tuple(
        pool if entry.paged else jnp.full_like(pool, 7.0)
        for entry, pool in zip(s.cache.layout, s.cache.pools))
    slot, first = s.admit(seq[:12], 16)
    got = [first] + [s.step({slot: int(t)})[slot] for t in seq[12:]]
    np.testing.assert_allclose(np.stack(got),
                               reference_logits(params, seq, cfg)[11:],
                               atol=TOL)
    assert not np.any(np.asarray(s.cache.conv[:, slot]) == 7.0)


# -- 3. the latent layers: no rotary, and a pool of their own depth ------------

def test_absorbed_path_equals_expanded_path_without_rotary(share):
    _cfg, model, params = share
    p = jax.tree_util.tree_map(lambda a: a[0], params["layers"])["attn"]
    y = jax.random.normal(jax.random.PRNGKey(2), (2, 20, model.dim))
    cos, sin = latent.rope_tables(model, 20)
    assert cos is None and sin is None
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
    # no position enters: the rows of a shifted sequence are the same rows
    _q, shifted = latent.project(p, y[:, 5:], model, cos, sin)
    np.testing.assert_array_equal(np.asarray(shifted),
                                  np.asarray(rows[:, 5:]))


# -- 4. the share ties to the model ---------------------------------------------

def test_four_shares_and_the_shared_expert_once_make_a_whole_kda_layer():
    """A KDA expert layer of the new block with E = 8 in 4 shares of 2:
    the mixer (computed alike on every chip) and the shared expert counted
    once, plus the routed parts of the 4 shares, equal the uncut
    reference's layer."""
    cfg, model, params = build(4, num_experts=8)
    layer = jax.tree_util.tree_map(lambda a: a[1], params["kda_layers"])
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 70, model.dim))
    with jax.default_matmul_precision("highest"):
        want, _ = ref._layer(layer, x[0], cfg, (0, 8), None, 16)
    y = ops.rmsnorm_reference(x, layer["ln1"], model.norm_eps)
    x1 = x + linear.mix_prefill(layer["kda"], y, model)[0]
    h = ops.rmsnorm_reference(x1, layer["ln2"], model.norm_eps)
    m = layer["moe"]
    total = x1 + moe.swiglu(h, m["shared_wg"], m["shared_wu"],
                            m["shared_wd"])
    routed = {k: v for k, v in m.items() if not k.startswith("shared_")}
    held = 0
    for s in range(4):
        part = dict(routed, **{k: routed[k][2 * s:2 * s + 2]
                               for k in ("wg", "wu", "wd")})
        out, stats = moe.apply(part, h, top_k=model.experts_per_token,
                               routed_scale=model.routed_scale,
                               expert_offset=2 * s)
        total = total + out
        held += int(stats["picks_held"])
        assert int(stats["dropped"]) == 0
    assert held == 70 * model.experts_per_token
    np.testing.assert_allclose(np.asarray(total[0]), np.asarray(want),
                               atol=5e-5)


# -- 5. negative controls ---------------------------------------------------------

def _history_dropped(model):
    """The seam with the convolution's history NOT carried into decode:
    every step sees zeros before its token."""
    real = model.decode_fns()

    def step(p, toks, pools, tables, lens):
        kv, state, conv = pools
        return real.decode_step_paged(
            p, toks, (kv, state, jnp.zeros_like(conv)), tables, lens)

    return dataclasses.replace(real, decode_step_paged=step)


def _kda(params, change):
    """``params`` with ``change(kda leaves) -> kda leaves`` applied to the
    KDA mixer of every stack that has one."""
    out = dict(params)
    for stack in ("dense_layers", "kda_layers"):
        out[stack] = dict(params[stack], kda=change(params[stack]["kda"]))
    return out


@pytest.mark.parametrize("broken", [
    "decay_dropped", "history_not_carried", "beta_dropped",
    "bfloat16_state", "rotary_applied"])
def test_negative_controls(share, broken):
    """The comparison of test 2 FAILS when part of the mathematics is
    dropped, or the state is kept in less than the configuration states."""
    cfg, model, params = share
    seq = tokens(21 + 52, seed=5)
    want = reference_logits(params, seq, cfg)[20:]
    kw = {}
    if broken == "decay_dropped":       # alpha = 1: exp(a_log) = 0
        params = _kda(params, lambda k: dict(
            k, a_log=jnp.full_like(k["a_log"], -1e9)))
    elif broken == "beta_dropped":      # beta = 1/2 whatever the token
        params = _kda(params, lambda k: dict(
            k, wbeta=jnp.zeros_like(k["wbeta"])))
    elif broken == "history_not_carried":
        kw["fns"] = _history_dropped(model)
    elif broken == "bfloat16_state":
        model = dataclasses.replace(model, state_dtype="bfloat16")
    elif broken == "rotary_applied":
        model = dataclasses.replace(model, qk_rotary=True)
    got = paged_decode(model, params, seq, 21, **kw)
    assert np.max(np.abs(got - want)) > 10 * TOL, broken


# -- 6. end to end through serving.Server ----------------------------------------

def test_server_serves_the_hybrid_model_and_frees_blocks_and_state(
        share, tmp_path):
    import threading

    from tensorflowonspark_tpu import serving
    from tensorflowonspark_tpu.serving.decode import scheduler
    from tensorflowonspark_tpu.utils import checkpoint as ckpt

    cfg, model, params = share
    export = ckpt.export_model(str(tmp_path / "export"), params, metadata={})
    spec = serving.DecodeSpec(model, slots=4, block_size=BS, max_tokens=12,
                              prefill_tokens=64)
    assert spec.prefix_sharing is False
    # dozens of sessions a chip are dozens of clients connecting at once
    from tensorflowonspark_tpu.serving import server as http_front
    assert http_front._HTTPServer.request_queue_size >= 128
    prompts = [tokens(n, seed=20 + n).tolist() for n in (9, 17, 30, 17, 3)]
    with serving.Server(serving.ModelSpec(export_dir=export, decode=spec),
                        num_replicas=1, request_timeout=300,
                        env={"JAX_PLATFORMS": "cpu"}) as srv:
        replies = [None] * 5
        threads = [threading.Thread(
            target=lambda i=i: replies.__setitem__(
                i, srv.generate(prompts[i], max_tokens=12, timeout=300)))
            for i in range(5)]          # five sessions, four slots
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        st = next(iter(srv.pool.stats().values()))["decode"]
    for prompt, rep in zip(prompts, replies):
        # greedy decoding by the reference: one causal pass over prompt +
        # served tokens gives every step's logits
        assert len(rep["tokens"]) == 12
        seq = np.asarray(list(prompt) + rep["tokens"], np.int32)
        rows = reference_logits(params, seq, cfg)[len(prompt) - 1:-1]
        for row, tok in zip(rows, rep["tokens"]):
            assert row[tok] >= np.max(row) - TOL    # a gap under TOL: a tie
    assert st["prefix_hits"] == 0 and st["moe"]["dropped"] == 0
    assert st["cache"]["row_bytes"] == 2 * 40 * 4
    assert st["cache"]["state_row_bytes"] == 6 * (4 * 16 * 16 + 3 * 192) * 4
    assert st["cache"]["state_bytes"] == 4 * st["cache"]["state_row_bytes"]
    assert st["cache"]["state_sessions"] == 0 == st["active"]
    # every session stepped 11 times, holding its state each time
    assert st["cache"]["state_session_steps"] == 5 * 11

    # the same engine, in process: nothing is held once all have retired
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
        assert eng._cache.occupancy == 0 and eng._cache.free_slots == 4
        assert eng._cache.blocks_in_use == 0    # no trie keeps any
        assert eng.stats()["cache"]["state_sessions"] == 0
    finally:
        eng.stop()


@pytest.mark.parametrize("asked,complaint", [
    ({"prefix_sharing": True}, "state at its end is not kept"),
    ({"draft": True}, "rejected tail cannot be taken back")])
def test_a_layout_with_state_refuses_the_trie_and_a_draft(share, asked,
                                                          complaint):
    from tensorflowonspark_tpu import serving

    _cfg, model, params = share
    kw = {}
    if asked.get("draft"):
        draft = T.Config(vocab_size=128, dim=16, n_layers=1, n_heads=2,
                         max_seq=160)
        kw = {"draft_cfg": draft,
              "draft_params": T.init(jax.random.PRNGKey(0), draft)}
    else:
        kw = dict(asked)
    with pytest.raises(ValueError, match=complaint):
        serving.DecodeSpec(model, slots=2, **kw)
    if "prefix_sharing" in asked:
        with pytest.raises(ValueError, match=complaint):
            kvcache.PagedKVCache(model, slots=2, prefix_sharing=True)
        # asked for or not, a layout of rows only keeps its trie
        rows_only = dataclasses.replace(model, linear_layers=())
        assert serving.DecodeSpec(rows_only).prefix_sharing is True
        assert serving.DecodeSpec(
            rows_only, prefix_sharing=False).prefix_sharing is False


def test_the_step_refuses_a_window_and_the_config_a_bad_layer_list(share):
    _cfg, model, params = share
    fns = model.decode_fns()
    cache = kvcache.PagedKVCache(model, slots=2, block_size=BS)
    with pytest.raises(ValueError, match="one token a slot"):
        fns.decode_step_paged(params, np.zeros((2, 4), np.int32),
                              cache.pools, cache.block_tables,
                              cache.lengths)
    with pytest.raises(ValueError, match="linear_layers names, in order"):
        dataclasses.replace(model, linear_layers=(2, 1))
    with pytest.raises(ValueError, match="linear_layers names, in order"):
        dataclasses.replace(model, linear_layers=(0, 8))
    with pytest.raises(ValueError, match="linear_layers names, in order"):
        T.Config(linear_layers=(0,), linear_heads=2, linear_head_dim=8,
                 linear_rank=8)         # not a latent block
