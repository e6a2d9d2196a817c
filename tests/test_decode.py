"""Decode-tier tests: block-paged KV cache units (refcount lint,
prefix-trie match/reclaim), sequence-length bucketing, the open-loop
load generator, seeded sampling, the CPU parity acceptance gates (paged
== full-recompute greedy; seeded-sampling replay token-identical;
speculative == non-speculative at the same seed, the draft on a paged
cache of its own), and the Server/HTTP generate surface incl.
oversized-prompt 400s.  Slow lane: a replica SIGKILLed mid-decode (sessions re-prefill
on the survivor; zero dropped and zero duplicated tokens)."""

import functools
import json
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from tensorflowonspark_tpu.serving import batcher as B
from tensorflowonspark_tpu.serving import decode as D
from tensorflowonspark_tpu.serving import replicas as R
from tensorflowonspark_tpu.serving import server as S

pytestmark = pytest.mark.decode


def _cfg(**kw):
    from tensorflowonspark_tpu.models import transformer as T
    base = dict(vocab_size=61, dim=32, n_layers=2, n_heads=2, max_seq=32,
                dtype="float32", attn_impl="reference")
    base.update(kw)
    return T.Config(**base)


def _params(cfg):
    import jax

    from tensorflowonspark_tpu.models import transformer as T
    return T.init(jax.random.PRNGKey(0), cfg)


def _each(handler):
    """An engine's ``emit`` (one list of events a hand-over) that calls
    ``handler(kind, sid, *payload)`` for every event, in order."""
    def emit(events):
        for event in events:
            handler(*event)
    return emit


def _oracle(params, prompt, cfg, **kw):
    from tensorflowonspark_tpu import ops
    from tensorflowonspark_tpu.models import transformer as T
    return T.greedy_decode_reference(
        params, prompt, cfg,
        attn_fn=functools.partial(ops.mha_reference, causal=True), **kw)


# --- sequence bucketing (satellite b) ---------------------------------------

def test_bucket_seq_pow2_and_cap():
    assert [B.bucket_seq(n, 64) for n in (1, 2, 3, 5, 9, 33, 64, 100)] == \
        [1, 2, 4, 8, 16, 64, 64, 64]
    # the cap itself is a legal bucket even when not a power of two
    assert B.bucket_seq(48, 48) == 48
    assert B.bucket_seq(49, 48) == 48
    assert B.bucket_seq(3, 48) == 4


def test_pad_seq_edge_replication_and_errors():
    a = np.array([1, 2, 3], dtype=np.int32)
    p = B.pad_seq(a, 8)
    assert p.shape == (8,) and (p[3:] == 3).all()
    assert B.pad_seq(a, 3) is a  # no-op returns the input
    m = np.arange(6, dtype=np.float32).reshape(2, 3)
    p2 = B.pad_seq(m, 5, axis=1)
    assert p2.shape == (2, 5) and (p2[:, 3:] == m[:, -1:]).all()
    with pytest.raises(ValueError):
        B.pad_seq(a, 2)  # cannot shrink
    with pytest.raises(ValueError):
        B.pad_seq(np.zeros((0,), np.int32), 4)  # nothing to replicate
    with pytest.raises(ValueError):
        B.pad_seq(m, 4, axis=2)  # no such axis


def test_batcher_seq_bucketing_groups_pads_and_ships_lengths():
    batches = []

    def dispatch(batch):
        batches.append(batch)
        batch.complete({"y": batch.inputs["tokens"]})

    with pytest.raises(ValueError):
        B.MicroBatcher(dispatch, seq_axis=0)  # seq_axis requires seq_cap
    mb = B.MicroBatcher(dispatch, max_batch=8, max_delay_ms=50,
                        queue_max=100, seq_axis=0, seq_cap=16)
    reqs = [mb.submit({"tokens": np.arange(n, dtype=np.int32)})
            for n in (3, 5, 7, 9)]
    mb.start()
    for r in reqs:
        r.result(timeout=10)
    mb.close()
    # 3 -> bucket 4 alone; 5 and 7 share bucket 8; 9 -> bucket 16
    shapes = sorted(b.inputs["tokens"].shape for b in batches)
    assert shapes == [(1, 4), (1, 16), (2, 8)]
    for b in batches:
        # true lengths ride alongside as an int32 column; padding is
        # edge-replicated so padded ids stay in-vocabulary
        lens = b.inputs["_seq_len"]
        assert lens.dtype == np.int32
        for row, n in zip(b.inputs["tokens"], lens):
            assert (row[:n] == np.arange(n)).all()
            assert (row[n:] == n - 1).all()


# --- open-loop load generator (tentpole harness) ----------------------------

def test_run_open_loop_classifies_and_aggregates():
    def request_fn(i):
        if i == 1:
            raise B.Overloaded(5, 4, retry_after=0.1)
        if i == 2:
            raise RuntimeError("boom")
        time.sleep(0.001)
        return {"ttft_ms": 5.0 + i, "token_ms": [1.0, 2.0], "tokens": 3}

    stats = D.run_open_loop(request_fn, rate_rps=500, n_requests=8,
                            seed=7, shed_exc=B.Overloaded)
    assert stats["requests"] == 8
    assert stats["completed"] == 6
    assert stats["shed"] == 1 and stats["errors"] == 1
    assert stats["tokens"] == 18 and stats["tokens_per_sec"] > 0
    assert stats["latency_p50_ms"] > 0
    assert stats["latency_p99_ms"] >= stats["latency_p50_ms"]
    assert stats["ttft_p50_ms"] >= 5.0
    assert stats["tok_p50_ms"] in (1.0, 2.0)
    assert stats["offered_rps"] == 500
    # seeded arrivals: the same seed replays the same schedule
    again = D.run_open_loop(request_fn, rate_rps=500, n_requests=8,
                            seed=7, shed_exc=B.Overloaded)
    assert again["completed"] == 6 and again["shed"] == 1


# --- KV cache units ---------------------------------------------------------

def test_paged_kvcache_lifecycle_refcounts_and_prefix_match():
    from tensorflowonspark_tpu.serving.decode import kvcache
    cfg = _cfg()
    cache = kvcache.PagedKVCache(cfg, slots=2, block_size=4)
    hd = cfg.dim // cfg.n_heads
    assert cache.k.shape == (cache.num_blocks, cfg.n_layers, cfg.n_heads,
                             4, hd)
    assert cache.blocks_per_slot == 8  # ceil(32 / 4)
    assert cache.blocks_in_use == 0 and cache.leaked_blocks() == []

    prompt = list(range(1, 11))  # 10 tokens -> 2 whole blocks + tail
    assert cache.match_prefix(prompt) == ([], 0)  # cold trie
    slot = cache.alloc()
    own = cache.alloc_blocks(3)
    assert 0 not in own  # the sentinel is never handed out
    cache.map_session(slot, [], own, 10)
    k = np.zeros((cfg.n_layers, cfg.n_heads, 10, hd), np.float32)
    cache.insert_tail(slot, k, k, 0, 10)
    cache.register_prompt(slot, prompt)
    assert cache.blocks_in_use == 3 and cache.leaked_blocks() == []

    # a follower matches whole blocks only, capped one token short of
    # the full prompt so admission always has a real tail to prefill
    shared, mtoks = cache.match_prefix(prompt)
    assert mtoks == 8 and shared == own[:2]
    slot2 = cache.alloc()
    own2 = cache.alloc_blocks(1)
    cache.map_session(slot2, shared, own2, 10)
    assert cache.blocks_in_use == 4  # leader's 3 + follower's tail block
    assert cache.leaked_blocks() == []

    # tail writes must start block-aligned (copy-on-write contract)
    with pytest.raises(ValueError):
        cache.insert_tail(slot2, k, k, 9, 1)

    # retiring both sessions keeps the registered prefix trie-resident
    cache.retire(slot)
    cache.retire(slot2)
    assert cache.occupancy == 0
    assert cache.blocks_in_use == 2  # the two whole-prefix blocks
    assert cache.leaked_blocks() == []
    assert cache.match_prefix(prompt)[1] == 8  # still a hit


def test_paged_kvcache_trie_reclaim_lru_and_oom():
    from tensorflowonspark_tpu.serving.decode import kvcache
    cfg = _cfg()
    with pytest.raises(ValueError):
        # below sentinel + slots*blocks_per_slot: live sessions starve
        kvcache.PagedKVCache(cfg, slots=1, block_size=4, num_blocks=8)
    cache = kvcache.PagedKVCache(cfg, slots=1, block_size=4, num_blocks=9)
    prompt = list(range(1, 11))
    slot = cache.alloc()
    cache.map_session(slot, [], cache.alloc_blocks(3), 10)
    cache.register_prompt(slot, prompt)
    cache.retire(slot)
    assert cache.blocks_in_use == 2  # trie-only now

    # one block over the free list: the LRU *leaf* is evicted, the
    # parent (shorter prefix) stays matchable
    got = cache.alloc_blocks(7)
    assert cache.match_prefix(prompt)[1] == 4
    # live references hold everything else: reclaim can't satisfy this
    with pytest.raises(kvcache.CacheOOM):
        cache.alloc_blocks(2)
    # ... but the attempt drained the remaining (fully freed) trie path
    assert cache.match_prefix(prompt) == ([], 0)
    for b in got:
        cache._release(b)
    assert cache.blocks_in_use == 0 and cache.leaked_blocks() == []


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_trie_reclaim_releases_what_a_walk_per_block_would(seed):
    """``PrefixTrie.reclaim``'s contract, restated here one block at a
    time: the least recently touched leaf whose block only the trie
    holds, parents exposed as their last child goes, blocks a session
    still holds never.  Today's ``reclaim`` IS that walk per block (a
    third of a second per admission into a pool of 8,704 blocks:
    PERF.md section 7); whatever replaces it must release the same
    blocks in the same order."""
    import random

    from tensorflowonspark_tpu.serving.decode import kvcache

    def plain(trie, need, refcount, release):
        freed = 0
        while freed < need:
            best, stack = None, [trie.root]
            while stack:
                children = stack.pop()
                for key, node in children.items():
                    if node.children:
                        stack.append(node.children)
                    elif refcount[node.block] == 1 and (
                            best is None or node.tick < best[0]):
                        best = (node.tick, children, key, node)
            if best is None:
                break
            del best[1][best[2]]
            release(best[3].block)
            freed += 1
        return freed

    def build():
        rng = random.Random(seed)
        trie, refcount, block = kvcache.PrefixTrie(2), {}, 0
        stems = [[rng.randrange(4) for _ in range(8)] for _ in range(3)]
        for _ in range(12):
            stem = rng.choice(stems)[:2 * rng.randrange(1, 5)]
            tokens = stem + [rng.randrange(4) for _ in range(
                2 * rng.randrange(0, 4))]
            blocks = list(range(block, block + len(tokens) // 2))
            block += len(blocks)
            trie.insert(tokens, blocks,
                        lambda b: refcount.__setitem__(b, 1))
            trie.match(rng.choice(stems))
        for b in rng.sample(sorted(refcount), len(refcount) // 5):
            refcount[b] += 1            # a session holds these
        return trie, refcount

    for need in (1, 3, 7, 1000):
        released = []
        for reclaim in (lambda t, *a: t.reclaim(*a), plain):
            trie, refcount = build()
            order = []

            def release(b):
                refcount[b] -= 1
                order.append(b)
            got = reclaim(trie, need, refcount, release)
            assert got == len(order) <= need
            released.append(order)
        assert released[0] == released[1]
        assert released[0]      # something was evictable


def _walk_reclaim(trie, need, refcount, release):
    """The eviction order, found by one walk of the trie per block
    released: the oracle of the tests below (the pinned test above
    keeps its own copy)."""
    freed = 0
    while freed < need:
        best, stack = None, [trie.root]
        while stack:
            children = stack.pop()
            for key, node in children.items():
                if node.children:
                    stack.append(node.children)
                elif refcount[node.block] == 1 and (
                        best is None or node.tick < best[0]):
                    best = (node.tick, children, key, node)
        if best is None:
            break
        del best[1][best[2]]
        trie.nodes -= 1
        release(best[3].block)
        freed += 1
    return freed


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_paged_kvcache_long_run_evicts_as_a_walk_per_block_would(seed):
    """A few hundred sessions through a pool that is exactly its live
    set, driven the way the engine drives it, beside a cache whose trie
    evicts by the plain walk per block: the same physical blocks at
    every allocation (so the same eviction order), no block leaked, and
    ``nodes`` is what a walk counts."""
    import random

    from tensorflowonspark_tpu.serving.decode import kvcache

    class WalkTrie(kvcache.PrefixTrie):
        reclaim = _walk_reclaim

    cfg = _cfg()
    slots, bs = 4, 4
    caches = [kvcache.PagedKVCache(cfg, slots, block_size=bs,
                                   num_blocks=1 + slots * 8)
              for _ in range(2)]
    caches[1].trie = WalkTrie(bs)
    rng = random.Random(seed)
    stems = [[rng.randrange(3) for _ in range(16)] for _ in range(4)]
    live = {}           # slot -> [length, tokens still to decode]
    admitted = evicting = 0
    while admitted < 300 or live:
        if admitted < 300 and len(live) < slots and rng.random() < 0.6:
            prompt = rng.choice(stems)[:bs * rng.randrange(0, 5)] + [
                rng.randrange(3) for _ in range(rng.randrange(1, 12))]
            got = []
            for cache in caches:
                shared, mlen = cache.match_prefix(prompt)
                slot = cache.alloc()
                evicting += -(-(len(prompt) - mlen) // bs) \
                    > len(cache._free_blocks)
                own = cache.alloc_blocks(-(-(len(prompt) - mlen) // bs))
                cache.map_session(slot, shared, own, len(prompt))
                cache.register_prompt(slot, prompt)
                got.append((slot, shared, own))
            assert got[0] == got[1]
            live[got[0][0]] = [len(prompt), rng.randrange(1, 32 - len(prompt))]
            admitted += 1
        for slot in sorted(live):
            length, left = live[slot]
            for cache in caches:
                cache.ensure_capacity(slot, length + 1)
                cache.lengths[slot] += 1
            if left == 1:
                for cache in caches:
                    cache.retire(slot)
                del live[slot]
            else:
                live[slot] = [length + 1, left - 1]
        assert np.array_equal(caches[0].block_tables, caches[1].block_tables)
    assert evicting > 100         # the free list was short most times
    for cache in caches:
        assert cache.leaked_blocks() == []
        assert cache.trie.nodes == sum(1 for _ in cache.trie.walk())
    assert caches[0].trie.nodes == caches[1].trie.nodes
    assert caches[0]._free_blocks == caches[1]._free_blocks
    assert caches[0].trie.blocks_reclaimed > 300


class _CountingRefs(dict):
    reads = 0

    def __getitem__(self, key):
        self.reads += 1
        return dict.__getitem__(self, key)


def test_trie_reclaim_reads_no_more_refcounts_than_it_releases():
    """The cost of ``reclaim`` without a clock: it looks at a block's
    reference count once per leaf it pops, so releasing 64 blocks of a
    trie of 4,000 nodes reads 64 counts and one per leaf a session
    holds, however large the trie (a walk per block reads every leaf's,
    64 times)."""
    from tensorflowonspark_tpu.serving.decode import kvcache

    trie, refs, slots = kvcache.PrefixTrie(1), _CountingRefs(), 16
    for p in range(500):
        trie.insert([p] + list(range(7)), range(8 * p, 8 * p + 8),
                    lambda b: refs.__setitem__(b, 1))
    assert trie.nodes == 4000
    for p in range(slots):          # the oldest leaves: all popped first
        refs[8 * p + 7] += 1
    order, refs.reads = [], 0
    assert trie.reclaim(64, refs, order.append) == 64
    assert refs.reads <= 64 + slots + 2
    # the first path no session holds, from its leaf up, and so on
    assert order[:9] == [8 * slots + i for i in range(7, -1, -1)] \
        + [8 * slots + 15]
    walked = _CountingRefs(refs)
    for b in order:
        walked[b] -= 1              # as a cache's ``release`` would
    assert _walk_reclaim(trie, 1, walked, order.append) == 1
    assert walked.reads > 400       # one walk: every leaf left


def test_trie_heap_stays_within_its_multiple_of_nodes():
    """Entries gone stale (a leaf matched again, grown or evicted) are
    dropped by a rebuild once they outnumber the nodes: a server that
    runs for days holds a heap no larger than ``HEAP_SLACK * nodes +
    HEAP_SLACK_MIN``."""
    import random

    from tensorflowonspark_tpu.serving.decode import kvcache

    rng = random.Random(7)
    trie, refs = kvcache.PrefixTrie(2), {}
    free, prompts, rebuilds = list(range(400)), [], 0

    def release(b):
        del refs[b]
        free.append(b)

    def incref(b):
        refs[b] = 1
        free.remove(b)
    for _ in range(10000):
        what = rng.random()
        if what < 0.97 and prompts:
            before = len(trie._heap)    # a match pushes: fewer = rebuilt
            trie.match(rng.choice(prompts))
            rebuilds += len(trie._heap) < before
        elif what < 0.995:
            tokens = [rng.randrange(3)
                      for _ in range(2 * rng.randrange(1, 9))]
            if len(free) < 8:
                trie.reclaim(8 - len(free), refs, release)
            trie.insert(tokens, free[-8:], incref)
            prompts = prompts[-63:] + [tokens]
        else:
            trie.reclaim(rng.randrange(1, 20), refs, release)
        assert len(trie._heap) <= (trie.HEAP_SLACK * trie.nodes
                                   + trie.HEAP_SLACK_MIN)
    assert rebuilds >= 5                # the bound was met, and held
    assert trie.nodes == sum(1 for _ in trie.walk()) == len(refs) > 100


def test_alloc_blocks_span_only_when_the_free_list_is_short(monkeypatch):
    from tensorflowonspark_tpu.serving.decode import kvcache

    spans = []

    class Span:
        def __init__(self, name, **attrs):
            spans.append((name, attrs))
            self.attrs = attrs

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

        def add(self, **attrs):
            self.attrs.update(attrs)

    monkeypatch.setattr(kvcache.telemetry, "span", Span)
    cache = kvcache.PagedKVCache(_cfg(), slots=1, block_size=4, num_blocks=9)
    slot = cache.alloc()
    cache.map_session(slot, [], cache.alloc_blocks(3), 10)
    cache.register_prompt(slot, list(range(1, 11)))
    cache.retire(slot)
    got = cache.alloc_blocks(6)             # exactly the free list
    assert spans == [] and cache.trie.reclaim_calls == 0
    got += cache.alloc_blocks(1)            # one short: the leaf goes
    assert spans == [("tfos/decode/alloc_blocks",
                      {"blocks": 1, "reclaimed": 1})]
    with pytest.raises(kvcache.CacheOOM):
        cache.alloc_blocks(2)               # one more is all there is
    assert spans[1:] == [("tfos/decode/alloc_blocks",
                          {"blocks": 2, "reclaimed": 1})]
    assert (cache.trie.reclaim_calls, cache.trie.blocks_reclaimed,
            cache.trie.nodes) == (2, 2, 0)
    assert cache.trie.reclaim_s > 0


def test_engine_stats_count_the_trie_and_what_it_gave_back():
    """``stats()["cache"]`` carries ``trie_nodes`` and, as totals since
    the engine started that only grow, ``reclaim_calls``,
    ``blocks_reclaimed`` and ``reclaim_s``."""
    cfg = _cfg()
    params = _params(cfg)
    # the pool is the live set: retired prompts' blocks have to go
    spec = D.DecodeSpec(cfg, slots=2, max_tokens=4, block_size=4,
                        num_blocks=1 + 2 * 8)
    done = []
    eng = D.DecodeEngine(
        params, spec,
        _each(lambda kind, sid, *rest: kind in ("done", "error")
              and done.append((kind, sid))))
    eng.start(timeout=300)
    snaps = []
    try:
        for rnd in range(2):
            for i in range(6):
                eng.submit((rnd, i), [1 + rnd, 2 + i] + list(range(3, 20)))
            deadline = time.time() + 300
            while len(done) < 6 * (rnd + 1) and time.time() < deadline:
                time.sleep(0.01)
            snaps.append(eng.stats()["cache"])
    finally:
        eng.stop()
    assert [kind for kind, _sid in done] == ["done"] * 12
    totals = ("reclaim_calls", "blocks_reclaimed", "reclaim_s")
    for snap in snaps:
        assert {"trie_nodes", *totals} <= set(snap)
    assert snaps[0]["blocks_reclaimed"] > 0 and snaps[0]["trie_nodes"] > 0
    assert all(snaps[1][k] > snaps[0][k] for k in totals)
    assert snaps[1]["blocks_reclaimed"] >= snaps[1]["reclaim_calls"]


def test_sampling_make_validation_and_pure_function():
    from tensorflowonspark_tpu.serving.decode import sampling
    assert sampling.make() is None
    assert sampling.make(temperature=0.0, top_k=5, seed=3) is None  # greedy
    for bad in (dict(temperature=-0.5), dict(temperature=1.0, top_k=0),
                dict(temperature=1.0, top_p=0.0),
                dict(temperature=1.0, top_p=1.5)):
        with pytest.raises(ValueError):
            sampling.make(**bad)
    logits = np.random.default_rng(0).normal(size=61)
    assert sampling.sample_token(logits, None, 4) == int(np.argmax(logits))
    p = sampling.make(temperature=0.8, top_k=12, top_p=0.9, seed=42)
    a = [sampling.sample_token(logits, p, i) for i in range(16)]
    b = [sampling.sample_token(logits, p, i) for i in range(16)]
    assert a == b  # pure in (logits, params, index): replayable
    p2 = sampling.make(temperature=0.8, top_k=12, top_p=0.9, seed=43)
    assert [sampling.sample_token(logits, p2, i) for i in range(16)] != a


def test_engine_submit_rejects_bad_prompts_via_emit():
    events = []
    cfg = _cfg()
    eng = D.DecodeEngine(params=None, spec=D.DecodeSpec(cfg, slots=2),
                         emit=events.extend)
    eng.submit("s-empty", [])
    eng.submit("s-long", list(range(cfg.max_seq)))
    kinds = [(k, sid) for k, sid, *_ in events]
    assert ("error", "s-empty") in kinds and ("error", "s-long") in kinds


# --- THE acceptance gate: token-identical continuous batching ---------------

def test_parity_staggered_mixed_length_token_identical():
    """Seeded multi-request trace with staggered arrivals and mixed
    prompt lengths; every session's streamed tokens must be
    token-identical to a full-recompute greedy decode of the same
    prompt, with each token index emitted exactly once."""
    cfg = _cfg()
    params = _params(cfg)
    rng = np.random.default_rng(3)
    prompts = {f"s{i}": rng.integers(0, cfg.vocab_size, size=n).tolist()
               for i, n in enumerate((5, 3, 9, 12))}

    events = {sid: {"tokens": [], "done": None, "error": None}
              for sid in prompts}
    lock = threading.Lock()

    def emit(kind, sid, *rest):
        with lock:
            if kind == "token":
                events[sid]["tokens"].append(rest)  # (index, token)
            elif kind == "done":
                events[sid]["done"] = rest[0]
            else:
                events[sid]["error"] = rest[0]

    eng = D.DecodeEngine(_params(cfg), D.DecodeSpec(cfg, slots=2,
                                                    max_tokens=6),
                         _each(emit))
    eng.start(timeout=300)
    try:
        # staggered admission: s0 decodes alone first, then the rest
        # arrive mid-flight (slots=2 also forces queueing)
        eng.submit("s0", prompts["s0"])
        deadline = time.time() + 300
        while not events["s0"]["tokens"] and time.time() < deadline:
            time.sleep(0.01)
        assert events["s0"]["tokens"], "no first token within deadline"
        for sid in ("s1", "s2", "s3"):
            eng.submit(sid, prompts[sid])
        while (any(e["done"] is None and e["error"] is None
                   for e in events.values())
               and time.time() < deadline):
            time.sleep(0.01)
    finally:
        eng.stop()

    for sid, prompt in prompts.items():
        ev = events[sid]
        assert ev["error"] is None, (sid, ev["error"])
        ref = _oracle(params, prompt, cfg, max_tokens=6)
        assert ev["done"] == ref, (sid, ev["done"], ref)
        # streamed (index, token) pairs: exactly once per index, in order
        idxs = [i for i, _ in ev["tokens"]]
        assert idxs == list(range(len(ref))), (sid, idxs)
        assert [t for _, t in ev["tokens"]] == ref, sid


def test_parity_eos_stops_early():
    cfg = _cfg()
    params = _params(cfg)
    prompt = [7, 11, 13, 17, 19]
    free_run = _oracle(params, prompt, cfg, max_tokens=8)
    eos = free_run[2]  # a token the free run provably emits
    ref = _oracle(params, prompt, cfg, max_tokens=8, eos_id=eos)
    # decode stops at (and includes) the FIRST occurrence of eos
    assert ref == free_run[:free_run.index(eos) + 1]

    events = {}
    eng = D.DecodeEngine(params, D.DecodeSpec(cfg, slots=2, max_tokens=8),
                         _each(lambda kind, sid, *rest: events.setdefault(
                             kind, []).append(rest)))
    eng.start(timeout=300)
    try:
        eng.submit("s", prompt, eos_id=eos)
        deadline = time.time() + 300
        while "done" not in events and "error" not in events and \
                time.time() < deadline:
            time.sleep(0.01)
    finally:
        eng.stop()
    assert "error" not in events, events
    assert events["done"][0][0] == ref


def _run_sessions(params, spec, jobs, timeout=300):
    """Drive a DecodeEngine over ``jobs`` = [(sid, prompt, submit_kw)];
    returns ({sid: tokens}, stats, engine) — stats captured before
    stop, the (stopped) engine returned for cache introspection."""
    events = {sid: {"done": None, "error": None} for sid, _, _ in jobs}
    lock = threading.Lock()

    def emit(kind, sid, *rest):
        with lock:
            if kind == "done":
                events[sid]["done"] = rest[0]
            elif kind == "error":
                events[sid]["error"] = rest[0]

    eng = D.DecodeEngine(params, spec, _each(emit))
    eng.start(timeout=timeout)
    try:
        for sid, prompt, kw in jobs:
            eng.submit(sid, prompt, **kw)
        deadline = time.time() + timeout
        while (any(e["done"] is None and e["error"] is None
                   for e in events.values()) and time.time() < deadline):
            time.sleep(0.01)
        stats = eng.stats()
    finally:
        eng.stop()
    for sid, ev in events.items():
        assert ev["error"] is None, (sid, ev["error"])
        assert ev["done"] is not None, (sid, "timed out")
    return {sid: ev["done"] for sid, ev in events.items()}, stats, eng


def test_engine_counts_tokens_and_where_its_time_went():
    """``stats()`` always carries ``tokens`` (emitted by decode
    iterations: every token but each session's first, which its prefill
    produced), ``prompt_tokens``, and ``phase_s``, whose phases are
    chained clock reads and so sum to the engine thread's wall time."""
    cfg = _cfg()
    params = _params(cfg)
    spec = D.DecodeSpec(cfg, slots=4, max_tokens=8)
    jobs = [(i, [2 + i, 3, 5, 7][: 2 + i % 3], {"max_tokens": 4 + i})
            for i in range(6)]
    t0 = time.perf_counter()
    out, stats, eng = _run_sessions(params, spec, jobs)
    wall = time.perf_counter() - t0
    emitted = sum(len(toks) for toks in out.values())
    assert emitted == sum(4 + i for i in range(6))
    assert stats["tokens"] == emitted - len(jobs)
    assert stats["prompt_tokens"] == sum(len(p) for _s, p, _k in jobs)
    phases = stats["phase_s"]
    assert set(phases) == {"idle", "admit", "step", "fetch", "host"}
    assert all(v >= 0 for v in phases.values()) and phases["admit"] > 0
    # the engine thread's own wall time: from its first mark to its
    # last (the stats were taken while it ran; it stopped since)
    lived = eng._t_mark - eng._t_started
    assert lived <= wall + 1.0
    assert sum(eng._phase_s.values()) == pytest.approx(lived, rel=0.05)
    # the snapshot was within one 20 ms idle wait of the final tally
    assert sum(phases.values()) == pytest.approx(
        sum(eng._phase_s.values()), abs=0.5)


def test_parity_paged_equals_oracle_with_prefix_hits():
    """Gate (a): block-paged greedy decode — including trie-matched
    admissions that skip the shared prefill — is token-identical to a
    full-recompute greedy decode; the engine's paged cache leaks zero
    block references afterwards."""
    cfg = _cfg()
    params = _params(cfg)
    rng = np.random.default_rng(5)
    system = rng.integers(1, cfg.vocab_size, size=8).tolist()
    prompts = {"lead": system + [3, 5],
               "follow": system + [7, 11, 13],
               "cold": rng.integers(1, cfg.vocab_size, size=6).tolist()}
    jobs = [(sid, p, {}) for sid, p in prompts.items()]
    # slots=1 serializes admission, so "follow" provably arrives after
    # "lead" registered the shared prefix -> a guaranteed trie hit
    paged, pstats, eng = _run_sessions(
        params, D.DecodeSpec(cfg, slots=1, max_tokens=6, block_size=4),
        jobs)
    for sid, p in prompts.items():
        ref = _oracle(params, p, cfg, max_tokens=6)
        assert paged[sid] == ref, (sid, paged[sid], ref)
    assert pstats["prefix_hits"] >= 1
    assert pstats["prefix_tokens_saved"] >= 8  # the whole system prompt
    # refcount lint: every retired session returned its blocks; only
    # trie-resident prefixes (reusable capacity) remain accounted
    cache = eng._cache
    assert cache.occupancy == 0
    assert cache.leaked_blocks() == []


def test_parity_seeded_sampling_replay_token_identical():
    """Gate (b): a seeded-sampled session replayed from scratch (what
    failover does after re-prefill) emits the identical token stream;
    a different seed provably diverges."""
    from tensorflowonspark_tpu.serving.decode import sampling
    cfg = _cfg()
    params = _params(cfg)
    prompt = [2, 3, 5, 7, 11]
    sp = sampling.make(temperature=0.8, top_k=12, seed=99)
    first, _, _ = _run_sessions(
        params, D.DecodeSpec(cfg, slots=2, max_tokens=8, block_size=4),
        [("r1", prompt, {"sampling": sp})])
    replay, _, _ = _run_sessions(
        params, D.DecodeSpec(cfg, slots=2, max_tokens=8, block_size=4),
        [("r2", prompt, {"sampling": sp})])
    assert first["r1"] == replay["r2"]
    other, _, _ = _run_sessions(
        params, D.DecodeSpec(cfg, slots=2, max_tokens=8, block_size=4),
        [("r3", prompt,
          {"sampling": sampling.make(temperature=0.8, top_k=12,
                                     seed=100)})])
    assert other["r3"] != first["r1"]


def _draft():
    """A draft smaller than ``_cfg()``'s model, same vocabulary and
    ``max_seq``."""
    import jax

    from tensorflowonspark_tpu.models import transformer as T
    dcfg = _cfg(dim=16, n_layers=1)
    return dcfg, T.init(jax.random.PRNGKey(7), dcfg)


def _both_caches_clean(eng):
    """Every session retired: neither cache holds a slot or fails the
    refcount lint, and the draft's pool (no trie to keep a block for)
    is empty."""
    for cache in (eng._cache, eng._dcache):
        assert cache.occupancy == 0
        assert cache.leaked_blocks() == []
    assert eng._dcache.trie is None and eng._dcache.blocks_in_use == 0


def test_parity_speculative_equals_plain_same_seed():
    """Gate (c): speculative decoding (draft proposes, target verifies
    in one windowed step) returns token-identical output to the
    non-speculative engine for greedy AND seeded-sampled sessions; a
    draft that IS the target is always accepted (the speedup path).
    Either way no block of either cache is lost."""
    from tensorflowonspark_tpu.serving.decode import sampling

    cfg = _cfg()
    params = _params(cfg)
    dcfg, dparams = _draft()
    sp = sampling.make(temperature=0.9, top_k=16, seed=7)
    jobs = [("g", [3, 5, 7, 9, 11], {}),
            ("s", [4, 6, 8, 10], {"sampling": sp})]
    plain, _, _ = _run_sessions(
        params, D.DecodeSpec(cfg, slots=2, max_tokens=7, block_size=4),
        jobs)
    assert plain["g"] == _oracle(params, [3, 5, 7, 9, 11], cfg,
                                 max_tokens=7)
    specd, st, eng = _run_sessions(
        params, D.DecodeSpec(cfg, slots=2, max_tokens=7, block_size=4,
                             draft_params=dparams, draft_cfg=dcfg,
                             spec_window=3), jobs)
    assert specd == plain
    assert st["spec_proposed"] > 0
    _both_caches_clean(eng)
    # perfect draft (the target itself): every proposal accepted, output
    # still identical — multiple tokens really do land per fused step
    perfect, pt, eng = _run_sessions(
        params, D.DecodeSpec(cfg, slots=2, max_tokens=7, block_size=4,
                             draft_params=params, draft_cfg=cfg,
                             spec_window=3), jobs)
    assert perfect == plain
    assert pt["spec_accepted"] == pt["spec_proposed"] > 0
    _both_caches_clean(eng)
    with pytest.raises(ValueError):
        D.DecodeSpec(cfg, draft_params=dparams, draft_cfg=None)
    with pytest.raises(TypeError):
        D.DecodeSpec(cfg, paged=False)  # one cache: nothing to choose


def test_speculative_sessions_give_back_every_block_of_both_caches():
    """Sessions of different lengths served speculatively — one retired
    by its first token, one by eos, more sessions than slots — leave the
    target's cache and the draft's without a slot held, a block leaked
    or a refcount adrift, blocks grown for rejected windows included."""
    cfg = _cfg()
    params = _params(cfg)
    dcfg, dparams = _draft()
    rng = np.random.default_rng(11)
    sizes = ((3, 9), (10, 4), (17, 12), (5, 1), (8, 20), (13, 6))
    jobs = [(i, rng.integers(1, cfg.vocab_size, size=n).tolist(),
             {"max_tokens": m}) for i, (n, m) in enumerate(sizes)]
    eos = _oracle(params, jobs[0][1], cfg, max_tokens=9)[4]
    jobs[0][2]["eos_id"] = eos
    out, st, eng = _run_sessions(
        params, D.DecodeSpec(cfg, slots=2, block_size=4,
                             draft_params=dparams, draft_cfg=dcfg,
                             spec_window=3), jobs)
    assert out[0] == _oracle(params, jobs[0][1], cfg, max_tokens=9,
                             eos_id=eos)
    assert [len(out[i]) for i in range(1, 6)] == [4, 12, 1, 20, 6]
    assert st["retired"] == 6 and st["spec_proposed"] > 0
    _both_caches_clean(eng)


def test_speculative_window_past_max_seq_spills_into_the_sentinels():
    """A speculative session runs into ``max_seq``: its last window
    overruns the last block, in the target's cache and in the draft's.
    Both route the overflow to their sentinel block, so the session
    reads as the plain engine's and its neighbour as if it had been
    alone — with a small draft and with the target as its own draft."""
    cfg = _cfg()
    params = _params(cfg)
    rng = np.random.default_rng(13)
    edge = rng.integers(1, cfg.vocab_size, size=cfg.max_seq - 5).tolist()
    near = rng.integers(1, cfg.vocab_size, size=6).tolist()
    # the cap is max_seq - len(prompt) = 5 tokens: windows of 3 at 27, 30
    jobs = [("edge", edge, {"max_tokens": 20}),
            ("near", near, {"max_tokens": 12})]
    plain, _, _ = _run_sessions(
        params, D.DecodeSpec(cfg, slots=2, block_size=4), jobs)
    alone, _, _ = _run_sessions(
        params, D.DecodeSpec(cfg, slots=2, block_size=4), jobs[1:])
    assert len(plain["edge"]) == 5 and plain["near"] == alone["near"]
    for draft_cfg, draft_params in (_draft(), (cfg, params)):
        specd, st, eng = _run_sessions(
            params, D.DecodeSpec(cfg, slots=2, block_size=4,
                                 draft_params=draft_params,
                                 draft_cfg=draft_cfg, spec_window=3), jobs)
        assert specd == plain
        assert st["spec_proposed"] > 0
        _both_caches_clean(eng)


def test_speculative_engine_rebuilds_both_caches_after_a_failure(
        monkeypatch):
    """An injected failure between two windows of a live speculative
    session (site ``decode.step``) fails the session, and the engine
    goes on with a new cache AND a new draft cache: the next session is
    served, token for token the plain engine's."""
    from tensorflowonspark_tpu.utils import faults

    cfg = _cfg()
    params = _params(cfg)
    dcfg, dparams = _draft()
    prompt = [3, 5, 7, 9, 11]
    plain, _, _ = _run_sessions(
        params, D.DecodeSpec(cfg, slots=2, max_tokens=7, block_size=4),
        [("next", prompt, {})])
    events = {"victim": [], "next": []}

    def emit(kind, sid, *rest):
        events[sid].append((kind,) + rest)
        if (kind, sid, rest[:1]) == ("token", "victim", (2,)):
            # on the engine's thread, between two of its checks: the next
            # one fires, with this session three tokens into twenty
            monkeypatch.setenv(faults.PLAN_ENV, "decode.step:exc@1")

    def wait_for(sid):
        deadline = time.time() + 300
        while time.time() < deadline:
            if events[sid] and events[sid][-1][0] in ("done", "error"):
                return events[sid][-1]
            time.sleep(0.01)
        raise AssertionError(f"session {sid!r} timed out")

    eng = D.DecodeEngine(
        params, D.DecodeSpec(cfg, slots=2, max_tokens=7, block_size=4,
                             draft_params=dparams, draft_cfg=dcfg,
                             spec_window=3), _each(emit))
    eng.start(timeout=300)
    first = eng._cache, eng._dcache
    try:
        eng.submit("victim", [2, 4, 6, 8], max_tokens=20)
        last = wait_for("victim")
        assert last[0] == "error" and "injected fault" in last[1]
        eng.submit("next", prompt)
        assert wait_for("next")[:2] == ("done", plain["next"])
    finally:
        eng.stop()
        monkeypatch.delenv(faults.PLAN_ENV, raising=False)
        faults._reset_for_tests()
    # the caches the victim's blocks were in are gone, both of them
    assert eng._cache is not first[0] and eng._dcache is not first[1]
    _both_caches_clean(eng)


# --- resident weights: the engine holds what the step multiplies ------------

_GAINS = {"ln1", "ln2", "ln_f", "kv_norm", "q_norm", "router_bias"}


def _mixed_cfg(block, **kw):
    """float32 weights multiplied in bfloat16 (``param_dtype`` and
    ``dtype`` apart), either block."""
    if block == "classic":
        return _cfg(**dict({"dtype": "bfloat16"}, **kw))
    from tensorflowonspark_tpu.models import transformer as T
    base = dict(
        vocab_size=61, dim=32, n_layers=3, n_heads=2, max_seq=32,
        dtype="bfloat16", attn_impl="reference", attn_kind="latent",
        kv_lora_rank=16, qk_nope_dim=8, qk_rope_dim=4, v_head_dim=8,
        qk_norm=True, ffn_kind="swiglu", ffn_dim=48, n_dense_layers=1,
        n_experts=8, n_experts_held=4, experts_per_token=2, expert_dim=24,
        n_shared_experts=1, routed_scale=2.5)
    base.update(kw)
    return T.Config(**base)


def _host(tree):
    """A tree as the replica hands it over: numpy, on the host (copies:
    ``np.asarray`` of a CPU device array is a view that keeps it alive)."""
    import jax
    return jax.tree_util.tree_map(np.array, tree)


def _leaf_names(tree):
    import jax
    return [(path[-1].key, leaf) for path, leaf
            in jax.tree_util.tree_flatten_with_path(tree)[0]]


def _cpu_slots(cfg):
    """The CPU backend has no bfloat16 product with a batch axis wider
    than one (``DotThunk``: BF16 x BF16 = F32 unimplemented), which the
    latent block's absorbed attention is per slot: one slot there."""
    return 2 if cfg.classic else 1


def _serving_programs(cfg, params):
    """``{program: every output}`` of the three serving programs on
    ``params``: a prefill, its rows into a paged cache, a tail over eight
    cached tokens, one step of every slot."""
    import jax

    from tensorflowonspark_tpu.serving.decode import kvcache

    fns = cfg.decode_fns()
    n = _cpu_slots(cfg)
    cache = kvcache.PagedKVCache(cfg, slots=n, block_size=4,
                                 prefix_sharing=False)
    rng = np.random.default_rng(17)
    toks = rng.integers(1, cfg.vocab_size, (n, 8)).astype(np.int32)
    lens = np.asarray([8, 6][:n], np.int32)
    out = {"prefill": jax.jit(fns.prefill)(params, toks, lens)}
    rows = out["prefill"][1]
    for slot in range(n):
        assert cache.alloc() == slot
        cache.map_session(slot, [], cache.alloc_blocks(2), int(lens[slot]))
        cache.insert_tail(slot, *rows, 0, int(lens[slot]), row=slot)
    out["prefill_extend"] = jax.jit(fns.prefill_extend)(
        params, toks[:1, :4], cache.pools, cache.block_tables[:1, :2],
        np.asarray([8], np.int32), np.asarray([3], np.int32))
    for slot in range(n):
        cache.ensure_capacity(slot, int(lens[slot]) + 1)
    out["decode_step_paged"] = jax.jit(fns.decode_step_paged)(
        params, toks[:, -1:], cache.pools, cache.block_tables,
        cache.lengths.copy())
    return {name: jax.tree_util.tree_leaves(o) for name, o in out.items()}


@functools.lru_cache(maxsize=None)
def _programs_on_both_trees(block):
    cfg = _mixed_cfg(block)
    params = _params(cfg)
    return (_serving_programs(cfg, params),
            _serving_programs(cfg, cfg.decode_fns().resident(_host(params))))


@pytest.mark.parametrize("program", ["prefill", "prefill_extend",
                                     "decode_step_paged"])
@pytest.mark.parametrize("block", ["classic", "latent"])
def test_resident_tree_gives_the_float32_trees_bits(block, program):
    """Casting the weights once changes no bit of a program's logits,
    cache rows, pools or counters: the matrices entered every product in
    the compute type before, and still do."""
    wide, held = _programs_on_both_trees(block)
    assert len(wide[program]) == len(held[program]) >= 2
    for a, b in zip(wide[program], held[program]):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


@pytest.mark.parametrize("block", ["classic", "latent"])
def test_resident_casts_the_matrices_and_keeps_the_gains(block):
    """Every leaf the programs use only through a cast has the compute
    type; norm gains and the router's bias (multiplied and added in
    float32) keep float32."""
    import jax.numpy as jnp

    cfg = _mixed_cfg(block)
    held = _leaf_names(cfg.decode_fns().resident(_host(_params(cfg))))
    gains = {name for name, _ in held} & _GAINS
    assert gains == ({"ln1", "ln2", "ln_f"} if block == "classic"
                     else _GAINS)
    for name, leaf in held:
        want = jnp.float32 if name in _GAINS else jnp.bfloat16
        assert leaf.dtype == want, (name, leaf.dtype)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("block", ["classic", "latent"])
def test_resident_hands_back_a_leaf_that_has_the_type(block, dtype):
    """``dtype == param_dtype``: nothing is cast, nothing is copied —
    each leaf the engine holds IS the buffer it was given."""
    import jax

    cfg = _mixed_cfg(block, dtype=dtype, param_dtype=dtype)
    params = _params(cfg)
    same = cfg.decode_fns().resident(params)
    held, stats = D.DecodeEngine._resident(cfg, params)
    for given, a, b in zip(*map(jax.tree_util.tree_leaves,
                                (params, same, held))):
        assert a is given
        assert b.unsafe_buffer_pointer() == given.unsafe_buffer_pointer()
    assert stats["cast_bytes"] == 0 and stats["cast_leaves"] == 0
    assert stats["bytes"] == sum(
        x.nbytes for x in jax.tree_util.tree_leaves(params))


def _weight_converts(cfg, params):
    """Conversions, in the lowered step, of an operand with the shape of
    a weight or of the embedding table (whole, or one layer's slice)."""
    import re

    import jax

    from tensorflowonspark_tpu.serving.decode import kvcache

    shapes = set()
    for name, leaf in _leaf_names(params):
        if name not in _GAINS:
            shapes.update(("x".join(map(str, s)) for s in
                           (leaf.shape, leaf.shape[1:]) if len(s) >= 2))
    cache = kvcache.PagedKVCache(cfg, slots=2, block_size=4,
                                 prefix_sharing=False)
    text = jax.jit(cfg.decode_fns().decode_step_paged).lower(
        params, np.zeros((2, 1), np.int32), cache.pools,
        cache.block_tables, cache.lengths).as_text()
    return [m for m in re.findall(
        r"stablehlo\.convert [^\n]*\(tensor<([0-9x]+)xf32>\) -> "
        r"tensor<[0-9x]+xbf16>", text) if m in shapes]


@pytest.mark.parametrize("block", ["classic", "latent"])
def test_lowered_step_of_the_resident_tree_converts_no_weight(block):
    """The float32 tree's step converts every matrix and the table (the
    control); the resident tree's converts none."""
    cfg = _mixed_cfg(block)
    params = _params(cfg)
    assert len(_weight_converts(cfg, params)) >= 6
    held = cfg.decode_fns().resident(_host(params))
    assert _weight_converts(cfg, held) == []


def _as_given(cfg, params):
    """The parent's ``set_params``: the tree on the device as it came."""
    import jax
    return jax.device_put(params), {"bytes": 0, "cast_bytes": 0,
                                    "cast_leaves": 0}


_JOBS = [("a", [3, 5, 7, 9, 11], {}), ("b", [4, 6, 8], {}),
         ("c", [2, 3, 5, 7, 11, 13, 17], {})]


@pytest.mark.parametrize("block", ["classic", "latent"])
def test_engine_on_a_float32_tree_emits_the_float32_trees_tokens(
        block, monkeypatch):
    """An engine handed float32 host weights at bfloat16 compute casts
    them (``stats()["params"]``) and emits, token for token, what the
    same programs emit when they are handed the float32 tree itself."""
    cfg = _mixed_cfg(block)
    params = _host(_params(cfg))
    spec = D.DecodeSpec(cfg, slots=_cpu_slots(cfg), max_tokens=9,
                        block_size=4)
    held, st, eng = _run_sessions(params, spec, _JOBS)
    cast = [leaf for name, leaf in _leaf_names(params)
            if name not in _GAINS]
    assert st["params"]["cast_leaves"] == len(cast)
    assert st["params"]["cast_bytes"] == sum(x.nbytes for x in cast) // 2
    assert st["params"]["bytes"] == st["params"]["cast_bytes"] + sum(
        leaf.nbytes for name, leaf in _leaf_names(params) if name in _GAINS)
    monkeypatch.setattr(D.DecodeEngine, "_resident", staticmethod(_as_given))
    wide, st, _ = _run_sessions(params, spec, _JOBS)
    assert st["params"]["cast_leaves"] == 0
    assert held == wide and all(len(t) == 9 for t in held.values())


def test_set_params_while_serving_casts_the_new_tree():
    """A hot reload is cast like the first tree and swapped in between
    iterations: the next session's tokens are a fresh engine's on the new
    tree, and ``stats()["params"]`` is the new tree's."""
    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu.models import transformer as T

    cfg = _mixed_cfg("classic")
    old = _host(_params(cfg))
    new = _host(T.init(jax.random.PRNGKey(5), cfg))
    # one leaf of the new tree comes in the compute type already
    new["head"] = np.asarray(jnp.asarray(new["head"]).astype(jnp.bfloat16))
    spec = D.DecodeSpec(cfg, slots=2, max_tokens=8, block_size=4)
    fresh, st_new, _ = _run_sessions(new, spec, _JOBS[:1])
    stale, _, _ = _run_sessions(old, spec, _JOBS[:1])
    done = {}

    def emit(kind, sid, *rest):
        if kind in ("done", "error"):
            done[sid] = (kind, rest[0])

    def result(sid):
        deadline = time.time() + 300
        while sid not in done and time.time() < deadline:
            time.sleep(0.01)
        assert done[sid][0] == "done", done.get(sid)
        return done[sid][1]

    eng = D.DecodeEngine(old, spec, _each(emit))
    eng.start(timeout=300)
    try:
        # another prompt than the one served after the swap: a prefix
        # the trie kept would bring the old tree's K/V with it
        eng.submit("old", _JOBS[1][1])
        result("old")
        st_old = eng.stats()
        eng.set_params(new)
        eng.submit("a", _JOBS[0][1])
        after, st = result("a"), eng.stats()
    finally:
        eng.stop()
    assert after == fresh["a"] != stale["a"]
    assert st_old["params"]["cast_leaves"] == 6
    assert st["params"] == st_new["params"]
    assert st["params"]["cast_leaves"] == 5
    assert st["params"]["bytes"] == st_old["params"]["bytes"]
    assert st_old["params"]["cast_bytes"] - st["params"]["cast_bytes"] \
        == new["head"].nbytes


def test_speculative_draft_tree_is_resident_and_put_up_once(monkeypatch):
    """The existing gate with a draft whose weights are float32 host
    arrays multiplied in bfloat16: output token-identical to plain
    decoding, the draft's tree cast like the target's and put on the
    device once — not once a draft step."""
    import jax

    cfg = _mixed_cfg("classic")
    params = _host(_params(cfg))
    dcfg, dparams = _draft()
    dcfg = _mixed_cfg("classic", dim=dcfg.dim, n_layers=dcfg.n_layers)
    dparams = _host(dparams)
    calls = []
    plain_resident = D.DecodeEngine._resident

    def counted(cfg_, tree):
        calls.append(cfg_)
        return plain_resident(cfg_, tree)

    monkeypatch.setattr(D.DecodeEngine, "_resident", staticmethod(counted))
    plain, _, _ = _run_sessions(
        params, D.DecodeSpec(cfg, slots=2, max_tokens=9, block_size=4),
        _JOBS)
    assert calls == [cfg]
    specd, st, eng = _run_sessions(
        params, D.DecodeSpec(cfg, slots=2, max_tokens=9, block_size=4,
                             draft_params=dparams, draft_cfg=dcfg,
                             spec_window=3), _JOBS)
    assert specd == plain
    assert st["spec_proposed"] > 0 and st["iterations"] > 3
    assert calls == [cfg, cfg, dcfg]
    for name, leaf in _leaf_names(eng._draft_params):
        assert isinstance(leaf, jax.Array)
        assert leaf.dtype == ("float32" if name in _GAINS else "bfloat16")
    _both_caches_clean(eng)


def test_no_float32_copy_of_a_cast_leaf_outlives_set_params():
    """After ``set_params`` the engine references the resident tree
    only, and no float32 array with a cast leaf's shape is left on the
    device (widths no other test uses)."""
    import gc

    import jax

    cfg = _mixed_cfg("classic", vocab_size=67, dim=24, n_heads=2,
                     mlp_ratio=3)
    params = _host(_params(cfg))
    eng = D.DecodeEngine(params, D.DecodeSpec(cfg, slots=2, block_size=4),
                         lambda *a: None)
    eng.start(timeout=300)
    try:
        eng.set_params(_host(_params(cfg)))
        cast = [leaf.shape for name, leaf in _leaf_names(params)
                if name not in _GAINS]
        held = _leaf_names(eng._params)
        assert all(leaf.dtype == "bfloat16" for name, leaf in held
                   if name not in _GAINS)
        assert eng._params_stats["cast_leaves"] == len(cast) == 6
        gc.collect()
        wide = [a for a in jax.live_arrays()
                if a.dtype == "float32" and a.shape in cast]
        assert wide == []
    finally:
        eng.stop()


# --- Server / HTTP e2e ------------------------------------------------------

def test_server_generate_and_http_roundtrip(tmp_path):
    import jax

    from tensorflowonspark_tpu.utils import checkpoint as ckpt

    cfg = _cfg()
    params = _params(cfg)
    export = str(tmp_path / "export")
    ckpt.export_model(export, params, metadata={})
    spec = R.ModelSpec(export_dir=export,
                       decode=D.DecodeSpec(cfg, slots=4, max_tokens=8))
    prompt = [2, 3, 5, 7]
    ref = _oracle(params, prompt, cfg, max_tokens=6)
    with S.Server(spec, num_replicas=1, request_timeout=300) as srv:
        out = srv.generate(prompt, max_tokens=6, timeout=300)
        assert out["tokens"] == ref
        assert out["ttft_ms"] >= 0
        # gaps only exist between adjacent streamed tokens
        assert len(out["token_ms"]) == len(ref) - 1
        # predict on a decode-only spec is a clear error, not a hang
        with pytest.raises(Exception):
            srv.predict({"x": np.ones(1)}, timeout=30)
        # oversized prompts are rejected driver-side before any replica
        # sees the session (no crash, no shed)
        with pytest.raises(ValueError):
            srv.generate(list(range(1, cfg.max_seq + 1)), max_tokens=2,
                         timeout=30)
        # seeded sampling through the full server stack is replayable
        s1 = srv.generate(prompt, max_tokens=6, timeout=300,
                          temperature=0.9, top_k=8, seed=5)
        s2 = srv.generate(prompt, max_tokens=6, timeout=300,
                          temperature=0.9, top_k=8, seed=5)
        assert s1["tokens"] == s2["tokens"]
        # the replica answers the profile directive while it serves: a
        # capture taken INSIDE the process that owns the device
        cap = str(tmp_path / "capture")
        got = {}
        th = threading.Thread(target=lambda: got.update(
            out=srv.generate(prompt, max_tokens=6, timeout=300)))
        th.start()
        assert srv.profile(0.3, cap) == cap
        th.join()
        assert got["out"]["tokens"] == ref
        assert [f for _r, _d, fs in os.walk(cap) for f in fs
                if f.endswith(".xplane.pb")]
        st = next(iter(srv.pool.stats().values()))["decode"]
        assert st["tokens"] >= 3 * (len(ref) - 1)
        assert st["prompt_tokens"] >= 3 * len(prompt)
        assert set(st["phase_s"]) == {"idle", "admit", "step", "fetch",
                                      "host"}
        httpd = S.serve_http(srv, port=0, block=False)
        try:
            host, port = httpd.server_address
            req = urllib.request.Request(
                f"http://{host}:{port}/v1/generate",
                data=json.dumps({"prompt": prompt,
                                 "max_tokens": 6}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=300) as resp:
                assert resp.status == 200
                doc = json.loads(resp.read())
            assert doc["tokens"] == ref
            # malformed body -> 400, not a crash
            bad = urllib.request.Request(
                f"http://{host}:{port}/v1/generate",
                data=json.dumps({"nope": 1}).encode(),
                headers={"Content-Type": "application/json"})
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(bad, timeout=30)
            assert ei.value.code == 400
            # oversized prompt / invalid sampling params -> 400 too
            for body in ({"prompt": list(range(1, cfg.max_seq + 1))},
                         {"prompt": prompt, "temperature": -1.0}):
                r400 = urllib.request.Request(
                    f"http://{host}:{port}/v1/generate",
                    data=json.dumps(body).encode(),
                    headers={"Content-Type": "application/json"})
                with pytest.raises(urllib.error.HTTPError) as ei2:
                    urllib.request.urlopen(r400, timeout=30)
                assert ei2.value.code == 400, body
        finally:
            httpd.shutdown()
        summ = srv.summary()
    dec = summ["decode"]
    assert dec["completed"] >= 2 and dec["ttft_p99_ms"] >= 0


class _GenShedStub:
    pool = None

    def generate(self, prompt, max_tokens=None, eos_id=None, timeout=None,
                 **sampling_kw):
        raise B.Overloaded(65, 64, retry_after=0.5)


def test_http_generate_overload_maps_to_503():
    httpd = S.serve_http(_GenShedStub(), port=0, block=False)
    try:
        host, port = httpd.server_address
        req = urllib.request.Request(
            f"http://{host}:{port}/v1/generate",
            data=json.dumps({"prompt": [1, 2]}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=30)
        assert ei.value.code == 503
        assert float(ei.value.headers["Retry-After"]) == pytest.approx(0.5)
    finally:
        httpd.shutdown()


# --- slow lane: replica SIGKILL mid-decode (satellite c) --------------------

@pytest.mark.slow
def test_replica_sigkill_mid_decode_zero_drop_zero_dup(tmp_path):
    """A 2-replica decode service survives one SIGKILLed replica with
    sessions in flight: orphans re-prefill on the survivor and the
    resolve-once ledger dedupes the replayed stream, so every session
    still returns the exact oracle tokens — zero dropped, zero
    duplicated."""
    from tensorflowonspark_tpu.utils import checkpoint as ckpt

    cfg = _cfg()
    params = _params(cfg)
    export = str(tmp_path / "export")
    ckpt.export_model(export, params, metadata={})
    spec = R.ModelSpec(export_dir=export,
                       decode=D.DecodeSpec(cfg, slots=4, max_tokens=24))
    rng = np.random.default_rng(11)
    with S.Server(spec, num_replicas=2, request_timeout=300) as srv:
        # warm both replicas' compile caches first so the kill lands
        # mid-stream, not mid-compile
        srv.generate([1, 2, 3], max_tokens=2, timeout=300)
        results, errors = {}, {}

        def one(i):
            p = rng.integers(0, cfg.vocab_size, size=3 + i % 5).tolist()
            try:
                results[i] = (p, srv.generate(p, max_tokens=20,
                                              timeout=300))
            except Exception as e:  # noqa: BLE001 - asserted below
                errors[i] = e

        ts = [threading.Thread(target=one, args=(i,)) for i in range(6)]
        for t in ts:
            t.start()
        deadline = time.time() + 120
        while srv.pool.outstanding_sessions() < 3 and \
                time.time() < deadline:
            time.sleep(0.01)
        pids = srv.pool.replica_pids()
        os.kill(pids[sorted(pids)[0]], 9)
        for t in ts:
            t.join()
        assert not errors, errors
        assert len(results) == 6
        for i, (p, out) in results.items():
            ref = _oracle(params, p, cfg, max_tokens=20)
            assert out["tokens"] == ref, (i, out["tokens"], ref)
