"""What crosses from the device to the host, and from a replica to the
driver, per decode iteration: the iteration's tokens, once.

- the device's pick (``scheduler.tfos_pick``) is ``sampling.sample_token``
  for greedy rows, exact ties and a ``[slots, K]`` window included;
- a mixed cohort (greedy + seeded sampling) emits what the host path
  emits, and logits come to the host only while a sampling session is
  active (``stats()``: ``picks``, ``logits_fetches``);
- one hand-over an iteration and one an admission (``messages``,
  ``events``), a session's ``done`` never before its tokens, an
  iteration that raises hands over what it gathered before its errors;
- both collectors (``ReplicaPool._collect``, the fabric router's) resolve
  sessions from ``gen_batch``.
"""

import queue
import threading
import time

import numpy as np
import pytest

from tensorflowonspark_tpu.serving import decode as D
from tensorflowonspark_tpu.serving import replicas as R
from tensorflowonspark_tpu.serving.decode import sampling, scheduler

pytestmark = pytest.mark.decode


def _cfg(**kw):
    from tensorflowonspark_tpu.models import transformer as T
    base = dict(vocab_size=61, dim=32, n_layers=2, n_heads=2, max_seq=32,
                dtype="float32", attn_impl="reference")
    base.update(kw)
    return T.Config(**base)


def _params(cfg, seed=0):
    import jax

    from tensorflowonspark_tpu.models import transformer as T
    return T.init(jax.random.PRNGKey(seed), cfg)


# --- the pick ----------------------------------------------------------------

def _rows(kind):
    rng = np.random.default_rng(11)
    if kind == "random":
        return rng.normal(size=(8, 257)).astype(np.float32)
    if kind == "window":                 # a speculative step's [slots, K, V]
        return rng.normal(size=(4, 3, 129)).astype(np.float32)
    if kind == "ties":
        rows = rng.integers(-3, 4, size=(16, 64)).astype(np.float32)
        rows[0] = 0.0                    # every entry the maximum
        rows[1, [5, 40]] = 9.0           # the maximum twice
        rows[2] = -0.0
        rows[2, 7] = 0.0                 # -0.0 == 0.0: still the first index
        rows[3, -1] = 9.0                # the last column alone
        return rows
    if kind == "bfloat16":               # few distinct values: ties abound
        import jax.numpy as jnp
        return jnp.asarray(rng.normal(size=(8, 512)), jnp.bfloat16)
    raise AssertionError(kind)


@pytest.mark.parametrize("kind", ["random", "window", "ties", "bfloat16"])
def test_the_devices_pick_is_sample_token_of_a_greedy_row(kind):
    import jax

    rows = _rows(kind)
    ids = np.asarray(jax.jit(scheduler.tfos_pick)(rows))
    assert ids.dtype == np.int32 and ids.shape == rows.shape[:-1]
    host = np.asarray(rows, np.float32).reshape(-1, rows.shape[-1])
    want = [sampling.sample_token(row, None, i) for i, row in enumerate(host)]
    assert ids.reshape(-1).tolist() == want
    if kind == "ties":
        assert ids[:4].tolist() == [0, 5, 0, 63]


# --- the engine --------------------------------------------------------------

class _Recorded(D.DecodeEngine):
    """An engine that logs its hand-overs, and ``"iterate"`` as each
    iteration starts, in one list."""

    def __init__(self, params, spec):
        self.log = []
        super().__init__(params, spec, lambda events: self.log.append(
            list(events)))

    def _iterate(self, cache, dcache):
        self.log.append("iterate")
        return super()._iterate(cache, dcache)

    def handovers(self):
        return [m for m in self.log if m != "iterate"]

    def wait(self, sids, timeout=300):
        deadline = time.time() + timeout
        while time.time() < deadline:
            ended = {e[1] for m in self.handovers() for e in m
                     if e[0] in ("done", "error")}
            if set(sids) <= ended:
                return
            time.sleep(0.01)
        raise AssertionError(f"sessions {sids} timed out")


class _HostPath(_Recorded):
    """The reference: every logits array comes to the host and every
    token is ``sample_token`` of its row, as before there was a pick."""

    def _fetch(self, logits, samplers, counters=None):
        return super()._fetch(logits, [{"temperature": 1.0}], counters)

    @staticmethod
    def _choose(ids, logits, at, sampling_, index):
        return sampling.sample_token(logits[at], sampling_, index)


def _serve(engine_cls, params, spec, jobs):
    """``jobs`` queued before the engine starts (ONE admission), run to
    the end: ``({sid: done tokens}, stats, engine)``."""
    eng = engine_cls(params, spec)
    for sid, prompt, kw in jobs:
        eng.submit(sid, prompt, **kw)
    eng.start(timeout=300)
    try:
        eng.wait([sid for sid, _p, _k in jobs])
        stats = eng.stats()
    finally:
        eng.stop()
    done = {e[1]: e[2] for m in eng.handovers() for e in m
            if e[0] == "done"}
    assert len(done) == len(jobs), eng.handovers()
    return done, stats, eng


_COHORT = [
    ("greedy", [2, 3, 5, 7, 11], {"max_tokens": 12}),
    ("seeded", [3, 5, 7, 11, 13, 17],
     {"max_tokens": 3, "sampling": sampling.make(
         temperature=0.9, top_k=20, top_p=0.95, seed=7)}),
    ("hot", [5, 7, 11, 13, 17],
     {"max_tokens": 5, "sampling": sampling.make(temperature=1.3, seed=8)}),
]


def test_a_mixed_cohort_emits_the_host_paths_tokens_and_fetches_only_for_it():
    cfg = _cfg()
    params = _params(cfg)
    spec = D.DecodeSpec(cfg, slots=4, block_size=4)
    want, ref_stats, _ = _serve(_HostPath, params, spec, _COHORT)
    got, stats, eng = _serve(_Recorded, params, spec, _COHORT)
    assert got == want
    assert [len(got[sid]) for sid, _p, _k in _COHORT] == [12, 3, 5]
    # each token counted where it was chosen
    assert stats["picks"] == {"device": 12, "host": 3 + 5}
    # one admission of one wave, then the four iterations in which a
    # sampling session still ran: the other seven fetched ids alone
    assert stats["iterations"] == 11 and stats["prefills"] == 1
    assert stats["logits_fetches"] == 1 + 4
    # the reference fetched every time
    assert ref_stats["logits_fetches"] == 1 + 11
    # the token streams agree event for event, not only the results
    stream = [e for m in eng.handovers() for e in m if e[0] == "token"]
    assert sorted(stream) == sorted(
        ("token", sid, i, t) for sid, toks in want.items()
        for i, t in enumerate(toks))


def test_a_greedy_cohort_never_fetches_logits():
    cfg = _cfg()
    params = _params(cfg)
    jobs = [(f"s{i}", [2 + i, 3, 5, 7][: 2 + i % 3], {"max_tokens": 4 + i})
            for i in range(5)]
    got, stats, _ = _serve(_Recorded, params,
                           D.DecodeSpec(cfg, slots=4, block_size=4), jobs)
    want, _, _ = _serve(_HostPath, params,
                        D.DecodeSpec(cfg, slots=4, block_size=4), jobs)
    assert got == want
    assert stats["logits_fetches"] == 0
    assert stats["picks"] == {"device": sum(4 + i for i in range(5)),
                              "host": 0}


def test_one_hand_over_an_iteration_and_one_an_admission():
    cfg = _cfg()
    jobs = [("a", [2, 3, 5], {"max_tokens": 3}),
            ("b", [3, 5, 7], {"max_tokens": 5}),
            ("c", [5, 7, 11], {"max_tokens": 7})]
    done, stats, eng = _serve(
        _Recorded, _params(cfg), D.DecodeSpec(cfg, slots=4, block_size=4),
        jobs)
    log = eng.log
    # the admission's first tokens are handed over BEFORE the first step
    assert log[1] == "iterate"
    assert [e[:3] for e in log[0]] == [("token", sid, 0) for sid in "abc"]
    # then every iteration makes exactly one hand-over, of one token event
    # per active slot, a session's ``done`` right behind its last token
    assert log[1::2] == ["iterate"] * 6 and len(log) == 13
    live = {"a": 3, "b": 5, "c": 7}
    for n, message in enumerate(log[2::2], start=1):
        active = [sid for sid in "abc" if live[sid] > n]
        tokens = [e for e in message if e[0] == "token"]
        assert [(e[1], e[2]) for e in tokens] == [(s, n) for s in active]
        ended = [sid for sid in active if live[sid] == n + 1]
        assert [e[1] for e in message if e[0] == "done"] == ended
        for sid in ended:
            at = [e[:2] for e in message]
            assert at.index(("done", sid)) == at.index(("token", sid)) + 1
    # a ``done`` carries what was streamed, and never precedes it
    flat = [e for m in eng.handovers() for e in m]
    for sid, toks in done.items():
        at = next(i for i, e in enumerate(flat) if e[:2] == ("done", sid))
        assert [e[3] for e in flat[:at] if e[:2] == ("token", sid)] == toks
    assert stats["messages"] == 7 and stats["events"] == len(flat) == 18


def test_an_iteration_that_raises_hands_over_its_tokens_before_the_errors():
    class Failing(_Recorded):
        def _retire(self, cache, dcache, slot):
            if self._active[slot].sid == "short":
                raise RuntimeError("boom")
            return super()._retire(cache, dcache, slot)

    cfg = _cfg()
    eng = Failing(_params(cfg), D.DecodeSpec(cfg, slots=4, block_size=4))
    eng.submit("long", [2, 3, 5], max_tokens=9)
    eng.submit("short", [3, 5, 7], max_tokens=3)
    eng.start(timeout=300)
    try:
        eng.wait(["long", "short"])
        # the replica keeps serving: fresh caches, a fresh session
        eng.submit("after", [5, 7, 11], max_tokens=2)
        eng.wait(["after"])
        stats = eng.stats()
    finally:
        eng.stop()
    messages = eng.handovers()
    failed = next(i for i, m in enumerate(messages)
                  if any(e[0] == "error" for e in m))
    # the failing iteration's own tokens, both sessions', came first ...
    assert [e[:3] for e in messages[failed - 1]] == [
        ("token", "long", 2), ("token", "short", 2)]
    # ... then the errors, as a message of their own
    assert [e[:2] for e in messages[failed]] == [
        ("error", "long"), ("error", "short")]
    assert all("boom" in e[2] for e in messages[failed])
    assert [e[0] for e in messages[-1]][-1] == "done"
    assert stats["messages"] == len(messages)


# --- the collectors ----------------------------------------------------------

class _Spec:
    decode = None


def _pool():
    pool = R.ReplicaPool(_Spec(), num_replicas=1)
    return pool, "replica 0 failed the decode session: bad"


def _router():
    from tensorflowonspark_tpu.serving.fabric import router as FR

    return (FR.FabricRouter(_Spec(), num_hosts=1),
            "fabric host 0 failed the decode session: bad")


@pytest.mark.parametrize("make", [_pool, _router])
def test_a_collector_resolves_sessions_from_gen_batch(make):
    collector, failure = make()
    collector._outq = outq = queue.Queue()
    table = collector._table
    table.up(0, 4242)
    sessions = {sid: D.PendingSession(sid, [1, 2], 4, None)
                for sid in ("s1", "s2", "s3")}
    for sid, sess in sessions.items():
        table.add(("gen", sid), {"session": sess}, owner=0)
        table.get(("gen", sid))["t"] = 0.0      # long silent
    thread = threading.Thread(target=collector._collect, daemon=True)
    thread.start()
    try:
        first = [("token", "s1", 0, 11), ("token", "s2", 0, 21)]
        outq.put(("gen_batch", 0, first))
        second = [("token", "s1", 1, 12), ("done", "s1", [11, 12],
                                           {"replica": 0}),
                  ("token", "s2", 1, 22), ("error", "s3", "bad")]
        outq.put(("gen_batch", 0, second))
        assert sessions["s1"].result(timeout=30)["tokens"] == [11, 12]
        with pytest.raises(RuntimeError) as err:
            sessions["s3"].result(timeout=30)
        assert str(err.value) == failure
        # liveness was touched per session that streamed, and only those
        assert table.get(("gen", "s2"))["t"] > 0.0
        assert sessions["s2"].tokens_so_far() == [21, 22]
        assert table.get(("gen", "s1")) is None
        # after a re-dispatch the survivor replays the stream: duplicates
        # are swallowed, event by event, and the rest of the message counts
        outq.put(("gen_batch", 0, first + second + [
            ("token", "s2", 2, 23), ("done", "s2", [21, 22, 23], {})]))
        assert sessions["s2"].result(timeout=30)["tokens"] == [21, 22, 23]
        assert sessions["s1"].result(timeout=30)["tokens"] == [11, 12]
        assert table.loads()[0] == 0
    finally:
        collector._stop.set()
        thread.join(10)
    assert not thread.is_alive()


def test_a_fabric_worker_counts_the_sessions_a_hand_over_ends():
    from tensorflowonspark_tpu.serving.fabric import host

    outq = queue.Queue()
    worker = host._Worker(3, 1, {}, outq)
    worker._sessions = 3
    events = [("token", "a", 4, 9), ("done", "a", [9], {}),
              ("token", "b", 0, 1), ("error", "c", "bad")]
    worker._emit(events)
    assert worker.load() == 1
    assert outq.get_nowait() == ("gen_batch", 3, events)
    assert outq.empty()
