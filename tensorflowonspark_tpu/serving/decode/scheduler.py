"""Iteration-level continuous batcher for autoregressive decode.

No reference counterpart (the reference delegates all inference to TF
Serving, SURVEY.md §2.2); this is the Orca-style iteration-level
scheduler the serving tier mounts behind
:class:`~tensorflowonspark_tpu.serving.replicas.ReplicaPool`:

- requests admit into free KV-cache slots **mid-flight** — there is no
  generation-boundary barrier; a new prompt joins the very next engine
  iteration after a slot frees up;
- each iteration runs (1) prefill for newly admitted prompts
  (sequence- and row-bucketed so compile count stays
  ``O(log slots · log max_seq)``), then (2) ONE fused decode step over
  every occupied slot;
- a finished sequence (EOS or ``max_tokens``) retires its slot
  immediately and the slot is eligible for re-admission in the same
  loop pass.

Three mechanisms ride the one loop (all token-exact against the
full-recompute oracle):

- **Block-paged KV with prefix sharing**: the cache is a
  :class:`~.kvcache.PagedKVCache`, the only kind there is; admission
  matches each prompt against the resident prefix trie and maps the
  shared blocks (refcount bump) instead of re-prefilling them — only
  the unmatched tail runs the model's tail prefill.  A model whose
  layout also has per-session state (a recurrent layer's) gets its
  state row with its slot, written whole by the admission's insert;
  such a layout runs without the trie and without a draft
  (:class:`DecodeSpec`).
- **Seeded sampling** (per-session temperature/top-k/top-p/seed,
  ``serving/decode/sampling.py``): the token is a pure function of
  ``(logits, params, index)``, so a failover replay re-draws the
  identical stream.  A greedy session's token is picked ON THE DEVICE
  (``jit_tfos_pick``: the argmax of its logits row) and only the ids
  come back; the logits come back too in an iteration in which some
  active session samples, and those sessions draw on the host.
- **Speculative decoding** (``spec_window`` + a draft model): the
  draft, on a paged cache of its own with the same slots, proposes K-1
  tokens, the verify step is ONE windowed paged step over the K-token
  window, and a draft token is accepted iff it EQUALS the target's
  seeded sample at that index — so speculative output is byte-identical
  to non-speculative at the same seed, not merely
  distribution-preserving.

Tokens stream back through the resolve-once machinery the predict path
already uses (batcher.PendingResult semantics): the driver-side
:class:`PendingSession` keys its token ledger by index, so a failover
replay after a replica SIGKILL (greedy and seeded-sampled decode are
both deterministic) re-delivers identical ``(index, token)`` pairs —
first arrival wins, ``_set``/``_fail`` resolve once, zero drop and
zero dup by construction.

The engine knows nothing of the model but its seam: ``cfg.decode_fns()``
(``models/transformer.DecodeFns``) hands it the incremental functions
(prefill, tail prefill, paged step) under one signature each and the
cache's row layout; the caches allocate their pools from that layout
and the engine passes them through as a tuple.

Module import stays stdlib + numpy (driver-importable); jax and the
model only load inside :class:`DecodeEngine`'s replica-side thread.
"""

from __future__ import annotations

import collections
import logging
import os
import threading
import time

import numpy as np

from tensorflowonspark_tpu.actors.ledger import IndexLedger, ResolveOnce
from tensorflowonspark_tpu.serving import batcher as _batcher
from tensorflowonspark_tpu.serving.decode import kvcache as _kvcache
from tensorflowonspark_tpu.serving.decode import sampling as _sampling
from tensorflowonspark_tpu.utils import faults, metrics_registry, telemetry

logger = logging.getLogger(__name__)

QUEUE_MAX_ENV = "TFOS_DECODE_QUEUE_MAX"


def queue_max_default():
    """A deployment's admission bound (``serving/server.py``): no field
    of :class:`DecodeSpec`."""
    return int(os.environ.get(QUEUE_MAX_ENV, "64"))


class DecodeSpec:
    """The decode tier's picklable config, carried to replicas inside
    the ModelSpec payload (replicas.ModelSpec(..., decode=...)).

    ``cfg`` is a ``models/transformer.Config``; ``slots`` sizes the
    KV cache; ``eos_id``/``max_tokens`` are per-session defaults a
    request may override (``max_tokens`` is always clamped to the
    cache page, ``max_seq - len(prompt)``).

    ``block_size``/``num_blocks`` size the
    :class:`~.kvcache.PagedKVCache` (``num_blocks`` None: twice the live
    set); ``prefix_sharing`` arms the prefix trie (None, the default:
    wherever the model's cache layout allows it).  Speculative decoding
    arms when BOTH ``draft_params`` (a transformer params pytree) and
    ``draft_cfg`` are given: the draft proposes ``spec_window - 1``
    tokens per iteration and one windowed verify step scores them.  The
    draft's cache is derived: the same slots and block size, the
    sentinel plus the live set of ITS ``max_seq``, no trie.

    A model whose layout has PER-SESSION STATE (``DecodeFns.has_state``: a
    linear-attention layer's recurrent state) runs without either, and
    asking for one is refused here, with the reason: prefix matching
    needs the state at the matched boundary, which nothing snapshots yet,
    and a rejected draft window cannot be taken back out of a state that
    has absorbed it.

    ``prefill_tokens`` bounds the padded tokens of ONE prefill program
    (rows x sequence bucket): admission cuts a wave that would exceed it
    into several prefills, so a prefill's temporaries are bounded however
    many long prompts arrive together.  Default: no bound.
    """

    def __init__(self, cfg, slots=8, eos_id=None, max_tokens=64,
                 block_size=16, num_blocks=None, prefix_sharing=None,
                 draft_params=None, draft_cfg=None, spec_window=4,
                 prefill_tokens=None):
        self.cfg = cfg
        self.slots = int(slots)
        self.eos_id = eos_id
        self.max_tokens = int(max_tokens)
        self.block_size = int(block_size)
        self.num_blocks = num_blocks
        fns = cfg.decode_fns()
        self.prefix_sharing = _kvcache.resolve_prefix_sharing(
            fns.rows, prefix_sharing)
        if fns.has_state and draft_cfg is not None:
            raise ValueError(
                "a draft model beside a cache layout with per-session "
                "state: the verify step would advance the state over the "
                "whole draft window, and a rejected tail cannot be taken "
                "back out of it (nothing snapshots state yet)")
        self.draft_params = draft_params
        self.draft_cfg = draft_cfg
        self.spec_window = int(spec_window)
        self.prefill_tokens = (None if prefill_tokens is None
                               else int(prefill_tokens))
        if self.prefill_tokens is not None and self.prefill_tokens < 1:
            raise ValueError("prefill_tokens must be >= 1")
        if self.spec_window < 2:
            raise ValueError(
                f"spec_window must be >= 2, got {self.spec_window}")
        if (draft_params is None) != (draft_cfg is None):
            raise ValueError(
                "speculative decoding needs BOTH draft_params and "
                "draft_cfg (or neither)")

    @property
    def speculative(self):
        return self.draft_params is not None


def tfos_pick(logits):
    """The greedy token of every row of ``logits`` ``[..., vocab]``, int32
    ``[...]``: what ``sampling.sample_token`` gives a greedy row — the
    FIRST index of the maximum, so exact ties agree too.  The engine jits
    it (``jit_tfos_pick`` in a device trace) and fetches its ids in place
    of the logits."""
    import jax.numpy as jnp

    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


class PendingSession(ResolveOnce):
    """One decode session's future: a streaming token ledger plus the
    resolve-once result, mirroring ``batcher.PendingResult``.  Both
    pieces come from ``actors.ledger``.

    The :class:`~tensorflowonspark_tpu.actors.ledger.IndexLedger` keys
    on token INDEX: after a replica SIGKILL the session re-prefills on a
    survivor and decode re-streams the same ``(index, token)`` pairs
    (greedy is deterministic; seeded sampling is a pure function of
    ``(logits, params, index)``, and the ``sampling`` dict — seed
    included — rides the dispatch blob, so the replay draws the same
    variates) — the first arrival of an index wins (its timestamp
    included, so TTFT/per-token stats survive failover), and a
    duplicate ``done`` is swallowed by the resolve-once gate.
    """

    __slots__ = ("id", "prompt", "max_tokens", "eos_id", "sampling",
                 "trace", "route_id", "t_submit", "_ledger")

    def __init__(self, sid, prompt, max_tokens, eos_id, sampling=None,
                 trace=None, route_id=None):
        super().__init__()
        self.id = sid
        self.prompt = [int(t) for t in prompt]
        self.max_tokens = int(max_tokens)
        self.eos_id = eos_id
        self.sampling = sampling
        self.route_id = route_id   # session-affinity key: a fabric
        # router pins returning sessions to the replica whose paged KV
        # cache still holds their prefix blocks (serving/fabric)
        self.trace = trace         # W3C traceparent string (or None);
        # rides the dispatch blob so replica-side decode spans join the
        # request's trace tree (docs/telemetry.md "Causal tracing")
        self.t_submit = time.perf_counter()
        self._ledger = IndexLedger()   # index -> token, first arrival wins

    def tokens_so_far(self):
        return [int(t) for t in self._ledger.values()]

    def result(self, timeout=None):
        """Block for the session result dict (``tokens``, ``ttft_ms``,
        ``token_ms`` gaps, ``total_ms`` + engine meta); raises the
        session's error or TimeoutError."""
        timeout = (_batcher.request_timeout_default()
                   if timeout is None else timeout)
        return self.wait(timeout, "decode session not done")

    # -- resolve-once plumbing (pool._collect calls these) ------------------
    def _token(self, index, token):
        self._ledger.record(index, int(token))

    def _set(self, tokens, meta):
        if self.done():
            return
        now = time.perf_counter()
        times = self._ledger.times()
        gaps = []
        order = sorted(times)
        for a, b in zip(order, order[1:]):
            if b == a + 1:  # only adjacent indices time a real gap
                gaps.append((times[b] - times[a]) * 1e3)
        self.resolve({
            "tokens": [int(t) for t in tokens],
            "ttft_ms": (round((times[0] - self.t_submit) * 1e3, 3)
                        if 0 in times else None),
            "token_ms": [round(g, 3) for g in gaps],
            "total_ms": round((now - self.t_submit) * 1e3, 3),
            **(meta or {}),
        })

    def _fail(self, exc):
        self.reject(exc)


class _Slot:
    """Replica-side per-slot generation state."""

    __slots__ = ("sid", "prompt_len", "generated", "max_tokens", "eos_id",
                 "sampling", "trace", "last", "t_admit", "prefill_rows")

    def __init__(self, sid, prompt_len, max_tokens, eos_id, first_token,
                 sampling=None, trace=None, prefill_rows=None):
        self.sid = sid
        self.prompt_len = prompt_len
        self.max_tokens = max_tokens
        self.eos_id = eos_id
        self.sampling = sampling
        self.trace = trace
        # rows of the prefill program that admitted it (its wave's row
        # bucket): a program is compiled per shape, and on the chip a
        # one-row prefill rounds otherwise than one of several rows
        self.prefill_rows = prefill_rows
        self.generated = [first_token]
        self.last = first_token
        self.t_admit = time.perf_counter()


class DecodeEngine:
    """The replica-side continuous-batching loop.

    ``emit(events)`` is the wire back to the pool
    (replicas._make_replica_task puts each call on the manager out-queue
    as ONE message): a list of ``("token", sid, index, token)`` per
    generated token, ``("done", sid, tokens, meta)`` at retirement and
    ``("error", sid, message)`` on a per-session failure, in the order
    the engine produced them.  The engine thread hands its events over
    once at the end of an iteration and once at the end of an admission
    (a first token does not wait for the step behind it); a session's
    tokens precede its ``done`` in the same or an earlier hand-over.
    ``submit``'s rejections and a failed cohort's errors go at once, as a
    hand-over of their own; an iteration that raises hands over what it
    had gathered before them.

    jax, the transformer model and the KV cache are imported/built on
    the engine thread — constructing a DecodeEngine never touches jax,
    so a driver that imports this module stays off the chip.
    """

    def __init__(self, params, spec, emit, replica=0):
        self._params = params
        self._spec = spec
        self._emit = emit
        self._events = []           # the engine thread's, since its last
        # hand-over
        self._replica = replica
        self._q = collections.deque()
        self._qlock = threading.Lock()
        self._sids = set()          # sids queued or active (dedupe)
        self._active = {}           # slot index -> _Slot
        self._cache = None          # engine-thread cache, read by stats()
        self._dcache = None         # the draft's, where there is a draft
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._thread = None
        self._started = threading.Event()
        self._init_error = None
        self._device = None         # tpu_info.device_facts(), engine thread
        self.iterations = 0
        self.prefills = 0
        self.retired = 0
        self.prefix_hits = 0
        self.prefix_tokens_saved = 0
        self.spec_proposed = 0
        self.spec_accepted = 0
        # always-on: tokens emitted by decode iterations (a session's
        # first token comes from its prefill and is not among them),
        # prompt tokens admitted, and where the engine thread's time
        # went.  The phases are chained clock reads (``_mark``), so they
        # sum to the thread's wall time by construction.
        self.tokens = 0
        self.prompt_tokens = 0
        # where each emitted token was chosen (the device's argmax / drawn
        # on the host from fetched logits), how often logits came to the
        # host at all, and the hand-overs with what they carried
        self.picks = {"device": 0, "host": 0}
        self.logits_fetches = 0
        self.messages = 0
        self.events = 0
        self._phase_s = {"idle": 0.0, "admit": 0.0, "step": 0.0,
                         "fetch": 0.0, "host": 0.0}
        self._t_mark = self._t_started = None
        self._fns = None            # the model's seam, engine thread
        # what ``set_params`` last put on the device, and the draft's tree
        self._params_stats = {"bytes": 0, "cast_bytes": 0, "cast_leaves": 0}
        self._draft_params = None
        # the paged step's own counters (computed on the device, fetched
        # with the logits): summed over steps, a ``*_max`` kept as maximum
        self._step_counters = {}
        # cached positions of all sessions, summed over iterations:
        # between two ``stats()`` its difference over that of
        # ``iterations`` is the mean a step gathered
        self._live_token_steps = 0
        # the same for sessions (each holds one row of every state entry)
        self._state_session_steps = 0

    def _mark(self, phase):
        """Everything since the last mark was ``phase``."""
        now = time.perf_counter()
        self._phase_s[phase] += now - self._t_mark
        self._t_mark = now

    # -- the wire ------------------------------------------------------------
    def _hand_over(self, events):
        """One call of ``emit``: one message to the pool."""
        with self._qlock:       # ``submit`` hands over off the engine thread
            self.messages += 1
            self.events += len(events)
        self._emit(events)

    def _flush(self):
        """Hand over what the engine thread has gathered, if anything."""
        if self._events:
            events, self._events = self._events, []
            self._hand_over(events)

    def _say_token(self, st, token):
        """``st``'s newest token, and where it was chosen."""
        greedy = _sampling.is_greedy(st.sampling)
        self.picks["device" if greedy else "host"] += 1
        self._events.append(("token", st.sid, len(st.generated) - 1, token))

    def _fetch(self, logits, samplers, counters=None):
        """``(ids, logits or None, counters)`` on the host, in ONE transfer:
        ``ids`` is the device's pick of every row of ``logits`` (argmax over
        the last axis, int32: what ``sample_token`` gives a greedy row,
        ties included).  The logits themselves stay on the device unless
        one of ``samplers`` (the ``sampling`` of the sessions these rows
        belong to) draws on the host."""
        ids = self._pick_jit(logits)
        if all(_sampling.is_greedy(p) for p in samplers):
            ids, counters = self._device_get((ids, counters))
            return ids, None, counters
        self.logits_fetches += 1
        return self._device_get((ids, logits, counters))

    @staticmethod
    def _choose(ids, logits, at, sampling, index):
        """The token of row ``at``: the device's pick, or the session's
        seeded draw at ``index`` from the fetched logits."""
        if _sampling.is_greedy(sampling):
            return int(ids[at])
        return _sampling.sample_token(logits[at], sampling, index)

    # -- lifecycle ----------------------------------------------------------
    def start(self, timeout=120.0):
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._run, name="tfos-decode-engine", daemon=True)
            self._thread.start()
        if not self._started.wait(timeout):
            raise TimeoutError("decode engine did not start")
        if self._init_error is not None:
            raise self._init_error
        return self

    def stop(self, timeout=10.0):
        self._stop.set()
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout)

    def set_params(self, params):
        """Hot-reload hook: swap params between iterations.  In-flight
        sessions finish against their already-cached K/V (old params)
        plus new-param compute for the remaining tokens — same in-band,
        no-drop semantics as the predict path's reload.

        The engine keeps the tree RESIDENT, not as given: on the device,
        and each leaf the model's programs only ever cast to the compute
        type (``DecodeFns.resident`` names them: the matrices and the
        embedding table, not the norm gains or a router's bias) already
        in that type, so no step casts it again.  A leaf that has the
        type stays the buffer it was; after this returns the engine
        references no wider copy.  ``stats()["params"]`` says what was
        cast."""
        self._params, self._params_stats = self._resident(
            self._spec.cfg, params)

    @staticmethod
    def _resident(cfg, params):
        """``(the tree the jitted programs are handed, its stats)``: on
        the device once, here — host arrays handed to a jitted step
        would be uploaded again on every call."""
        import jax

        leaves = jax.tree_util.tree_leaves
        held = jax.device_put(cfg.decode_fns().resident(params))
        cast = [h for p, h in zip(leaves(params), leaves(held))
                if h.dtype != p.dtype]
        return held, {
            "bytes": sum(int(h.nbytes) for h in leaves(held)),
            "cast_bytes": sum(int(h.nbytes) for h in cast),
            "cast_leaves": len(cast)}

    def submit(self, sid, prompt, max_tokens=None, eos_id=None,
               sampling=None, trace=None):
        """Queue one session; admission happens at the next iteration.
        Rejections (prompt too long, duplicate sid) are emitted as
        session errors, not raised — submit is called from the replica's
        message loop which must keep draining.  ``trace`` (a W3C
        traceparent string) links replica-side admit/retire telemetry
        into the originating request's trace."""
        cfg = self._spec.cfg
        prompt = [int(t) for t in prompt]
        if not prompt or len(prompt) > cfg.max_seq - 1:
            self._hand_over([(
                "error", sid,
                f"prompt length {len(prompt)} not in [1, "
                f"{cfg.max_seq - 1}] (max_seq {cfg.max_seq})")])
            return
        with self._qlock:
            if sid in self._sids:
                return              # failover re-send of a live session
            self._sids.add(sid)
            self._q.append({
                "sid": sid, "prompt": prompt,
                "max_tokens": int(max_tokens or self._spec.max_tokens),
                "eos_id": self._spec.eos_id if eos_id is None else eos_id,
                "sampling": sampling, "trace": trace,
                "t_queued": time.perf_counter(),
            })
        self._wake.set()

    def stats(self):
        with self._qlock:
            queued = len(self._q)
        out = {
            "iterations": self.iterations,
            "prefills": self.prefills,
            "retired": self.retired,
            "tokens": self.tokens,
            "prompt_tokens": self.prompt_tokens,
            "phase_s": {k: round(v, 6) for k, v in self._phase_s.items()},
            "picks": dict(self.picks),
            "logits_fetches": self.logits_fetches,
            "messages": self.messages,
            "events": self.events,
            "active": len(self._active),
            "queued": queued,
            "slots": self._spec.slots,
            "device": self._device,
            "prefix_hits": self.prefix_hits,
            "prefix_tokens_saved": self.prefix_tokens_saved,
            "params": dict(self._params_stats),
        }
        cache = self._cache
        out["blocks_in_use"] = cache.blocks_in_use if cache is not None else 0
        if cache is not None:
            # rows per token (the layers that have them), and per-session
            # state (0 bytes for a layout without; a session holds its
            # slot's row of it from admission to retirement)
            out["cache"] = {"row_bytes": cache.row_bytes,
                            "live_tokens": int(cache.lengths.sum()),
                            "live_token_steps": self._live_token_steps,
                            "state_row_bytes": cache.state_row_bytes,
                            "state_bytes": cache.state_row_bytes
                            * cache.slots,
                            "state_sessions": cache.occupancy,
                            "state_session_steps":
                                self._state_session_steps}
            trie = cache.trie
            if trie is not None:
                # totals since the engine started, like ``phase_s``
                out["cache"].update(
                    trie_nodes=trie.nodes,
                    blocks_reclaimed=trie.blocks_reclaimed,
                    reclaim_calls=trie.reclaim_calls,
                    reclaim_s=round(trie.reclaim_s, 6))
        fns = self._fns
        if fns is not None and fns.summarize is not None:
            # since the engine started; ``step_counters`` are the raw
            # totals, so that a reader can summarize an interval
            totals = dict(self._step_counters)
            out.update(fns.summarize(totals))
            out["step_counters"] = totals
        if self._spec.speculative:
            out["spec_proposed"] = self.spec_proposed
            out["spec_accepted"] = self.spec_accepted
            out["spec_accept_rate"] = round(
                self.spec_accepted / max(1, self.spec_proposed), 4)
        return out

    # -- engine thread ------------------------------------------------------
    def _build_caches(self):
        spec = self._spec
        cache = _kvcache.PagedKVCache(
            spec.cfg, spec.slots, block_size=spec.block_size,
            num_blocks=spec.num_blocks, prefix_sharing=spec.prefix_sharing)
        dcache = None
        if spec.speculative:
            # the draft's own pool: the sentinel and the live set of its
            # max_seq.  No trie: it prefills every prompt whole, and a
            # session sits in the SAME slot of both caches (one admission
            # order, one retirement)
            per_slot = -(-spec.draft_cfg.max_seq // spec.block_size)
            dcache = _kvcache.PagedKVCache(
                spec.draft_cfg, spec.slots, block_size=spec.block_size,
                num_blocks=1 + spec.slots * per_slot, prefix_sharing=False)
        self._cache, self._dcache = cache, dcache
        return cache, dcache

    def _run(self):
        try:
            import jax
            import jax.numpy as jnp  # noqa: F401 - jit closure imports

            from tensorflowonspark_tpu import tpu_info

            spec = self._spec
            fns = self._fns = spec.cfg.decode_fns()
            self._device_get = jax.device_get
            self._device = tpu_info.device_facts()
            self.set_params(self._params)

            # the closures' names are the programs' names in a device
            # trace (``jit_tfos_decode_step_paged``): say what they are
            def tfos_prefill(p, toks, lens):
                return fns.prefill(p, toks, lens)

            def tfos_prefill_extend(p, toks, pools, ptab, plens, lens):
                return fns.prefill_extend(p, toks, pools, ptab, plens, lens)

            def tfos_decode_step_paged(p, toks, pools, tables, lens):
                return fns.decode_step_paged(p, toks, pools, tables, lens)

            # a program of its own, not an output of the step: the step
            # programs and ``DecodeFns`` stay what they were
            self._pick_jit = jax.jit(tfos_pick)
            self._prefill_jit = jax.jit(tfos_prefill)
            self._extend_jit = jax.jit(tfos_prefill_extend)
            self._pstep_jit = jax.jit(
                tfos_decode_step_paged,
                donate_argnums=(2,) if fns.donate else ())
            if spec.speculative:
                dfns = spec.draft_cfg.decode_fns()
                # the draft's weights take the target's road, once
                self._draft_params, _ = self._resident(
                    spec.draft_cfg, spec.draft_params)

                def tfos_draft_prefill(p, toks, lens):
                    return dfns.prefill(p, toks, lens)

                def tfos_draft_step(p, toks, pools, tables, lens):
                    return dfns.decode_step_paged(p, toks, pools, tables,
                                                  lens)

                self._dprefill_jit = jax.jit(tfos_draft_prefill)
                self._dstep_jit = jax.jit(
                    tfos_draft_step,
                    donate_argnums=(2,) if dfns.donate else ())
            cache, dcache = self._build_caches()
        except BaseException as e:  # noqa: BLE001 - surface via start()
            self._init_error = e
            self._started.set()
            return
        self._t_mark = self._t_started = time.perf_counter()
        self._started.set()
        while not self._stop.is_set():
            try:
                faults.check("decode.step", replica=self._replica)
                self._admit(cache, dcache)
                if not self._active:
                    with telemetry.span(telemetry.DECODE_IDLE):
                        self._wake.wait(0.02)
                        self._wake.clear()
                    self._mark("idle")
                    continue
                self._iterate(cache, dcache)
            except BaseException as e:  # noqa: BLE001 - fail the cohort,
                # rebuild the caches, keep the replica serving
                logger.exception("decode engine iteration failed")
                self._flush()   # what it had gathered, before its errors
                self._fail_all(repr(e))
                cache, dcache = self._build_caches()

    # -- admission ----------------------------------------------------------
    def _admit(self, cache, dcache=None):
        """Move queued sessions into free slots.

        Each prompt is first matched against the prefix trie; a hit
        maps the shared blocks (refcount bump) and only the unmatched
        tail runs the model's tail prefill — grouped by (tail bucket,
        prefix-block bucket) so compile count stays logarithmic.  Misses
        run the plain bucketed ``prefill``, and so does the draft model
        for every prompt.  Every admitted prompt's whole-block prefix is then
        offered to the trie, so the FIRST request of a prefix populates
        it for all followers.  The first token comes from the prefill
        logits either way (picked on the device, or sampled at index 0).
        """
        batch = []
        with self._qlock:
            while self._q and len(batch) < cache.free_slots:
                batch.append(self._q.popleft())
        if not batch:
            return
        self._mark("host")
        n_prompt = sum(len(req["prompt"]) for req in batch)
        with telemetry.span(telemetry.DECODE_ADMIT_SPAN,
                            sessions=len(batch), prompt_tokens=n_prompt):
            try:
                self._admit_batch(batch, cache, dcache)
            except BaseException as e:
                # these left the queue and hold no slot yet: nobody else
                # would ever answer them
                seated = {st.sid for st in self._active.values()}
                for req in batch:
                    if req["sid"] not in seated:
                        with self._qlock:
                            self._sids.discard(req["sid"])
                        self._events.append(("error", req["sid"], repr(e)))
                raise
            # first tokens do not wait for the step behind them
            self._flush()
        self.prompt_tokens += n_prompt
        self._mark("admit")

    def _waves(self, members, bucket):
        """``members`` of one sequence bucket, cut so that no prefill
        program pads to more than ``DecodeSpec.prefill_tokens`` tokens
        (rows are padded to a power of two, so the cut is one too); one
        wave where there is no bound."""
        bound = self._spec.prefill_tokens
        if bound is None:
            return [members]
        rows = 1
        while rows * 2 * bucket <= bound:
            rows *= 2
        return [members[i:i + rows] for i in range(0, len(members), rows)]

    def _admit_batch(self, batch, cache, dcache):
        """The admission itself, under ``_admit``'s span: trie match,
        bucketed prefills, slot installation, first tokens."""
        cfg = self._spec.cfg
        plain, matched = [], []
        with telemetry.span(telemetry.DECODE_TRIE_MATCH):
            for req in batch:
                shared, mlen = cache.match_prefix(req["prompt"])
                if mlen > 0:
                    matched.append((req, shared, mlen))
                else:
                    plain.append(req)

        # (req, first token, the prefill's rows (still on the device,
        # whole batch), row index, shared, mlen)
        admitted = []
        # -- plain bucketed prefill (whole prompt) --------------------------
        groups = {}
        for req in plain:
            t = _batcher.bucket_seq(len(req["prompt"]), cfg.max_seq)
            groups.setdefault(t, []).append(req)
        for t, group in groups.items():
            waves = self._waves(group, t)
            for members in waves:
                rows = _batcher.bucket_size(len(members), self._spec.slots)
                toks = np.stack([
                    _batcher.pad_seq(np.asarray(m["prompt"], np.int32), t)
                    for m in members])
                lens = np.asarray([len(m["prompt"]) for m in members],
                                  np.int32)
                toks = _batcher.pad_rows(toks, rows)
                lens = _batcher.pad_rows(lens, rows)
                # dispatch to first tokens on the host
                with telemetry.span(telemetry.DECODE_PREFILL, bucket=t,
                                    rows=rows, tokens=rows * t,
                                    split=len(waves)):
                    logits, kv = self._prefill_jit(self._params, toks, lens)
                    ids, logits, _ = self._fetch(
                        logits, [m["sampling"] for m in members])
                self.prefills += 1
                for i, req in enumerate(members):
                    first = self._choose(ids, logits, i, req["sampling"], 0)
                    admitted.append((req, first, kv, i, [], 0))
        # -- prefix-hit tail prefill ----------------------------------------
        groups = {}
        for req, shared, mlen in matched:
            tail = len(req["prompt"]) - mlen
            key = (_batcher.bucket_seq(tail, cfg.max_seq),
                   _batcher.bucket_size(len(shared),
                                        cache.blocks_per_slot))
            groups.setdefault(key, []).append((req, shared, mlen))
        for (t, nbp), group in groups.items():
            waves = self._waves(group, t)
            for members in waves:
                rows = _batcher.bucket_size(len(members), self._spec.slots)
                toks = np.stack([
                    _batcher.pad_seq(
                        np.asarray(m[0]["prompt"][m[2]:], np.int32), t)
                    for m in members])
                lens = np.asarray(
                    [len(m[0]["prompt"]) - m[2] for m in members], np.int32)
                ptab = np.zeros((len(members), nbp), np.int32)
                for i, (_req, shared, _mlen) in enumerate(members):
                    ptab[i, :len(shared)] = shared
                plens = np.asarray([m[2] for m in members], np.int32)
                toks = _batcher.pad_rows(toks, rows)
                lens = _batcher.pad_rows(lens, rows)
                ptab = _batcher.pad_rows(ptab, rows)
                plens = _batcher.pad_rows(plens, rows)
                with telemetry.span(telemetry.DECODE_PREFILL, bucket=t,
                                    rows=rows, prefix_blocks=nbp,
                                    tokens=rows * t, split=len(waves)):
                    logits, kv = self._extend_jit(
                        self._params, toks, cache.pools, ptab, plens, lens)
                    ids, logits, _ = self._fetch(
                        logits, [m[0]["sampling"] for m in members])
                self.prefills += 1
                for i, (req, shared, mlen) in enumerate(members):
                    first = self._choose(ids, logits, i, req["sampling"], 0)
                    admitted.append((req, first, kv, i, shared, mlen))
                    self.prefix_hits += 1
                    self.prefix_tokens_saved += mlen
                    metrics_registry.inc("tfos_decode_prefix_hits")
        # -- draft prefill (speculative mode: full prompt, own cache) -------
        draft_kv = {}  # sid -> (the draft prefill's rows, on the device
        #                  and whole, row index)
        if dcache is not None:
            groups = {}
            for req in batch:
                t = _batcher.bucket_seq(len(req["prompt"]),
                                        self._spec.draft_cfg.max_seq)
                groups.setdefault(t, []).append(req)
            for t, members in groups.items():
                rows = _batcher.bucket_size(len(members), self._spec.slots)
                toks = np.stack([
                    _batcher.pad_seq(np.asarray(m["prompt"], np.int32), t)
                    for m in members])
                lens = np.asarray(
                    [len(m["prompt"]) for m in members], np.int32)
                toks = _batcher.pad_rows(toks, rows)
                lens = _batcher.pad_rows(lens, rows)
                _lg, dkv = self._dprefill_jit(
                    self._draft_params, toks, lens)
                for i, req in enumerate(members):
                    draft_kv[req["sid"]] = (dkv, i)

        # -- slot installation + first-token emission -----------------------
        for i in range(len(admitted)):
            # drop each prefill's rows with its last session: several waves'
            # outputs need not stay on the device until all are seated
            req, first, kv, row, shared, mlen = admitted[i]
            admitted[i] = None
            plen = len(req["prompt"])
            slot = cache.alloc()
            # cannot be None: admission is bounded by free_slots
            bs = cache.block_size
            own = cache.alloc_blocks(-(-(plen - mlen) // bs))
            cache.map_session(slot, shared, own, plen)
            with telemetry.span(telemetry.DECODE_KV_INSERT,
                                tokens=plen - mlen,
                                state_bytes=cache.state_row_bytes):
                cache.insert_tail(slot, *kv, mlen, plen - mlen, row=row)
            cache.register_prompt(slot, req["prompt"])
            if dcache is not None:
                if dcache.alloc() != slot:
                    raise AssertionError(
                        "the draft's cache and the target's hand out "
                        "different slots")
                dkv, drow = draft_kv.pop(req["sid"])
                dcache.map_session(slot, [],
                                   dcache.alloc_blocks(-(-plen // bs)), plen)
                dcache.insert_tail(slot, *dkv, 0, plen, row=drow)
            mt = min(req["max_tokens"], cache.max_seq - plen)
            st = _Slot(req["sid"], plen, max(1, mt), req["eos_id"], first,
                       req["sampling"], trace=req.get("trace"),
                       prefill_rows=int(kv[0].shape[0]))
            self._active[slot] = st
            with telemetry.activate(st.trace):
                telemetry.event(
                    telemetry.DECODE_ADMIT, sid=st.sid, slot=slot,
                    prompt_len=plen, prefix_hit_len=mlen,
                    queue_ms=round((time.perf_counter()
                                    - req.get("t_queued", time.perf_counter()))
                                   * 1e3, 3))
            self._say_token(st, first)
            if (st.eos_id is not None and first == st.eos_id) \
                    or st.max_tokens <= 1:
                self._retire(cache, dcache, slot)
        metrics_registry.set_gauge("tfos_decode_slot_occupancy",
                                   cache.occupancy)
        metrics_registry.set_gauge("tfos_decode_blocks_in_use",
                                   cache.blocks_in_use)

    # -- iteration (plain W=1 or speculative W=K) ---------------------------
    def _iterate(self, cache, dcache):
        """One fused windowed step over every occupied slot.

        Without a draft model the window is 1 token — the plain paged
        step.  With one, the draft proposes ``K-1`` tokens host-sampled
        at their future indices, the window ``[last, d_1 .. d_{K-1}]``
        runs ONE paged verify step, and draft token ``d_j``
        is accepted iff it equals the target's seeded sample at index
        ``base+j-1`` — every emitted token is exactly the target
        sample conditioned on a correct history, so speculative output
        matches non-speculative token-for-token.  The draft ingests the
        full window (K steps of its own paged step at width 1) so its
        cache stays aligned; rejection rolls both cursors back by
        assignment, and the stale K/V past the cursor is unreachable
        (masked) until a later correct write lands on it.  Blocks grown
        for a rejected window stay mapped until retirement.

        The phases each run under their span and are closed by a
        ``_mark``: build the window (the draft's proposals included),
        dispatch the step, fetch its tokens (device wait, the pick, a few
        ints D2H; the logits too while a session samples), choose every
        slot's, then gather the events, retire, and hand them over.
        """
        spec = self._spec
        k_win = spec.spec_window if dcache is not None else 1
        with telemetry.span(telemetry.DECODE_ITERATE,
                            active=len(self._active)) as span:
            with telemetry.span(telemetry.DECODE_BUILD_WINDOW):
                window = np.zeros((cache.slots, k_win), np.int32)
                for slot, st in self._active.items():
                    window[slot, 0] = st.last
                n0 = cache.lengths.copy()
                self._live_token_steps += int(n0.sum())
                self._state_session_steps += len(self._active)
                if dcache is not None:
                    self._propose(dcache, window, n0)
                for slot in self._active:
                    cache.ensure_capacity(slot, int(n0[slot]) + k_win)
            self._mark("host")
            with telemetry.span(telemetry.DECODE_STEP_DISPATCH):
                logits, cache.pools, counters = self._pstep_jit(
                    self._params, window, cache.pools, cache.block_tables,
                    n0)
            self._mark("step")
            with telemetry.span(telemetry.DECODE_LOGITS_FETCH):
                # ids [slots, K], logits [slots, K, vocab] or None, and a
                # few ints computed by the step's program
                ids, logits, counters = self._fetch(
                    logits, [st.sampling for st in self._active.values()],
                    counters)
                for name, value in counters.items():
                    total = self._step_counters.get(name, 0)
                    self._step_counters[name] = (
                        max(total, int(value)) if name.endswith("_max")
                        else total + int(value))
            self._mark("fetch")
            self.iterations += 1
            sampled = {}
            with telemetry.span(telemetry.DECODE_SAMPLE):
                for slot, st in self._active.items():
                    base = len(st.generated)
                    # rows past max_seq wrote their token's k/v to the
                    # sentinel, so their logits miss history — never emit
                    # from them
                    valid = min(k_win, cache.max_seq - int(n0[slot]))
                    emitted = []
                    for j in range(valid):
                        if j > 0 and int(window[slot, j]) != emitted[j - 1]:
                            break       # draft diverged; later rows stale
                        if j > 0:
                            self.spec_accepted += 1
                        emitted.append(self._choose(
                            ids, logits, (slot, j), st.sampling, base + j))
                    if dcache is not None:
                        self.spec_proposed += k_win - 1
                    sampled[slot] = emitted
            n_emitted = 0
            with telemetry.span(telemetry.DECODE_EMIT):
                for slot, emitted in sampled.items():
                    st = self._active[slot]
                    done = False
                    for tok in emitted:
                        st.generated.append(tok)
                        st.last = tok
                        cache.lengths[slot] += 1
                        n_emitted += 1
                        self._say_token(st, tok)
                        if (st.eos_id is not None and tok == st.eos_id) \
                                or len(st.generated) >= st.max_tokens:
                            done = True
                            break
                    if dcache is not None:
                        # roll the draft cursor back onto the accepted
                        # prefix
                        dcache.lengths[slot] = cache.lengths[slot]
                    if done or cache.lengths[slot] >= cache.max_seq:
                        self._retire(cache, dcache, slot)
                self._flush()
            self.tokens += n_emitted
            span.add(tokens=n_emitted)
        metrics_registry.set_gauge("tfos_decode_slot_occupancy",
                                   cache.occupancy)
        metrics_registry.set_gauge("tfos_decode_blocks_in_use",
                                   cache.blocks_in_use)
        if dcache is not None:
            metrics_registry.set_gauge(
                "tfos_decode_spec_accept",
                round(self.spec_accepted / max(1, self.spec_proposed), 4))
        self._mark("host")

    def _propose(self, dcache, window, n0):
        """Fill ``window[:, 1:]`` with the draft's proposals: K steps of
        the draft's own paged step at width 1, the last of which only
        ingests the window's last token."""
        k_win = window.shape[1]
        for slot in self._active:
            dcache.ensure_capacity(slot, int(n0[slot]) + k_win)
        # COPIES of the table and the cursors: dispatch is asynchronous
        # and may read a host array in place, after it changed (the
        # cursors by the increment below: the draft then writes one
        # column on and its proposals go astray; the table when a slot
        # retires, while the window's last draft step, which nobody
        # waits for, may not have run)
        tables = dcache.block_tables.copy()
        for j in range(k_win):
            dlogits, dcache.pools, _ = self._dstep_jit(
                self._draft_params, window[:, j:j + 1], dcache.pools,
                tables, dcache.lengths.copy())
            for slot in self._active:
                dcache.lengths[slot] += 1
            if j < k_win - 1:
                ids, dlogits, _ = self._fetch(
                    dlogits, [st.sampling for st in self._active.values()])
                for slot, st in self._active.items():
                    window[slot, j + 1] = self._choose(
                        ids, dlogits, (slot, 0), st.sampling,
                        len(st.generated) + j)

    def _retire(self, cache, dcache, slot):
        st = self._active.pop(slot)
        cache.retire(slot)
        if dcache is not None:
            dcache.retire(slot)
        with self._qlock:
            self._sids.discard(st.sid)
        self.retired += 1
        metrics_registry.inc("tfos_decode_retired_total")
        gen_ms = round((time.perf_counter() - st.t_admit) * 1e3, 3)
        with telemetry.activate(st.trace):
            telemetry.record_span(
                telemetry.DECODE_RETIRE, gen_ms / 1e3, sid=st.sid,
                tokens=len(st.generated), prompt_len=st.prompt_len,
                replica=self._replica)
        self._events.append(("done", st.sid, list(st.generated), {
            "replica": self._replica,
            "prompt_len": st.prompt_len,
            "prefill_rows": st.prefill_rows,
            "gen_ms": gen_ms,
        }))

    def _fail_all(self, message):
        with self._qlock:
            queued = list(self._q)
            self._q.clear()
            self._sids.clear()
        failed = [req["sid"] for req in queued] \
            + [st.sid for st in self._active.values()]
        self._active.clear()
        if failed:
            self._hand_over([("error", sid, message) for sid in failed])
