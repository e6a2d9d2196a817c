"""Runner ``serve_model``: ``runners/serve_decode.py``'s serving run for a
configuration that NAMES its model adapter.  One ``serving.Server`` with a
``DecodeSpec``, ``num_replicas=1``, the replica owning the chip; requests
over HTTP (``POST /v1/generate``) from the traffic mix's clients.

How a serving configuration names its adapter: its file says ``"adapter":
"<name>"`` and ``benchmark/models/<name>.py`` provides

    sizes(ctx)                  the configuration as this run uses it
    decode_spec(cfg, mix)       -> (transformer.Config, serving.DecodeSpec)
    init_params(model, seed)    weights from the seed, by the program's init
    reference(params, cfg, q_block, at_width)
                                -> forward(seq, at, round_to=None)
                                -> (logits at ``at``, routing margins or None)

(``train_fed.adapter_of`` finds a training adapter the same way;
``serve_decode`` imports ``models/decoder.py`` by name.  Moving its cell
onto this runner is a later ``benchmark`` issue.)

Process shape, hooks, window, pre-roll and the facts returned are
``serve_decode``'s: the chip belongs in turn to the export child, the
program's replica (reached only through benchmark/hooks/sitecustomize.py)
and the verify child.  Differences, each forced by the model:

- warm-up drives every prefill program the window can meet: (sequence
  bucket) x (row bucket), the rows capped by the mix's ``prefill_tokens``
  (the engine cuts a wave that would pad to more).  The K/V insert has
  one program per prefill shape (it takes the prefill's output as it is),
  so the same waves warm it; then the decode step.
- ``correct`` compares LOGITS, of what the timed path produced.  For the
  sampled requests (``readings`` / ``refused_by``):

  (b) the engine's own jitted prefill, insert and paged step (the same
  closures, so the same programs; the prefill in the row bucket the
  engine's reply names, ``prefill_rows``), replayed over prompt + served
  tokens, give every served token as their argmax or within
  ``replay_tie_logit`` of their top logit: this ties the logits that are
  compared to the tokens the timed path emitted.  Those logits' relative
  L2 error against the plain reference's full forward has its median and
  its 99th percentile over positions under ``rel_err_median_max`` and
  ``rel_err_p99_max``.
  (a) The share of served tokens that are the reference's own argmax is
  at least ``exact_share_min``.  (A single token's distance from the
  reference's top logit is reported, ``widest_gap``, and holds nothing:
  see below.)

  The reference runs in float32 ``highest`` over the bf16 weights, one
  layer and one expert upcast at a time, padded to one of two lengths.

  Router near-ties, and why the limits are on distributions.  With 128
  experts and top-8 the eighth and ninth score of a token are often
  closer than bfloat16 resolves, so the program's bf16 step picks another
  expert than the float32 reference at a share of (position, layer)
  pairs, and with seeded random weights one changed expert moves that
  token's stream by a tenth (a trained model's experts are not that far
  apart).  Every context of a few thousand tokens holds such positions
  (``routing_near_tie_share``: the share of positions where the
  reference's margin between the last expert chosen and the first left
  out is under ``route_margin`` in some layer), so none can be left out.
  At the worst of them the error is half the logits' own norm and the
  served token lies 2-3 logits under the reference's top: the maximum
  over a run's positions is an extreme value that swings from seed to
  seed and no limit on it separates bfloat16 from a precision lower.
  The median, the 99th percentile and the share of exact tokens do, and
  repeat within a few percent.

  The control.  Every limit lies between what the program reads and what
  the reference reads when its weights and cached rows are rounded one
  precision lower (the mix's ``verify.lower_precision``): that forward
  runs on the first sampled request of EVERY run, is judged by the same
  rule as if an engine had computed it and served its argmax, and a run
  in which it is not refused is not ``correct``.
"""

import json
import os
import subprocess
import sys
import threading
import time

from benchmark.lib import loadgen, stats
from benchmark.lib import manifest as M
from benchmark.lib import trace as T
from benchmark.runners.serve_decode import (
    Http, _read_json, _require_chip, _write_json, cache_entries,
    engine_stats, hook_call, mix_of)


def say(msg):
    print(f"[bench:serve_model] {msg}", flush=True)


def adapter_of(ctx):
    return M.load_module(os.path.join(
        ctx["root"], "benchmark", "models", ctx["config"]["adapter"] + ".py"))


# -- children that own the chip ----------------------------------------------

def child_export(ctx):
    """Weights from the seed, by the program's own init, in the type the
    engine holds them, written where the server loads them."""
    from tensorflowonspark_tpu.utils import checkpoint as ckpt

    adapter = adapter_of(ctx)
    device = _require_chip(ctx)
    model, _spec = adapter.decode_spec(adapter.sizes(ctx), mix_of(ctx))
    params = adapter.init_params(model, ctx["seed"])
    ckpt.export_model(os.path.join(ctx["work"], "export"), params,
                      metadata={})
    return {"device": device}


def bucket(n, cap):
    """The engine's own sequence bucket (power of two, capped)."""
    from tensorflowonspark_tpu.serving import batcher

    return batcher.bucket_seq(n, cap)


def replay(model, spec, params, served):
    """The engine's own programs over prompt + served tokens: the same
    closures the engine jits (so the same compiled programs), a
    ``PagedKVCache`` of the spec's size, each sampled request in a slot
    of its own, all slots stepped together.  Returns per request the
    float32 logits [len(tokens), vocab] that PRODUCED each served token.
    """
    import jax
    import numpy as np

    from tensorflowonspark_tpu.serving.decode import kvcache

    fns = model.decode_fns()

    def tfos_prefill(p, toks, lens):
        return fns.prefill(p, toks, lens)

    def tfos_decode_step_paged(p, toks, pools, tables, lens):
        return fns.decode_step_paged(p, toks, pools, tables, lens)

    prefill = jax.jit(tfos_prefill)
    step = jax.jit(tfos_decode_step_paged,
                   donate_argnums=(2,) if fns.donate else ())
    cache = kvcache.PagedKVCache(
        model, spec.slots, block_size=spec.block_size,
        num_blocks=spec.num_blocks, prefix_sharing=False)
    if len(served) > spec.slots:
        raise ValueError("more sampled requests than slots")
    out = []
    for slot, r in enumerate(served):
        plen = len(r["prompt"])
        t = bucket(plen, model.max_seq)
        # the program the engine ran: the wave's row bucket as well (on
        # the chip one row rounds otherwise than several; the other rows'
        # content changes nothing: PERF.md, PR27)
        wave = int(r.get("prefill_rows") or 1)
        toks = np.zeros((wave, t), np.int32)
        toks[:, :plen] = r["prompt"]
        logits, rows = prefill(params, toks, np.full((wave,), plen, np.int32))
        assert cache.alloc() == slot
        own = cache.alloc_blocks(-(-plen // cache.block_size))
        cache.map_session(slot, [], own, plen)
        cache.insert_tail(slot, *rows, 0, plen, row=0)
        out.append([np.asarray(logits[0])])
    steps = max(len(r["tokens"]) for r in served) - 1
    for i in range(steps):
        window = np.zeros((cache.slots, 1), np.int32)
        live = [s for s, r in enumerate(served) if i < len(r["tokens"]) - 1]
        for s in live:
            window[s, 0] = served[s]["tokens"][i]
            cache.ensure_capacity(s, int(cache.lengths[s]) + 1)
        logits, cache.pools, _counters = step(
            params, window, cache.pools, cache.block_tables,
            cache.lengths.copy())
        logits = np.asarray(logits)
        for s in live:
            out[s].append(logits[s, 0])
            cache.lengths[s] += 1
    del cache
    return [np.stack(rows) for rows in out]


def readings(rows):
    """What the limits are set on, from ``(want, got, tokens)`` per
    sequence: ``want`` the reference's logits [n, V], ``got`` the logits
    that are judged, ``tokens`` what was served from them."""
    import numpy as np

    errs, gaps, own_gaps = [], [], []
    for want, got, tokens in rows:
        at = np.arange(len(tokens))
        errs.append(np.linalg.norm(got - want, axis=1)
                    / np.linalg.norm(want, axis=1))
        gaps.append(np.max(want, axis=1) - want[at, tokens])
        own_gaps.append(np.max(got, axis=1) - got[at, tokens])
    errs, gaps, own_gaps = (np.concatenate(x) for x in (errs, gaps, own_gaps))
    return {
        "positions": int(errs.size),
        # (a) the served tokens against the reference
        "exact_share": float(np.mean(gaps == 0.0)),
        "gap_median": float(np.median(gaps)),
        "gap_p99": float(np.percentile(gaps, 99)),
        "widest_gap": float(np.max(gaps)),
        # (b) the served tokens against the logits judged, and those
        # logits against the reference
        "own_exact_share": float(np.mean(own_gaps == 0.0)),
        "own_widest_gap": float(np.max(own_gaps)),
        "rel_err_median": float(np.median(errs)),
        "rel_err_p99": float(np.percentile(errs, 99)),
        "rel_err_max": float(np.max(errs)),
    }


def refused_by(read, lim):
    """The limits that ``read`` is outside of (none: it passes)."""
    out = []
    if read["own_widest_gap"] > lim["replay_tie_logit"]:
        out.append("replay_tie_logit")
    if read["exact_share"] < lim["exact_share_min"]:
        out.append("exact_share_min")
    if read["rel_err_median"] > lim["rel_err_median_max"]:
        out.append("rel_err_median_max")
    if read["rel_err_p99"] > lim["rel_err_p99_max"]:
        out.append("rel_err_p99_max")
    return out


def child_verify(ctx):
    """(a) and (b) of the module docstring, for the sampled requests, and
    the lower-precision control through the same rule."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tensorflowonspark_tpu.utils import checkpoint as ckpt

    adapter = adapter_of(ctx)
    device = _require_chip(ctx)
    cfg, mix = adapter.sizes(ctx), mix_of(ctx)
    lim = mix["verify"]
    model, spec = adapter.decode_spec(cfg, mix)
    params, _meta = ckpt.load_exported(os.path.join(ctx["work"], "export"))
    params = jax.device_put(params)
    served = _read_json(os.path.join(ctx["work"], "served.json"))
    t0 = time.time()
    replayed = replay(model, spec, params, served)
    t_replay = time.time() - t0

    forward = adapter.reference(params, cfg, int(lim["q_block"]),
                                int(mix["max_tokens"]))
    rows, control, margins_all, per_request = [], [], [], []
    for k, (r, got) in enumerate(zip(served, replayed)):
        t1 = time.time()
        seq = r["prompt"] + r["tokens"]
        at = np.arange(len(r["prompt"]) - 1, len(seq) - 1)
        tokens = np.asarray(r["tokens"])
        want, margins = forward(seq, at)
        rows.append((want, got, tokens))
        if margins is not None:
            margins_all.extend(margins[:, at].min(axis=0).tolist())
        if k == 0 and lim.get("lower_precision"):
            # what the reference gives one precision lower, judged as if
            # an engine had computed it and served its argmax
            low, _ = forward(seq, at,
                             round_to=jnp.dtype(lim["lower_precision"]))
            control.append((want, low, np.argmax(low, axis=1)))
        per_request.append([len(r["prompt"]), len(tokens),
                            round(time.time() - t1, 2)])
    read = readings(rows)
    out = {
        "device": device, "requests": len(served), **read,
        "refused_by": refused_by(read, lim),
        "routing_near_tie_share": float(np.mean(
            np.asarray(margins_all or [np.inf]) < float(lim["route_margin"]))),
        "limits": {k: lim[k] for k in (
            "replay_tie_logit", "exact_share_min", "rel_err_median_max",
            "rel_err_p99_max", "route_margin")},
        "lower_precision": lim.get("lower_precision"),
        "replay_s": t_replay, "reference_s": time.time() - t0 - t_replay,
        "per_request_prompt_tokens_s": per_request,
        "prefill_rows": [int(r.get("prefill_rows") or 1) for r in served],
        "rule": "(b) the engine's programs replayed give every served "
                "token as their argmax, or within replay_tie_logit of "
                "their top logit; their logits' relative L2 error "
                "against the float32 reference has its median and its "
                "99th percentile over positions under the limits; (a) "
                "the share of served tokens that are the reference's own "
                "argmax is at least exact_share_min; and the reference "
                "one precision lower, put through the same rule, is "
                "refused",
    }
    out["ok"] = not out["refused_by"]
    if control:
        low = readings(control)
        out["lower_precision_reads"] = low
        out["lower_precision_refused_by"] = refused_by(low, lim)
        out["ok"] = out["ok"] and bool(out["lower_precision_refused_by"])
    return out


CHILDREN = {"export": child_export, "verify": child_verify}


def run_child(ctx, phase):
    out = os.path.join(ctx["work"], f"{phase}.json")
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), phase,
         os.path.join(ctx["work"], "ctx.json")], cwd=ctx["root"])
    if proc.returncode != 0 or not os.path.exists(out):
        raise RuntimeError(f"child {phase} failed (exit {proc.returncode})")
    res = _read_json(out)
    res["seconds"] = time.time() - t0
    return res


# -- the driver --------------------------------------------------------------

def warm_up(srv, send, model, spec, mix, seed):
    """Drive every program the window can need: for each power-of-two
    bucket of the mix's prompt lengths, one wave per row bucket (1, 2, 4,
    ... up to the slots and to what ``prefill_tokens`` allows), posted
    together behind a long-running blocker session so that the engine
    admits it as ONE prefill (its ``prefills`` counter says whether it
    did; a wave that split is sent again).  The insert's program is
    shaped by the prefill's output, the step by the blocker."""
    import random

    rng = random.Random(int(seed) ^ 0x5EED)
    vocab = model.vocab_size
    lo, hi = mix["prompt_len"]["min"], mix["prompt_len"]["max"]
    slots, bound = int(mix["slots"]), spec.prefill_tokens

    def prompt(n):
        return [rng.randrange(1, vocab) for _ in range(n)]

    def post_all(reqs):
        out, threads = [None] * len(reqs), []

        def one(i):
            try:
                out[i] = send(reqs[i])
            except Exception as e:  # noqa: BLE001 - raised below
                out[i] = e
        for i in range(len(reqs)):
            th = threading.Thread(target=one, args=(i,), daemon=True)
            th.start()
            threads.append(th)
        for th in threads:
            th.join()
        for r in out:
            if isinstance(r, Exception):
                raise RuntimeError(f"a warm-up request failed: {r!r}")

    buckets = sorted({bucket(n, model.max_seq) for n in (lo, hi)}
                     | {b for b in (1 << i for i in range(32))
                        if lo < b < hi})
    blocker = None

    def ensure_blocker():
        nonlocal blocker
        if blocker is None or not blocker.is_alive():
            blocker = threading.Thread(
                target=send, args=({"prompt": prompt(lo),
                                    "max_tokens": int(mix["max_tokens"])},),
                daemon=True)
            blocker.start()
            deadline = time.monotonic() + 900  # its prefill may compile
            while engine_stats(srv)["active"] < 1 \
                    and time.monotonic() < deadline:
                time.sleep(0.1)

    programs, splits, t_start = [], 0, time.time()
    for t in buckets:
        lengths = list(range(max(lo, t // 2 + 1), min(t, hi) + 1))
        rows = 1
        while True:
            # the smallest wave whose row bucket is ``rows``
            n = 1 if rows == 1 else rows // 2 + 1
            for _attempt in range(int(mix["warmup_attempts"])):
                ensure_blocker()
                before = engine_stats(srv)["prefills"]
                post_all([{"prompt": prompt(lengths[(i * 13) % len(lengths)]),
                           "max_tokens": 1} for i in range(n)])
                if engine_stats(srv)["prefills"] - before == 1:
                    break
                splits += 1
            programs.append([t, rows])
            say(f"warm-up: prefill bucket {t} x {rows} rows after "
                f"{time.time() - t_start:.1f}s")
            rows *= 2
            if rows > slots or (bound is not None and rows * t > bound) \
                    or rows // 2 + 1 > slots - 1:
                break
    if blocker is not None:
        blocker.join()
    return {"prefill_programs": programs, "resent_waves": splits}


def window_counters(model, s0, s1, iterations):
    """``(stats()["moe"], stats()["cache"])`` as the window's steps alone
    would read them: the step counters' raw totals at the window's end
    less those at its start, summarized by the model's own function (a
    ``*_max`` cannot be differenced: it stays the engine's), and the
    live tokens as the mean over the window's iterations.  A program
    without the raw totals gives what it said at the window's end."""
    moe, cache = s1.get("moe") or {}, dict(s1.get("cache") or {})
    summarize = model.decode_fns().summarize
    if summarize is not None and "step_counters" in s1:
        before = s0.get("step_counters") or {}
        moe = summarize({
            k: v if k.endswith("_max") else v - before.get(k, 0)
            for k, v in s1["step_counters"].items()})["moe"]
    if "live_token_steps" in cache and iterations:
        cache["live_tokens"] = (
            cache.pop("live_token_steps")
            - s0["cache"]["live_token_steps"]) / iterations
    return moe, cache


def run(ctx):
    from tensorflowonspark_tpu import serving

    adapter = adapter_of(ctx)
    mix = ctx["mix"] = mix_of(ctx)
    work = ctx["work"]
    seconds = float(ctx["seconds"])
    model, spec = adapter.decode_spec(adapter.sizes(ctx), mix)
    exported = run_child(ctx, "export")
    say(f"export child: {exported['seconds']:.1f}s on {exported['device']}")

    ctl = os.path.join(work, "hook")
    os.makedirs(ctl)
    hooks = os.path.join(ctx["root"], "benchmark", "hooks")
    replica_env = {
        "BENCH_HOOK_DIR": ctl,
        "PYTHONPATH": hooks + os.pathsep + os.environ.get("PYTHONPATH", "")}
    if ctx["rehearse"]:
        replica_env["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count=1"
    mspec = serving.ModelSpec(export_dir=os.path.join(work, "export"),
                              decode=spec)
    requests = loadgen.requests_from_mix(mix, ctx["seed"], model.vocab_size)
    t_enter = time.time()
    with serving.Server(mspec, num_replicas=1, request_timeout=900,
                        env=replica_env) as srv:
        boot_s = time.time() - t_enter
        httpd = serving.serve_http(srv, port=0, block=False)
        try:
            send = Http(httpd.server_address[1], 900)
            t0 = time.time()
            warmed = warm_up(srv, send, model, spec, mix, ctx["seed"])
            say(f"replica up in {boot_s:.1f}s; warm-up {time.time()-t0:.1f}s:"
                f" {warmed}")
            loop = loadgen.ClosedLoop(requests, int(mix["clients"]),
                                      send).start()
            time.sleep(float(mix["preroll_seconds"]))
            entries0 = cache_entries()
            s0 = engine_stats(srv)
            w0, w0_wall = time.perf_counter(), time.time()
            if ctx["trace"]:
                time.sleep(0.3 * seconds)
                trace_dir = os.path.join(work, "trace-replica")
                traced = hook_call(
                    ctl, "trace.go", {"seconds": float(mix["trace_seconds"]),
                                      "dir": trace_dir}, "trace.done",
                    float(mix["trace_seconds"]) + 60)
                if traced is None:
                    say("the replica's hook did not answer the trace request")
            time.sleep(max(0.0, w0 + seconds - time.perf_counter()))
            s1 = engine_stats(srv)
            w1 = time.perf_counter()
            entries1 = cache_entries()
            exhausted = loop.exhausted
            drained = loop.stop(float(mix["drain_timeout_s"]))
            mem = hook_call(ctl, "mem.go", {}, "mem.json", 10)
            summary = srv.summary()
        finally:
            httpd.shutdown()
    if exhausted:
        raise RuntimeError("the request list ran out before the window "
                           "closed: add a mix with more blocks")
    if not drained:
        say("requests were still in flight when the drain timeout ended")

    # -- reduce the records (as serve_decode does) ----------------------------
    records = sorted(loop.records, key=lambda r: r["sent"])
    token_times, gaps_in, ttfts, lateness = [], [], [], []
    attempted = failed = 0
    by_id = {r["id"]: r for r in requests}
    good = []
    for rec in records:
        want = by_id[rec["id"]]["max_tokens"]
        rep = rec.get("reply") or {}
        ok = "error" not in rec and len(rep.get("tokens", ())) == want \
            and rep.get("ttft_ms") is not None
        in_window = w0 <= rec["due"] < w1
        if in_window:
            attempted += 1
            failed += 0 if ok else 1
        if not ok:
            if "error" in rec:
                say(f"request {rec['id']} failed: {rec['error'][:200]}")
            continue
        t = rec["sent"] + rep["ttft_ms"] / 1e3
        times = [t]
        for g in rep["token_ms"]:
            t += g / 1e3
            times.append(t)
        token_times.append(times)
        if in_window:
            good.append(rec)
            ttfts.append((rec["sent"] - rec["due"]) * 1e3 + rep["ttft_ms"])
            lateness.append((rec["sent"] - rec["due"]) * 1e3)
            gaps_in.extend(rep["token_ms"])
    in_flight = loop.started - len(records)
    attempted += in_flight
    failed += in_flight
    tokens_in = sum(1 for ts in token_times for t in ts if w0 <= t < w1)
    decode_tokens_in = sum(1 for ts in token_times for t in ts[1:]
                           if w0 <= t < w1)
    window_s = w1 - w0
    d_iter = s1["iterations"] - s0["iterations"]

    # -- correctness ----------------------------------------------------------
    import random

    sample = random.Random(ctx["seed"]).sample(
        good, min(int(mix["verify_requests"]), len(good)))
    _write_json(os.path.join(work, "served.json"),
                [{"id": r["id"], "prompt": by_id[r["id"]]["prompt"],
                  "tokens": r["reply"]["tokens"],
                  "prefill_rows": r["reply"].get("prefill_rows")}
                 for r in sample])
    verified = run_child(ctx, "verify") if sample else None
    say("verify child: " + json.dumps(verified))
    moe, cache = window_counters(model, s0, s1, d_iter)
    correct = bool(
        verified and verified["ok"]
        and failed == 0 and attempted > 0
        and summary["decode"]["errors"] == 0
        # over the engine's whole life, the warm-up's steps too
        and (s1.get("moe") or {}).get("dropped", 0) == 0)
    compiled_in_window = (None if entries0 is None
                          else entries1 - entries0)
    if compiled_in_window:
        correct = False
        say(f"{compiled_in_window} programs were compiled INSIDE the window")

    device = dict(s1["device"])
    reduced = None
    if ctx["trace"]:
        path = T.find_xplane(os.path.join(work, "trace-replica"))
        if path:
            reduced = T.reduce(T.read_xplane(path))
    peak = ((mem or {}).get("stats") or {}).get("peak_bytes_in_use")
    device["memory_peak_bytes"] = peak
    facts = {
        "correct": correct, "attempted": attempted, "failed": failed,
        "device": device, "trace": reduced,
        "setup_s": w0_wall - ctx["t_start"], "boot_s": boot_s,
        "window_s": window_s, "window_tokens": tokens_in,
        "window_decode_tokens": decode_tokens_in,
        "window_iterations": d_iter,
        "window_prefills": s1["prefills"] - s0["prefills"],
        "slots": s1["slots"],
        "ttft_ms": ttfts, "token_gap_ms": gaps_in,
        "send_lateness_ms": lateness,
        "compiled_in_window": compiled_in_window,
        "prefix_hits": s1.get("prefix_hits"),
        "warm_up": warmed, "verify": verified,
        "export_s": exported["seconds"],
        "server_summary": summary.get("decode"),
        # the step's own counters over the window's iterations, and the
        # sizes a bytes function needs
        "engine_moe": moe, "engine_cache": cache,
        "model_sizes": adapter.sizes(ctx),
    }
    say(f"device {device}; window {window_s:.3f}s: {tokens_in} tokens "
        f"({tokens_in / window_s:.2f} tokens/s), {attempted} requests sent, "
        f"{failed} failed, {d_iter} iterations, "
        f"{facts['window_prefills']} prefills, iteration "
        f"{window_s * 1e3 / max(d_iter, 1):.2f} ms, occupancy "
        f"{decode_tokens_in / max(d_iter * s1['slots'], 1):.3f}; "
        f"experts {moe}; cache {cache}")
    if ttfts:
        say(f"ttft ms over {len(ttfts)} requests: p50 "
            f"{stats.percentile(ttfts, 0.5):.1f}, max {max(ttfts):.1f}; "
            f"token gap ms over {len(gaps_in)} gaps: p50 "
            f"{stats.percentile(gaps_in, 0.5):.2f}, p95 "
            f"{stats.percentile(gaps_in, 0.95):.2f}, p99 "
            f"{stats.percentile(gaps_in, 0.99):.2f}; client send lateness "
            f"p50 {stats.percentile(lateness, 0.5):.3f} ms, max "
            f"{max(lateness):.3f} ms")
    say(f"compile-cache entries gained inside the window: "
        f"{compiled_in_window}; prefix hits {facts['prefix_hits']}; replica "
        f"memory_stats {mem}; set-up {facts['setup_s']:.1f}s (export "
        f"{exported['seconds']:.1f}s, boot {boot_s:.1f}s)")
    if reduced:
        say(T.describe(reduced))
    return facts


if __name__ == "__main__":
    _phase, _ctx_path = sys.argv[1], sys.argv[2]
    _ctx = _read_json(_ctx_path)
    _write_json(os.path.join(_ctx["work"], f"{_phase}.json"),
                CHILDREN[_phase](_ctx))
