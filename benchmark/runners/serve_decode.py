"""Runner ``serve_decode``: one ``serving.Server`` with a ``DecodeSpec``,
``num_replicas=1``, the replica owning the chip; requests over HTTP
(``serve_http``, ``POST /v1/generate``) from the traffic mix's clients.

Process shape: this driver process imports the program (and so jax) but
never initializes a backend.  The chip belongs, one after another, to
(1) a short-lived child that makes the weights from the seed with the
program's own ``transformer.init`` and writes the export the server
loads, (2) the program's replica process, (3) after the server has
stopped, a child that checks sampled replies against the plain
reference.  The replica is the program's process: the benchmark reaches
into it only through benchmark/hooks/sitecustomize.py (memory reading,
and the profiler capture of a ``--trace 1`` run).

Warm-up is by construction, not by luck: every (sequence bucket x row
bucket) prefill program, every K/V insert shape (one per prompt length in
blocks) and the decode step are driven before the window, and the
clients' synchronized first requests fall into a pre-roll.  After the
window the run checks that the compile cache gained no entry during it.
"""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

from benchmark.lib import loadgen, stats
from benchmark.lib import trace as T
from benchmark.lib.manifest import rehearsed
from benchmark.models import decoder as adapter


def say(msg):
    print(f"[bench:serve_decode] {msg}", flush=True)


def _write_json(path, obj):
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(obj, f)
    os.replace(tmp, path)


def _read_json(path):
    with open(path) as f:
        return json.load(f)


def mix_of(ctx):
    """The traffic mix as this run uses it (a tiny model cannot take real
    prompt lengths, so a rehearsal lays the mix's ``rehearse`` block over
    it)."""
    return rehearsed(ctx["mix"], ctx["rehearse"])


def decode_spec(ctx):
    from tensorflowonspark_tpu import serving

    cfg, mix = adapter.sizes(ctx), mix_of(ctx)
    model = adapter.model_config(cfg)
    blocks_per_slot = -(-model.max_seq // int(mix["block_size"]))
    return model, serving.DecodeSpec(
        model, slots=int(mix["slots"]), block_size=int(mix["block_size"]),
        # sentinel + the live set, NOT the default's 2x: the default pool
        # plus the step's pool-sized temporaries does not fit the chip
        num_blocks=1 + int(mix["slots"]) * blocks_per_slot,
        max_tokens=int(mix["max_tokens"]))


# -- children that own the chip ----------------------------------------------

def _require_chip(ctx):
    from tensorflowonspark_tpu import tpu_info

    device = tpu_info.device_facts()
    if not ctx["rehearse"] and device["platform"] != "tpu":
        raise RuntimeError(f"no accelerator: jax came up on {device}")
    return device


def child_export(ctx):
    """Weights from the seed, by the program's own init, in the type the
    engine holds them (float32), written where the server loads them."""
    import jax

    from tensorflowonspark_tpu.models import transformer
    from tensorflowonspark_tpu.utils import checkpoint as ckpt

    device = _require_chip(ctx)
    model = adapter.model_config(adapter.sizes(ctx))
    params = jax.jit(lambda key: transformer.init(key, model))(
        jax.random.PRNGKey(ctx["seed"]))
    ckpt.export_model(os.path.join(ctx["work"], "export"), params,
                      metadata={})
    return {"device": device}


def child_verify(ctx):
    """Sampled replies against the plain float32 reference: one forward
    pass over prompt + served tokens per request; position by position
    the served token must be the reference's argmax or lie within
    ``near_tie_logit`` of its top logit (chip_smoke's NEAR_TIE rule: the
    two paths round bf16 differently, and with random weights the top
    two logits are often that close)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import decoder as ref
    from tensorflowonspark_tpu.utils import checkpoint as ckpt

    device = _require_chip(ctx)
    cfg = adapter.sizes(ctx)
    heads, tie = cfg["num_attention_heads"], ctx["mix"]["near_tie_logit"]
    params, _meta = ckpt.load_exported(os.path.join(ctx["work"], "export"))
    params = jax.device_put(params)
    served = _read_json(os.path.join(ctx["work"], "served.json"))
    # ONE padded length for every run of the mix (its longest prompt plus
    # its longest output): one reference program, found in the compile
    # cache by every run after the first
    mix = mix_of(ctx)
    pad = -(-(mix["prompt_len"]["max"] + mix["output_len"]["max"]) // 128) \
        * 128

    @jax.jit
    def fwd(params, tokens):
        return ref.logits(params, tokens, heads)[0]

    exact = ties = wrong = 0
    worst = 0.0
    with jax.default_matmul_precision("highest"):
        for r in served:
            seq = r["prompt"] + r["tokens"]
            toks = np.zeros((1, pad), np.int32)
            toks[0, :len(seq)] = seq
            lg = np.asarray(fwd(params, jnp.asarray(toks)))
            for i, tok in enumerate(r["tokens"]):
                row = lg[len(r["prompt"]) + i - 1]
                if int(np.argmax(row)) == tok:
                    exact += 1
                    continue
                gap = float(np.max(row) - row[tok])
                worst = max(worst, gap)
                if gap <= tie:
                    ties += 1
                else:
                    wrong += 1
    return {"device": device, "exact": exact, "near_ties": ties,
            "wrong": wrong, "widest_gap": worst, "requests": len(served),
            "rule": f"argmax of the float32 reference, or within {tie} of "
                    "its top logit"}


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

class Http:
    def __init__(self, port, timeout):
        self.url = f"http://127.0.0.1:{port}/v1/generate"
        self.timeout = timeout

    def __call__(self, req):
        body = json.dumps({"prompt": req["prompt"],
                           "max_tokens": req["max_tokens"]}).encode()
        r = urllib.request.Request(
            self.url, data=body, headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(r, timeout=self.timeout) as resp:
                return json.loads(resp.read())
        except urllib.error.HTTPError as e:
            # the reply's body says what the server or the replica raised
            raise RuntimeError(
                f"HTTP {e.code}: {e.read()[:2000].decode(errors='replace')}"
            ) from None


def engine_stats(srv):
    st = srv.pool.stats()
    return next(iter(st.values()))["decode"]


def warm_up(srv, send, model, mix, seed):
    """Drive every program the window can need.  A prefill program is
    picked by (power-of-two bucket of the prompt length, power-of-two
    bucket of how many prompts of that bucket are admitted together,
    capped at slots); the K/V insert is one eager scatter per number of
    blocks a prompt fills; row i of a prefill's K/V is sliced out by its
    own small program.  One long-running blocker session keeps the
    engine iterating, so that a wave of n requests posted together is
    admitted together at the next iteration boundary; the engine's
    ``prefills`` counter says whether it was (one wave, one prefill), and
    a wave that split is sent again."""
    import random

    rng = random.Random(int(seed) ^ 0x5EED)
    vocab, bs = model.vocab_size, int(mix["block_size"])
    lo, hi = mix["prompt_len"]["min"], mix["prompt_len"]["max"]
    slots = int(mix["slots"])

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
        return out

    buckets, b = [], 1
    while b < lo:
        b <<= 1
    while b < hi:
        buckets.append(b)
        b <<= 1
    buckets.append(b)
    # wave sizes whose row bucket is 1, 2, 4, ..., slots (a blocker holds
    # one slot, so slots - 1 are free: 5 requests already pad to 8 rows)
    waves, n = [], 1
    while n < slots:
        waves.append(n if n <= 2 else n // 2 + 1)
        n <<= 1
    waves.append(min(slots - 1, slots // 2 + 1))
    waves = sorted(set(waves))
    covered_blocks, splits = set(), 0
    blocker = None

    def ensure_blocker():
        nonlocal blocker
        if blocker is None or not blocker.is_alive():
            blocker = threading.Thread(
                target=send, args=({"prompt": prompt(lo),
                                    "max_tokens": int(mix["max_tokens"])},),
                daemon=True)
            blocker.start()
            deadline = time.monotonic() + 600  # its prefill may compile
            while engine_stats(srv)["active"] < 1 \
                    and time.monotonic() < deadline:
                time.sleep(0.1)

    for t in buckets:
        t_lo = max(lo, t // 2 + 1)
        lengths = list(range(t_lo, min(t, hi) + 1))
        for n in waves:
            for _attempt in range(int(mix["warmup_attempts"])):
                ensure_blocker()
                before = engine_stats(srv)["prefills"]
                # spread the wave's lengths over the bucket's block counts
                lens = [lengths[(len(covered_blocks) * 7 + i * 13)
                                % len(lengths)] for i in range(n)]
                post_all([{"prompt": prompt(ln), "max_tokens": 1}
                          for ln in lens])
                covered_blocks.update(-(-ln // bs) for ln in lens)
                delta = engine_stats(srv)["prefills"] - before
                if delta == 1:
                    break
                splits += 1
    # every insert shape the window can meet: one per block count
    ensure_blocker()
    for nb in range(-(-lo // bs), -(-hi // bs) + 1):
        if nb not in covered_blocks:
            post_all([{"prompt": prompt(min(hi, nb * bs)), "max_tokens": 1}])
            covered_blocks.add(nb)
    if blocker is not None:
        blocker.join()
    return {"buckets": buckets, "waves": waves, "resent_waves": splits,
            "block_counts": len(covered_blocks)}


def cache_entries():
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not d or not os.path.isdir(d):
        return None
    return len([n for n in os.listdir(d) if not n.endswith("-atime")])


def hook_call(ctl, name, payload, done, timeout):
    _write_json(os.path.join(ctl, name), payload)
    deadline = time.monotonic() + timeout
    path = os.path.join(ctl, done)
    while time.monotonic() < deadline:
        if os.path.exists(path):
            res = _read_json(path)
            os.remove(path)
            return res
        time.sleep(0.1)
    return None


def run(ctx):
    from tensorflowonspark_tpu import serving

    mix = ctx["mix"] = mix_of(ctx)
    work = ctx["work"]
    seconds = float(ctx["seconds"])
    model, spec = decode_spec(ctx)
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
    with serving.Server(mspec, num_replicas=1, request_timeout=600,
                        env=replica_env) as srv:
        boot_s = time.time() - t_enter
        httpd = serving.serve_http(srv, port=0, block=False)
        try:
            send = Http(httpd.server_address[1], 600)
            t0 = time.time()
            warmed = warm_up(srv, send, model, mix, ctx["seed"])
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

    # -- reduce the records ---------------------------------------------------
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
    # requests still in flight past the drain count as failed
    in_flight = loop.started - len(records)
    attempted += in_flight
    failed += in_flight
    tokens_in = sum(1 for ts in token_times for t in ts if w0 <= t < w1)
    decode_tokens_in = sum(1 for ts in token_times for t in ts[1:]
                           if w0 <= t < w1)
    window_s = w1 - w0
    d_iter = s1["iterations"] - s0["iterations"]

    # -- correctness: sampled replies against the plain reference -----------
    import random

    sample = random.Random(ctx["seed"]).sample(
        good, min(int(mix["verify_requests"]), len(good)))
    _write_json(os.path.join(work, "served.json"),
                [{"id": r["id"], "prompt": by_id[r["id"]]["prompt"],
                  "tokens": r["reply"]["tokens"]} for r in sample])
    verified = run_child(ctx, "verify") if sample else None
    say(f"verify child: {verified}")
    correct = bool(
        verified and verified["wrong"] == 0
        and verified["near_ties"] <= verified["exact"]
        and failed == 0 and attempted > 0
        and summary["decode"]["errors"] == 0)
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
    }
    say(f"device {device}; window {window_s:.3f}s: {tokens_in} tokens "
        f"({tokens_in / window_s:.2f} tokens/s), {attempted} requests sent, "
        f"{failed} failed, {d_iter} iterations, "
        f"{facts['window_prefills']} prefills, iteration "
        f"{window_s * 1e3 / max(d_iter, 1):.2f} ms, occupancy "
        f"{decode_tokens_in / max(d_iter * s1['slots'], 1):.3f}")
    if ttfts:
        say(f"ttft ms over {len(ttfts)} requests: p50 "
            f"{stats.percentile(ttfts, 0.5):.1f}, max {max(ttfts):.1f} "
            f"(samples beyond a p95: {stats.samples_beyond(len(ttfts), 0.95)}"
            f"); token gap ms over {len(gaps_in)} gaps: p50 "
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
