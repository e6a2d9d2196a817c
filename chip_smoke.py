"""chip_smoke.py — the quickest proof that the system starts on the chip.

Drives the main path once, through the entry points a user calls, at the
full width of the models the repo benchmarks (depth and step counts cut,
weights and data made from ``--seed``):

``train_fed``  ``TFCluster.run(LocalEngine(1), main_fun, ...,
               input_mode=InputMode.SPARK)`` + ``cluster.train(records)``:
               ResNet-50 / 1000 classes / 224x224x3 uint8 / batch 256 fed
               through the shm ring, ``DataFeed``, ``device_feed`` and
               ``resnet.make_train_step``; chief checkpoint + export.
``kernels``    ``ops.flash_attention`` (forward, backward "pallas" and
               "xla") and ``ops.fused_rmsnorm`` against their references
               at B8/S2048/H8/D128 bf16 and 16384x1024, then 3 train
               steps of the dim-1024 / 8-layer / vocab-16384 / seq-2048
               decoder with the flash kernel.
``decode``     ``serving.Server`` + ``DecodeSpec`` on that decoder, one
               replica owning the chip, ``POST /v1/generate`` over HTTP
               (two prompts share a prefix so the trie path runs), tokens
               checked against ``transformer.greedy_decode_reference``.

``--chips 4`` runs ONLY the four-chip path and what it is compared with:
one executor over four chips against a one-chip run of the same seed, then
four executors x one chip joined into one job.

Process shape: this parent never imports jax.  Each phase is a fresh child
process tree, one after another, so exactly one process owns the chip at
any time; the device facts of the last line come from the processes that
owned it.  Any phase that fails makes the script exit non-zero.  Without
an accelerator the script fails: ``--rehearse`` (tiny shapes, CPU,
interpreted kernels) exists for the sandbox, pins the children to the CPU
and so can never report ``"platform": "tpu"``.

Everything printed before the last line is smoke output — compile times,
step times, peak memory, cache hits — not benchmark numbers.  The last
line of stdout is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

import argparse
import json
import math
import os
import signal
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# bf16 tolerances, stated once.  KERNEL_TOL: max |kernel - reference| over
# max(1, max |reference|), reference in float32 at "highest" matmul
# precision.  LOSS_RTOL: per-step loss, four chips vs one chip.
# NEAR_TIE: a served token that is not the reference's argmax is accepted
# iff the reference's logit for it is within NEAR_TIE of the reference's
# maximum (the two paths round bf16 differently); the reference then
# continues from the served history.
KERNEL_TOL = 3e-2
LOSS_RTOL = 5e-2
NEAR_TIE = 0.25

PHASE_TIMEOUT_S = 900

FULL = {
    "resnet": {"depth": 50, "classes": 1000, "image": 224, "batch": 256,
               "steps": 8, "lr": 0.01, "dtype": "bfloat16"},
    "attn": {"b": 8, "s": 2048, "h": 8, "d": 128, "dtype": "bfloat16"},
    "norm": {"rows": 16384, "dim": 1024},
    "lm": {"vocab_size": 16384, "dim": 1024, "n_layers": 8, "n_heads": 8,
           "max_seq": 2048, "dtype": "bfloat16", "attn_impl": "flash"},
    "lm_batch": 8,
    "decode": {"slots": 8, "max_tokens": 32, "prefix": 192,
               "tails": [24, 40], "solo": [70, 100, 120], "pad_to": 512},
}
TINY = {
    "resnet": {"depth": 18, "classes": 10, "image": 32, "batch": 8,
               "steps": 4, "lr": 0.01, "dtype": "float32"},
    "attn": {"b": 1, "s": 128, "h": 2, "d": 32, "dtype": "float32"},
    "norm": {"rows": 64, "dim": 128},
    "lm": {"vocab_size": 128, "dim": 64, "n_layers": 2, "n_heads": 2,
           "max_seq": 128, "dtype": "float32", "attn_impl": "flash"},
    "lm_batch": 2,
    "decode": {"slots": 4, "max_tokens": 6, "prefix": 32,
               "tails": [5, 9], "solo": [11, 14, 20], "pad_to": 64},
}


def say(phase, msg):
    print(f"[chip_smoke:{phase}] {msg}", flush=True)


def write_json(path, obj):
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(obj, f)
    os.replace(tmp, path)


def read_json(path):
    with open(path) as f:
        return json.load(f)


def require_chip(device, conf):
    """Called by every process that is meant to own the chip."""
    if not conf["rehearse"] and device["platform"] != "tpu":
        raise RuntimeError(
            f"no accelerator: jax came up on {device} (chip_smoke never "
            "carries on on the CPU; --rehearse is the sandbox option)")


# -- train_fed ---------------------------------------------------------------

def records_fn(seed, image, classes, per_part):
    """Partition function run by the feeder task on the executor (no jax):
    partition number -> ``per_part`` (uint8 image, label) records made
    from the seed, each carrying its global id in its first four bytes."""

    def gen(parts):
        import numpy as np

        for part in parts:
            rng = np.random.default_rng([seed, part])
            block = rng.integers(0, 256, (per_part, image, image, 3),
                                 dtype=np.uint8)
            ids = np.arange(part * per_part, (part + 1) * per_part,
                            dtype="<u4")
            block.reshape(per_part, -1)[:, :4] = \
                ids.view(np.uint8).reshape(per_part, 4)
            for i in range(per_part):
                yield block[i], int(ids[i]) % classes

    return gen


def train_main(args, ctx):
    """The trainer: the main_fun shape of
    examples/resnet/resnet_imagenet_spark.py, plus the smoke's checks."""
    import os
    import time

    import jax
    import numpy as np
    import optax

    from tensorflowonspark_tpu import tpu_info
    from tensorflowonspark_tpu.infeed import device_feed, synchronized
    from tensorflowonspark_tpu.models import resnet
    from tensorflowonspark_tpu.parallel import (
        batch_sharding, local_to_global, make_mesh, shard_train_state,
    )
    from tensorflowonspark_tpu.recordio import native
    from tensorflowonspark_tpu.utils import checkpoint as ckpt
    from tensorflowonspark_tpu.utils import compile_cache
    from tensorflowonspark_tpu.utils.metrics import TrainMetrics

    cache = compile_cache.CacheCounter().install()
    env = ctx.jax_initialize()
    device = tpu_info.device_facts()
    require_chip(device, args)
    ring = str(ctx.mgr.get("shm_input") or "")
    if native.load() is None or not ring:
        raise RuntimeError(
            "the native shm ring is not in use (pure-python feed path): "
            f"native.load()={native.load()!r} shm_input={ring!r}")
    r = args["resnet"]
    image, batch = r["image"], r["batch"]
    mesh = make_mesh({"data": -1})

    opt = optax.sgd(r["lr"], momentum=0.9)

    # one jitted init program: eager init is hundreds of tiny dispatches,
    # each compiled on its own
    @jax.jit
    def init_all(key):
        params, state = resnet.init(key, depth=r["depth"],
                                    num_classes=r["classes"])
        return params, state, opt.init(params)

    t0 = time.perf_counter()
    params, state, opt_state = init_all(jax.random.PRNGKey(args["seed"]))
    (params, state, opt_state), (p_sh, s_sh, o_sh) = shard_train_state(
        mesh, params, state, opt_state)
    step_fn = jax.jit(
        resnet.make_train_step(opt, depth=r["depth"],
                               compute_dtype=jax.numpy.dtype(r["dtype"])),
        in_shardings=(p_sh, s_sh, o_sh, batch_sharding(mesh),
                      batch_sharding(mesh)),
        out_shardings=(p_sh, s_sh, o_sh, None, None),
        donate_argnums=(0, 1, 2),
    )
    init_s = time.perf_counter() - t0

    per_proc = batch // max(env["num_processes"], 1)
    metrics = TrainMetrics(
        flops_per_item=3 * resnet.flops_per_image(r["depth"], image))
    feed = ctx.get_data_feed(
        train_mode=True, metrics=metrics,
        input_mapping={"image": "image", "label": "label"})
    seen_ids = []

    def collate(cols):
        imgs = np.asarray(cols["image"], dtype=np.uint8).reshape(
            -1, image, image, 3)
        # the id every record carries in its first four bytes
        seen_ids.append(np.ascontiguousarray(
            imgs.reshape(len(imgs), -1)[:, :4]).view("<u4")[:, 0].copy())
        return imgs, np.asarray(cols["label"], dtype=np.int32)

    losses, step_s, placement = [], [], None
    step = 0
    for imgs, labels in synchronized(device_feed(
        feed, per_proc, collate=collate, depth=2, columnar=True,
        placement=lambda b: local_to_global(mesh, b),
    ), feed=feed):
        if placement is None:
            placement = {
                "batch_devices": sorted(
                    s.device.id for s in imgs.addressable_shards),
                "batch_shard_rows": [
                    s.data.shape[0] for s in imgs.addressable_shards],
                "param_devices": sorted(
                    d.id for d in
                    jax.tree_util.tree_leaves(params)[0].sharding.device_set),
            }
        t0 = time.perf_counter()
        params, state, opt_state, loss, _acc = step_fn(
            params, state, opt_state, imgs, labels)
        losses.append(float(loss))  # value fetch: the step has finished
        step_s.append(time.perf_counter() - t0)
        step += 1
        metrics.step(len(labels) * env["num_processes"])

    ckpt_info = None
    if ckpt.is_chief(ctx):
        ckpt_dir = os.path.join(args["model_dir"], "ckpt")
        ckpt.save_checkpoint(
            ckpt_dir,
            {"params": params, "state": state,
             "opt": ckpt.pack_pytree(opt_state)}, step)
        ckpt.export_model(os.path.join(args["model_dir"], "export"), params,
                          metadata={"model": f"resnet{r['depth']}"})
        _restored, restored_step = ckpt.restore_latest(ckpt_dir)
        ckpt_info = {"step": step, "restored_step": restored_step}

    stats = jax.local_devices()[0].memory_stats() or {}
    write_json(
        os.path.join(args["out"],
                     f"{args['tag']}-{ctx.job_name}-{ctx.task_index}.json"),
        {"device": device,
         "ids": np.concatenate(seen_ids).tolist() if seen_ids else [],
         "losses": losses, "init_s": init_s, "step_s": step_s,
         "placement": placement, "checkpoint": ckpt_info,
         "peak_bytes": stats.get("peak_bytes_in_use"),
         "native_ring": ring, "cache": cache.report()})


def executor_env(conf, devices=None):
    """Environment of a phase's executors.  They inherit this process's
    platform; a slice-health probe that only times out is fatal here, not
    a warning.  A rehearsal stands in for "this executor owns ``devices``
    chips" with that many virtual CPU devices."""
    env = {"TFOS_SLICE_HEALTH": "strict"}
    if conf["rehearse"] and devices:
        env["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={devices}"
    return env


def run_train(conf, out, tag, num_chips=0, steps=None):
    """Driver side of one fed training run (never initializes jax):
    returns the chief trainer's result after checking what every fed run
    must satisfy.  ``num_chips``: chips the executor claims (0: all the
    host has)."""
    from tensorflowonspark_tpu import cluster as TFCluster
    from tensorflowonspark_tpu.cluster import InputMode
    from tensorflowonspark_tpu.engine import LocalEngine

    r = dict(conf["resnet"])
    r["steps"] = steps or r["steps"]
    parts = 2
    n_records = r["steps"] * r["batch"]
    args = {"resnet": r, "seed": conf["seed"], "rehearse": conf["rehearse"],
            "out": out, "tag": tag,
            "model_dir": os.path.join(out, f"{tag}-model")}
    engine = LocalEngine(1, env=executor_env(conf, num_chips))
    try:
        cluster = TFCluster.run(
            engine, train_main, args, num_executors=1, num_chips=num_chips,
            input_mode=InputMode.SPARK, master_node="chief")
        ds = engine.parallelize(list(range(parts)), parts).map_partitions(
            records_fn(conf["seed"], r["image"], r["classes"],
                       n_records // parts))
        cluster.train(ds, num_epochs=1)
        cluster.shutdown(grace_secs=1)
    finally:
        engine.stop()
    res = read_json(os.path.join(out, f"{tag}-chief-0.json"))
    ids = res.pop("ids")
    if sorted(ids) != list(range(n_records)):
        raise AssertionError(
            f"{tag}: fed {n_records} records, trainer saw {len(ids)} "
            f"({len(set(ids))} distinct): not every id exactly once")
    res["ids_checksum"] = [sum(ids[i:i + r["batch"]])
                           for i in range(0, len(ids), r["batch"])]
    if len(res["losses"]) != r["steps"] or \
            not all(math.isfinite(x) for x in res["losses"]):
        raise AssertionError(f"{tag}: losses not finite / wrong count: "
                             f"{res['losses']}")
    if res["checkpoint"] != {"step": r["steps"],
                             "restored_step": r["steps"]}:
        raise AssertionError(f"{tag}: checkpoint {res['checkpoint']}")
    if not os.path.exists(os.path.join(args["model_dir"], "export",
                                       "export.json")):
        raise AssertionError(f"{tag}: no export written")
    say(tag, f"device={res['device']} records={n_records} ids exactly once; "
             f"losses={[round(x, 4) for x in res['losses']]}")
    say(tag, f"init+placement {res['init_s']:.1f}s, first step (compiles) "
             f"{res['step_s'][0]:.1f}s, later steps "
             f"{[round(x, 3) for x in res['step_s'][1:]]}s, memory_stats "
             f"peak {res['peak_bytes']} (live buffers; a program's "
             f"temporaries are not in it), ring {res['native_ring']}, "
             f"compile cache {res['cache']}")
    return res


def phase_train_fed(conf, out):
    res = run_train(conf, out, "train_fed")
    return {"device": res["device"], "cache": res["cache"]}


# -- kernels -----------------------------------------------------------------

def phase_kernels(conf, out):
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tensorflowonspark_tpu import ops, tpu_info
    from tensorflowonspark_tpu.models import transformer
    from tensorflowonspark_tpu.ops._pallas import resolve_interpret
    from tensorflowonspark_tpu.utils import compile_cache

    cache = compile_cache.CacheCounter().install()
    device = tpu_info.device_facts()
    require_chip(device, conf)
    interpret = resolve_interpret(None)
    if interpret and not conf["rehearse"]:
        raise RuntimeError("pallas resolved to interpret mode on the chip")
    say("kernels", f"device={device} pallas interpret={interpret}")

    def err(a, b):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        return float(np.max(np.abs(a - b)) / max(1.0, np.max(np.abs(b))))

    def timed(fn, *xs):
        t0 = time.perf_counter()
        y = jax.block_until_ready(fn(*xs))
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        y = jax.block_until_ready(fn(*xs))
        return y, first, time.perf_counter() - t0

    a = conf["attn"]
    dtype = jnp.dtype(a["dtype"])
    ks = jax.random.split(jax.random.PRNGKey(conf["seed"]), 6)
    shape = (a["b"], a["s"], a["h"], a["d"])
    q, k, v, g = (jax.random.normal(kk, shape, jnp.float32).astype(dtype)
                  for kk in ks[:4])
    flash = functools.partial(ops.flash_attention, causal=True)
    ref = functools.partial(ops.mha_reference, causal=True)

    def grads(attn):
        return jax.jit(jax.grad(
            lambda q_, k_, v_, g_: jnp.sum(
                attn(q_, k_, v_).astype(jnp.float32)
                * g_.astype(jnp.float32)), argnums=(0, 1, 2)))

    with jax.default_matmul_precision("highest"):
        q32, k32, v32 = (x.astype(jnp.float32) for x in (q, k, v))
        ref_out = jax.jit(ref)(q32, k32, v32)
        ref_grads = grads(ref)(q32, k32, v32, g)
        del q32, k32, v32
    checks = {}
    out_, first, again = timed(jax.jit(flash), q, k, v)
    checks["flash_fwd"] = err(out_, ref_out)
    say("kernels", f"flash fwd {shape} {dtype}: err {checks['flash_fwd']:.2e}"
                   f", first call {first:.2f}s, second {again * 1e3:.2f}ms")
    for impl in ("pallas", "xla"):
        got, first, again = timed(
            grads(functools.partial(flash, bwd_impl=impl)), q, k, v, g)
        checks[f"flash_bwd_{impl}"] = max(
            err(x, y) for x, y in zip(got, ref_grads))
        say("kernels", f"flash fwd+bwd[{impl}]: err "
                       f"{checks[f'flash_bwd_{impl}']:.2e}, first call "
                       f"{first:.2f}s, second {again * 1e3:.2f}ms")
    del ref_out, ref_grads, got, out_

    n = conf["norm"]
    x = jax.random.normal(ks[4], (n["rows"], n["dim"]),
                          jnp.float32).astype(dtype)
    scale = 1.0 + 0.1 * jax.random.normal(ks[5], (n["dim"],), jnp.float32)
    y, first, again = timed(jax.jit(ops.fused_rmsnorm), x, scale)
    checks["fused_rmsnorm"] = err(
        y, ops.rmsnorm_reference(x.astype(jnp.float32), scale))
    say("kernels", f"fused_rmsnorm {x.shape}: err "
                   f"{checks['fused_rmsnorm']:.2e}, first call {first:.2f}s, "
                   f"second {again * 1e3:.2f}ms")
    bad = {name: e for name, e in checks.items() if not e <= KERNEL_TOL}
    if bad:
        raise AssertionError(f"kernels off their references by more than "
                             f"{KERNEL_TOL}: {bad}")
    del q, k, v, g, x, y

    cfg = transformer.Config(**conf["lm"])
    opt = optax.adam(1e-3)

    @jax.jit
    def init_all(key):
        params = transformer.init(key, cfg)
        return params, opt.init(params)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, opt_state, tokens):
        loss, grads_ = jax.value_and_grad(transformer.loss_fn)(
            params, tokens, cfg)
        updates, opt_state = opt.update(grads_, opt_state)
        return optax.apply_updates(params, updates), opt_state, loss

    params, opt_state = init_all(jax.random.PRNGKey(conf["seed"]))
    tokens = jnp.asarray(
        np.random.default_rng(conf["seed"]).integers(
            0, cfg.vocab_size, (conf["lm_batch"], cfg.max_seq)), jnp.int32)
    losses, step_s = [], []
    for _ in range(3):
        t0 = time.perf_counter()
        params, opt_state, loss = train_step(params, opt_state, tokens)
        losses.append(float(loss))
        step_s.append(time.perf_counter() - t0)
    if not all(np.isfinite(losses)) or \
            not all(b < a for a, b in zip(losses, losses[1:])):
        raise AssertionError(f"decoder losses not finite and falling: "
                             f"{losses}")
    stats = jax.local_devices()[0].memory_stats() or {}
    say("kernels", f"decoder dim {cfg.dim} x {cfg.n_layers} layers, batch "
                   f"{conf['lm_batch']} x {cfg.max_seq}, attn "
                   f"{cfg.attn_impl}: losses "
                   f"{[round(x, 4) for x in losses]}, first step (compiles) "
                   f"{step_s[0]:.1f}s, later "
                   f"{[round(x, 3) for x in step_s[1:]]}s, memory_stats "
                   f"peak {stats.get('peak_bytes_in_use')}, compile cache "
                   f"{cache.report()}")
    return {"device": device, "cache": cache.report()}


# -- decode ------------------------------------------------------------------

def decode_prompts(conf):
    """Token-id prompts from the seed: two that share a block-aligned
    prefix (the second must hit the trie) and a few unrelated ones."""
    import numpy as np

    d, vocab = conf["decode"], conf["lm"]["vocab_size"]
    rng = np.random.default_rng(conf["seed"] + 1)

    def toks(n):
        return rng.integers(1, vocab, size=n).tolist()

    prefix = toks(d["prefix"])
    return ([prefix + toks(n) for n in d["tails"]],
            [toks(n) for n in d["solo"]])


def phase_decode_export(conf, out):
    """Child that owns the chip only long enough to write the export."""
    import jax

    from tensorflowonspark_tpu import tpu_info
    from tensorflowonspark_tpu.models import transformer
    from tensorflowonspark_tpu.utils import checkpoint as ckpt

    device = tpu_info.device_facts()
    require_chip(device, conf)
    cfg = transformer.Config(**conf["lm"])
    params = jax.jit(lambda key: transformer.init(key, cfg))(
        jax.random.PRNGKey(conf["seed"]))
    ckpt.export_model(os.path.join(out, "decode-export"), params,
                      metadata={})
    return {"device": device}


def phase_decode(conf, out):
    """Driver: starts the server (its replica owns the chip), talks to it
    over HTTP, and hands the served tokens to the verify child."""
    import threading
    import urllib.request

    from tensorflowonspark_tpu import serving
    from tensorflowonspark_tpu.models import transformer

    exported = run_child("decode_export", out)
    d = conf["decode"]
    cfg = transformer.Config(**conf["lm"])
    shared, solo = decode_prompts(conf)
    prompts = {f"shared{i}": p for i, p in enumerate(shared)}
    prompts.update({f"solo{i}": p for i, p in enumerate(solo)})
    spec = serving.ModelSpec(
        export_dir=os.path.join(out, "decode-export"),
        decode=serving.DecodeSpec(cfg, slots=d["slots"],
                                  max_tokens=d["max_tokens"]))
    served = {}

    def post(name):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/generate",
            data=json.dumps({"prompt": prompts[name],
                             "max_tokens": d["max_tokens"]}).encode(),
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=600) as resp:
                served[name] = json.loads(resp.read())
        except Exception as e:  # noqa: BLE001 - reported below, fatal
            served[name] = {"error": repr(e)}

    with serving.Server(spec, num_replicas=1, request_timeout=600) as srv:
        httpd = serving.serve_http(srv, port=0, block=False)
        port = httpd.server_address[1]
        try:
            # in order: the second must find the first's prefix resident
            for i in range(len(shared)):
                post(f"shared{i}")
            threads = [threading.Thread(target=post, args=(f"solo{i}",))
                       for i in range(len(solo))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            summary = srv.summary(include_replicas=True)
        finally:
            httpd.shutdown()
    failed = {n: r.get("error", "short") for n, r in served.items()
              if "error" in r or len(r.get("tokens", ())) != d["max_tokens"]}
    stats = summary["decode"]
    engine = next(iter(summary["replica_stats"].values()))["decode"]
    if failed or len(served) != len(prompts) or stats["shed"] \
            or stats["errors"] or stats["completed"] != len(prompts):
        raise AssertionError(f"decode: failed={failed} stats={stats}")
    if engine["prefix_hits"] < 1:
        raise AssertionError(f"decode: the shared prefix never hit the "
                             f"trie: {engine}")
    require_chip(engine["device"], conf)
    say("decode", f"replica device={engine['device']} sessions "
                  f"{stats['completed']} x {d['max_tokens']} tokens, none "
                  f"shed or errored; prefix hits {engine['prefix_hits']} "
                  f"({engine['prefix_tokens_saved']} prompt tokens from "
                  f"cache); ttft p50 {stats['ttft_p50_ms']}ms (first "
                  f"requests compile), token gap p50 {stats['tok_p50_ms']}ms")
    write_json(os.path.join(out, "decode-served.json"),
               {n: {"prompt": prompts[n], "tokens": served[n]["tokens"]}
                for n in prompts})
    verified = run_child("decode_verify", out)
    devices = [exported["device"], engine["device"], verified["device"]]
    if any(dev != devices[0] for dev in devices):
        raise AssertionError(f"decode: children disagree on the device: "
                             f"{devices}")
    return {"device": engine["device"], "cache": verified["cache"]}


def phase_decode_verify(conf, out):
    """Child that owns the chip after the server is gone: the served
    tokens against ``greedy_decode_reference``, near-ties by NEAR_TIE."""
    import functools

    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu import tpu_info
    from tensorflowonspark_tpu.models import transformer
    from tensorflowonspark_tpu.utils import checkpoint as ckpt
    from tensorflowonspark_tpu.utils import compile_cache

    cache = compile_cache.CacheCounter().install()
    device = tpu_info.device_facts()
    require_chip(device, conf)
    cfg = transformer.Config(**conf["lm"])
    pad_to = conf["decode"]["pad_to"]
    params, _meta = ckpt.load_exported(os.path.join(out, "decode-export"))
    params = jax.device_put(params)
    fwd = jax.jit(functools.partial(transformer.apply, cfg=cfg))
    exact = ties = 0
    worst = 0.0
    for name, rec in sorted(read_json(
            os.path.join(out, "decode-served.json")).items()):
        prompt, tokens = rec["prompt"], rec["tokens"]
        done = 0
        while done < len(tokens):
            history = prompt + tokens[:done]
            ref = transformer.greedy_decode_reference(
                params, history, cfg, max_tokens=len(tokens) - done,
                pad_to=pad_to)
            agree = 0
            while agree < len(ref) and ref[agree] == tokens[done + agree]:
                agree += 1
            exact += agree
            done += agree
            if done == len(tokens):
                break
            # first disagreement: a near-tie, or a wrong token
            history = prompt + tokens[:done]
            logits = fwd(params, jnp.asarray(
                [history + [0] * (pad_to - len(history))], jnp.int32)
            )[0, len(history) - 1]
            gap = float(jnp.max(logits) - logits[tokens[done]])
            worst = max(worst, gap)
            if not gap <= NEAR_TIE:
                raise AssertionError(
                    f"decode: {name} token {done} is {tokens[done]}, the "
                    f"reference says {ref[agree]} and puts the served "
                    f"token {gap:.3f} below its maximum (> {NEAR_TIE})")
            ties += 1
            done += 1
    if ties > exact:
        raise AssertionError(f"decode: {ties} near-ties against {exact} "
                             "exact tokens is not agreement")
    say("decode", f"verify device={device}: {exact} tokens equal "
                  f"greedy_decode_reference, {ties} bf16 near-ties accepted "
                  f"(served token within {NEAR_TIE} of the reference's top "
                  f"logit; widest {worst:.3f}), compile cache "
                  f"{cache.report()}")
    return {"device": device, "cache": cache.report()}


# -- four chips --------------------------------------------------------------

def group_main(args, ctx):
    """One of four executors x one chip: join the job, prove it is ONE job
    (four global devices, one local) and that the chips talk."""
    import os

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tensorflowonspark_tpu import tpu_info
    from tensorflowonspark_tpu.parallel import local_to_global, make_mesh

    env = ctx.jax_initialize()
    device = tpu_info.device_facts()
    require_chip(device, args)
    mesh = make_mesh({"data": -1})
    mine = np.full((1,), float(jax.process_index() + 1), np.float32)
    total = float(jax.jit(jnp.sum)(local_to_global(mesh, mine)))
    write_json(
        os.path.join(args["out"], f"group-{jax.process_index()}.json"),
        {"device": device, "local_devices": jax.local_device_count(),
         "processes": env["num_processes"],
         "process_index": jax.process_index(), "sum": total,
         "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS")})


def phase_four_chip(conf, out):
    """(a) one executor over four chips against one chip, same seed and
    global batch; (b) four executors x one chip as one job."""
    from tensorflowonspark_tpu import cluster as TFCluster
    from tensorflowonspark_tpu.cluster import InputMode
    from tensorflowonspark_tpu.engine import LocalEngine

    one = run_train(conf, out, "one_chip", num_chips=1, steps=3)
    if one["device"]["count"] != 1:
        raise AssertionError(f"one_chip run saw {one['device']}")
    four = run_train(conf, out, "four_chip", steps=3)
    p = four["placement"]
    if four["device"]["count"] != 4 or len(set(p["batch_devices"])) != 4 \
            or len(set(p["param_devices"])) != 4 \
            or sum(p["batch_shard_rows"]) != conf["resnet"]["batch"]:
        raise AssertionError(f"four_chip: shards not on four distinct "
                             f"devices: {four['device']} {p}")
    if one["ids_checksum"] != four["ids_checksum"]:
        raise AssertionError("the two runs saw different batches; their "
                             "losses cannot be compared")
    rel = [abs(a - b) / abs(a) for a, b in zip(one["losses"], four["losses"])]
    say("four_chip", f"(a) batch shards {p['batch_shard_rows']} on devices "
                     f"{p['batch_devices']}, state on {p['param_devices']}; "
                     f"losses 1 chip {one['losses']} vs 4 chips "
                     f"{four['losses']}, relative difference "
                     f"{[round(x, 5) for x in rel]} (tolerance {LOSS_RTOL})")
    if not max(rel) <= LOSS_RTOL:
        raise AssertionError(f"four_chip: losses differ by {rel}")

    engine = LocalEngine(4, env=executor_env(conf, 1))
    try:
        cluster = TFCluster.run(
            engine, group_main, {"out": out, "rehearse": conf["rehearse"]},
            num_executors=4, num_chips=1,
            input_mode=InputMode.TENSORFLOW, master_node="chief")
        cluster.shutdown()
    finally:
        engine.stop()
    group = [read_json(os.path.join(out, f"group-{i}.json"))
             for i in range(4)]
    say("four_chip", f"(b) 4 executors x 1 chip: {group}")
    for g in group:
        if g["device"]["count"] != 4 or g["local_devices"] != 1 \
                or g["processes"] != 4 or g["sum"] != 10.0:
            raise AssertionError(f"four executors did not form one "
                                 f"four-device job: {g}")
    return {"device": four["device"], "cache": four["cache"]}


# -- the parent --------------------------------------------------------------

PHASES = {"train_fed": phase_train_fed, "kernels": phase_kernels,
          "decode": phase_decode, "decode_export": phase_decode_export,
          "decode_verify": phase_decode_verify, "four_chip": phase_four_chip}


def run_child(phase, out, own_group=False):
    """Run one phase in a fresh process and return the result it wrote.
    The parent gives each phase its own process group and kills the group
    when the phase ends, so that whatever the phase started dies with it;
    a phase's own children stay inside that group."""
    result = os.path.join(out, f"{phase}.result.json")
    if os.path.exists(result):
        os.remove(result)
    t0 = time.monotonic()
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--phase", phase,
         "--out", out], start_new_session=own_group)
    try:
        rc = proc.wait(timeout=PHASE_TIMEOUT_S)
    except subprocess.TimeoutExpired:
        rc = f"timeout after {PHASE_TIMEOUT_S}s"
    finally:
        try:
            if own_group:
                os.killpg(proc.pid, signal.SIGKILL)
            else:
                proc.kill()
        except ProcessLookupError:
            pass
        proc.wait()
    if rc != 0 or not os.path.exists(result):
        raise RuntimeError(f"phase {phase} failed (exit {rc})")
    res = read_json(result)
    say(phase, f"ok in {time.monotonic() - t0:.1f}s")
    return res


def child_main(phase, out):
    conf = read_json(os.path.join(out, "conf.json"))
    res = PHASES[phase](conf, out)
    write_json(os.path.join(out, f"{phase}.result.json"), res)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the four-chip path and its one-chip "
                         "comparison (run by the builder on four chips)")
    ap.add_argument("--rehearse", action="store_true",
                    help="sandbox rehearsal: tiny shapes, CPU, interpreted "
                         "kernels; can never report platform tpu")
    ap.add_argument("--phases", default=None,
                    help="comma-separated subset of the one-chip phases "
                         "(debugging; the last line then lists them)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=os.path.join(ROOT, "chip_smoke_out"))
    ap.add_argument("--phase", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    out = os.path.abspath(args.out)
    if args.phase:
        return child_main(args.phase, out)

    from tensorflowonspark_tpu.utils import compile_cache

    os.makedirs(out, exist_ok=True)
    cache_dir = compile_cache.export_env()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.chips}")
        # lets the rehearsal claim chips the way the chip run does
        os.environ["TFOS_TPU_CHIPS_PER_HOST"] = str(args.chips)
        # CPU programs stay out of the chip's compile cache
        os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    default = ["four_chip"] if args.chips == 4 else \
        ["train_fed", "kernels", "decode"]
    phases = args.phases.split(",") if args.phases else default
    conf = dict(TINY if args.rehearse else FULL, seed=args.seed,
                rehearse=args.rehearse)
    say("parent", f"phases {phases}, rehearse={args.rehearse}, compile cache "
                  f"{cache_dir}, out {out}")
    last = {"ok": False}
    try:
        write_json(os.path.join(out, "conf.json"), conf)
        results = {p: run_child(p, out, own_group=True) for p in phases}
        for p, res in results.items():
            c = res["cache"]
            say(p, "compile cache off (rehearsal)" if args.rehearse else
                f"compile cache: {c['requests']} programs asked for, "
                f"{c['hits']} read from the cache, {c['compiled']} compiled")
        devices = [res["device"] for res in results.values()]
        if any(dev != devices[0] for dev in devices):
            raise RuntimeError(f"phases disagree on the device: {devices}")
        if "jax" in sys.modules:
            raise RuntimeError("the parent imported jax")
        if devices[0]["platform"] == "tpu" and args.rehearse:
            raise RuntimeError("a rehearsal reported platform tpu")
        if args.chips == 4 and devices[0]["count"] != 4:
            raise RuntimeError(f"--chips 4 but the chip-owning process "
                               f"saw {devices[0]}")
        say("parent", "jax imported by the parent: False")
        last = {"ok": True, "device": devices[0]}
        if args.rehearse:
            last["rehearsal"] = True
        if phases != default:
            last["phases"] = phases
    except Exception as e:  # noqa: BLE001 - the boundary: report and fail
        import traceback

        traceback.print_exc()
        last["error"] = str(e)[:300]
    print(json.dumps(last), flush=True)
    return 0 if last["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
