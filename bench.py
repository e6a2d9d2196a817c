"""Benchmark: ResNet-50 training throughput on the attached accelerator.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"} where
value is model FLOPs utilization (MFU) of the ResNet-50 train step and
vs_baseline is relative to the BASELINE.json north-star of 0.50 MFU.
Also reports images/sec/chip inside the same line's "extra" field.
"""

import json
import os
import time

import numpy as np

from tensorflowonspark_tpu.utils import telemetry

# records per shm-ring chunk (node.FEED_CHUNK_RECORDS scale); bigger
# chunks amortize per-chunk python + copy overheads, smaller ones keep
# ring latency low — sweep with scripts/stress_fed.py
FED_CHUNK = int(os.environ.get("TFOS_FED_CHUNK", "64"))


def _feeder_main(ring_name, mgr_addr, authkey_hex, total_records, image,
                 pool=None, columnar=True):
    """Feeder child (no jax): generate (uint8 image, label) records and push
    chunks through the shm ring exactly like node.train's feeder closure —
    including its columnar chunk encoder (n-D image fields go over the
    wire as dense flattened columns; columnar=False reverts to pickled
    row lists for the A/B lane)."""
    import numpy as np

    from tensorflowonspark_tpu import manager as tfmanager
    from tensorflowonspark_tpu import node as tfnode
    from tensorflowonspark_tpu.recordio import shm as shmq

    if telemetry.enabled():
        # same schema as cluster nodes, opt-in via TFOS_TELEMETRY_DIR
        # (inherited through the spawn env)
        telemetry.configure(node_id=f"feeder-{os.getpid()}", role="feeder")
    if columnar:
        encode = tfnode._ChunkEncoder()
    else:
        def encode(chunk):
            return chunk
    mgr = tfmanager.connect(tuple(mgr_addr), bytes.fromhex(authkey_hex))
    ring = shmq.ShmQueue(ring_name, create=False, producer=True)
    rng = np.random.default_rng(0)
    # pool MUST exceed the chunk size: with repeats inside one chunk,
    # pickle memoizes the duplicate array references and the row-path
    # wire volume collapses to pool-size unique images — flattering the
    # row path by 4x in round-3 measurements
    pool = pool or 2 * FED_CHUNK
    images = [rng.integers(0, 256, (image, image, 3), dtype=np.uint8)
              for _ in range(pool)]
    sent = 0
    chunk = []
    with telemetry.span("feeder/push", records=total_records,
                        columnar=columnar):
        while sent < total_records:
            chunk.append((images[sent % pool], sent % 1000))
            sent += 1
            if len(chunk) >= FED_CHUNK:
                ring.put(encode(chunk))
                chunk = []
        if chunk:
            ring.put(encode(chunk))
        ring.put(None)  # end-of-feed marker
    ring.close()
    mgr.set("feeder_done", 1)
    telemetry.flush()


def _fed_setup(batch, image, steps, columnar=True, tag="", target=None,
               extra=(), rec_bytes=None):
    """Pre-jax setup of the fed pipeline: IPC manager + shm ring + a real
    feeder process.  Must run before this process initializes a jax
    backend: the manager server is forked, and a fork after the
    accelerator runtime has started its threads is unsafe.  The feeder
    child never imports jax, so it never competes for the chip.

    ``target`` swaps the feeder entry point (default ``_feeder_main``);
    a custom target is called with ``(ring_name, mgr_addr, authkey_hex,
    total_records, *extra)`` and ``rec_bytes`` sizes the ring for its
    record width (stress_fed's pipeline A/B lanes use this)."""
    import multiprocessing as mp
    import secrets

    from tensorflowonspark_tpu import manager as tfmanager
    from tensorflowonspark_tpu.recordio import shm as shmq

    if not shmq.available():
        raise RuntimeError(
            "the fed lane needs the native shm ring (make -C native); "
            "there is no slower path to fall back to")
    authkey = secrets.token_bytes(16)
    mgr = tfmanager.start(authkey, ["input", "output", "error", "control"])
    ring_name = f"/tfos-bench-{os.getpid():x}{tag}"
    # modest capacity on purpose: a huge ring would let the feeder run
    # steps ahead during compile and overstate steady-state throughput.
    # Must hold several chunks or producer/consumer serialize — scale
    # with TFOS_FED_CHUNK (env TFOS_FED_RING_MB overrides).
    ring_mb = int(os.environ.get(
        "TFOS_FED_RING_MB",
        str(max(64, 6 * FED_CHUNK * (rec_bytes or image * image * 3)
                // (1 << 20)))))
    ring = shmq.ShmQueue(ring_name, ring_mb << 20, create=True)
    mgr.set("shm_input", ring_name)
    total = (steps + 2) * batch  # +2 warmup batches
    ctx = mp.get_context("spawn")
    if target is None:
        args = (ring_name, list(mgr.address), authkey.hex(), total,
                image, None, columnar)
    else:
        args = (ring_name, list(mgr.address), authkey.hex(),
                total) + tuple(extra)
    proc = ctx.Process(target=target or _feeder_main, args=args,
                       daemon=True)
    proc.start()
    return {"mgr": mgr, "ring": ring, "proc": proc, "steps": steps,
            "batch": batch, "image": image, "columnar": columnar}


def _fed_run(fed, step_fn, params, state, opt_state, loop_ips=None,
             xfer_ips=None):
    """Train from the fed pipeline on the device; report fed throughput,
    infeed stall, the device-resident per-dispatch comparator, and the
    raw host→device transfer ceiling.

    ``loop_ips``/``xfer_ips``: pass the comparator numbers from an
    earlier lane (same step_fn/shapes) to skip re-measuring them — the
    A/B counter-lane must not double the per-dispatch device time spent
    on fed benching."""
    import jax
    import numpy as np

    from tensorflowonspark_tpu.feed import DataFeed
    from tensorflowonspark_tpu.infeed import device_feed
    from tensorflowonspark_tpu.utils.metrics import TrainMetrics

    batch, image, steps = fed["batch"], fed["image"], fed["steps"]
    fed_step = jax.jit(step_fn, donate_argnums=(0, 1, 2))
    p, s, o = params, state, opt_state

    if loop_ips is None:
        # comparator: same per-dispatch step loop, device-resident batch
        rng = np.random.default_rng(0)
        res_imgs = jax.device_put(
            rng.integers(0, 256, (batch, image, image, 3), dtype=np.uint8)
        )
        res_labels = jax.device_put(
            rng.integers(0, 1000, batch).astype(np.int32))
        p, s, o, loss, _ = fed_step(p, s, o, res_imgs, res_labels)  # compile
        float(loss)  # value fetch: waits for the step that produced it
        t0 = time.perf_counter()
        for _ in range(steps):
            p, s, o, loss, _ = fed_step(p, s, o, res_imgs, res_labels)
        float(loss)
        loop_ips = batch * steps / (time.perf_counter() - t0)

    if xfer_ips is None:
        # raw host→device transfer ceiling: device_put of a full uint8
        # batch, no compute.  vs_transfer_ceiling then separates what
        # the pipeline costs from what the host→device link allows.
        rng = np.random.default_rng(1)
        xfer_steps = int(os.environ.get("TFOS_BENCH_FED_XFER_STEPS",
                                        str(min(steps, 2))))
        bufs = [rng.integers(0, 256, (batch, image, image, 3),
                             dtype=np.uint8) for _ in range(2)]
        # a 1-element readback after each put proves the whole buffer
        # arrived, at the cost of one tiny round trip
        int(jax.device_put(bufs[0])[0, 0, 0, 0])  # warm the path
        t0 = time.perf_counter()
        for i in range(xfer_steps):
            int(jax.device_put(bufs[i % 2])[0, 0, 0, 0])
        xfer_ips = batch * xfer_steps / (time.perf_counter() - t0)

    metrics = TrainMetrics()
    feed = DataFeed(fed["mgr"], train_mode=True,
                    input_mapping={"image": "image", "label": "label"},
                    metrics=metrics)

    # watchdog: a feeder that dies without pushing the end-of-feed None
    # would block the consumer forever — unblock it by closing the feed
    import threading

    stop_watch = threading.Event()

    def watchdog():
        fed["proc"].join()
        if fed["proc"].exitcode not in (0, None) and not stop_watch.is_set():
            import sys

            from tensorflowonspark_tpu.recordio import shm as shmq

            print(f"bench: feeder died rc={fed['proc'].exitcode}, "
                  "closing feed", file=sys.stderr, flush=True)
            try:
                shmq.ShmQueue(fed["ring"].name, create=False,
                              producer=True).put(None)
            except Exception:  # noqa: BLE001 - consumer may already be done
                pass

    threading.Thread(target=watchdog, daemon=True).start()

    # wedge watchdog: a feeder that stalls while alive (no records, no
    # exit) must not turn the whole unattended bench into a hang.  The
    # deadline is on PROGRESS, not wall clock — it resets every batch —
    # and firing is loud: logged and flagged in the lane's result.
    deadline_s = float(os.environ.get("TFOS_BENCH_FED_DEADLINE", "900"))
    progress = {"n": -1, "deadline_hit": False}

    def stall_watch():
        import sys

        last = (progress["n"], time.monotonic())
        while not stop_watch.wait(min(15.0, deadline_s / 4 or 1)):
            now = time.monotonic()
            if progress["n"] != last[0]:
                last = (progress["n"], now)
            elif now - last[1] > deadline_s:
                progress["deadline_hit"] = True
                print(f"bench: fed lane made no progress for "
                      f"{deadline_s:.0f}s; ending it early",
                      file=sys.stderr, flush=True)
                feed.poison()
                return

    threading.Thread(target=stall_watch, daemon=True).start()

    columnar = fed["columnar"]
    if columnar:
        # dense-array pull: aligned chunks pass through zero-copy, the
        # per-record python loop + np.stack (the 12k img/s wall, PERF.md)
        # is gone from the consumer hot path
        def collate(cols):
            return cols["image"], np.asarray(cols["label"], np.int32)
    else:
        def collate(cols):
            return np.stack(cols["image"]), np.asarray(cols["label"],
                                                       np.int32)

    nsteps = 0
    n_timed = 0
    t0 = None
    wait_base = 0.0
    last = None
    for imgs, labels in device_feed(feed, batch, collate=collate, depth=2,
                                    columnar=columnar):
        p, s, o, last, _ = fed_step(p, s, o, imgs, labels)
        nsteps += 1
        progress["n"] = nsteps
        if nsteps == 1:
            float(last)  # absorb warmup/compile skew (value fetch)
            t0 = time.perf_counter()
            wait_base = metrics.infeed_time  # align stall window with dt
        else:
            n_timed += 1
    stop_watch.set()
    if last is None or n_timed == 0:  # feeder died before one full batch
        rc = fed["proc"].exitcode
        fed["mgr"].set("state", "stopped")
        fed["ring"].close()
        return {"error": f"no fed batches completed (feeder exitcode={rc})"}
    float(last)
    dt = time.perf_counter() - t0
    fed_ips = batch * n_timed / dt
    stall = max(metrics.infeed_time - wait_base, 0.0)

    fed["proc"].join(timeout=10)
    if fed["proc"].is_alive():
        fed["proc"].kill()
    fed["mgr"].set("state", "stopped")
    fed["ring"].close()

    # with depth-2 double buffering the best the fed path can do is the
    # slower of (pure transfer, pure compute); against a serialized link
    # it is the harmonic combination — report the optimistic one
    ceiling = min(xfer_ips, loop_ips) if xfer_ips and loop_ips else None
    out = {
        "images_per_sec_per_chip": round(fed_ips, 1),
        "loop_images_per_sec": round(loop_ips, 1),
        "transfer_images_per_sec": round(xfer_ips, 1) if xfer_ips else None,
        "vs_device_resident": round(fed_ips / loop_ips, 4) if loop_ips else None,
        "vs_transfer_ceiling": round(fed_ips / ceiling, 4) if ceiling else None,
        "infeed_wait_s": round(stall, 3),
        "infeed_stall_frac": round(stall / dt, 4) if dt else None,
        "steps": n_timed, "chunk_records": FED_CHUNK,
        "columnar": columnar,
    }
    if progress["deadline_hit"]:
        out["deadline_hit"] = True  # truncated lane: numbers are partial
    return out


def _on_tpu_guess():
    """Pre-jax platform guess: the fed lanes fork their IPC manager and
    spawn their feeders before this process initializes a backend, so
    the workload is sized before the platform is known.  main() turns a
    wrong guess into an error.  Chip discovery delegates to tpu_info
    (stdlib-only import, honors TFOS_TPU_CHIPS_PER_HOST)."""
    from tensorflowonspark_tpu import tpu_info

    plat = os.environ.get("JAX_PLATFORMS", "").lower()
    if plat in ("cpu",):
        return False
    return bool(plat) or tpu_info.count_chips() > 0


def bench_config_path():
    """THE bench_config.json location (TFOS_BENCH_CONFIG overrides the
    repo-root default).  Single source of truth — the sweep scripts'
    --promote writers and the session script's arg emitter all resolve
    through here so producer and consumer can never drift apart."""
    return os.environ.get("TFOS_BENCH_CONFIG") or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "bench_config.json")


_RUN_ID = None


def run_stamp():
    """Identity keys stamped onto THE one JSON line: a per-run id plus
    the telemetry sink it spooled to (null when telemetry was off), so a
    bench artifact can be joined to its trace spool after the fact.
    bench_check reads only the lane paths it names and ignores unknown
    top-level keys, so the stamp is compare-safe (tested in
    tests/test_obs.py)."""
    global _RUN_ID
    if _RUN_ID is None:
        _RUN_ID = time.strftime("%Y%m%dT%H%M%S") + "-" + os.urandom(3).hex()
    return {"run_id": _RUN_ID,
            "telemetry_dir": os.environ.get(telemetry.DIR_ENV)}


def _promoted_config():
    """Optional bench_config.json at the repo root: sweep winners
    applied to the TPU bench without code edits.  Top-level keys are the
    ResNet config (scripts/sweep_resnet.py --promote); the "transformer"
    sub-dict is the transformer sweep's winner
    (scripts/sweep_transformer.py --promote).  TFOS_BENCH_* env vars
    still win over promoted values."""
    path = bench_config_path()
    if not os.path.exists(path):
        return {}
    try:
        with open(path) as f:
            cfg = json.load(f)
        return cfg if isinstance(cfg, dict) else {}
    except (OSError, ValueError) as e:
        import sys

        print(f"bench: ignoring unreadable bench_config.json: {e}",
              file=sys.stderr, flush=True)
        return {}


# Lanes whose replicas, hosts or subprocess are pinned to the CPU because
# the bench parent owns the chip: their counts hold, their times are not
# device numbers, and each result carries "platform": "cpu" to say so.
_CPU_PINNED_LANES = ("serve", "elastic_serve", "deploy", "decode",
                     "serve_fabric", "elastic")


def main():
    from tensorflowonspark_tpu.utils import compile_cache

    # children (feeders, replicas, subprocess lanes) inherit the cache
    compile_cache.export_env()
    if os.environ.get(telemetry.DIR_ENV):
        # opt-in: the bench emits the same span schema as cluster nodes
        # so trace_merge.py can lay a bench run on the same timeline
        telemetry.configure(node_id="bench", role="bench")
    on_tpu = _on_tpu_guess()
    promoted = _promoted_config() if on_tpu else {}
    batch = int(os.environ.get(
        "TFOS_BENCH_BATCH",
        promoted.get("batch", 256) if on_tpu else 16))
    image = int(os.environ.get(
        "TFOS_BENCH_IMAGE",
        promoted.get("image", 224) if on_tpu else 64))
    steps = int(os.environ.get("TFOS_BENCH_STEPS", "20" if on_tpu else "3"))
    stem_s2d = os.environ.get(
        "TFOS_BENCH_STEM_S2D",
        "1" if promoted.get("stem_s2d", True) else "0") != "0"
    remat = os.environ.get(
        "TFOS_BENCH_REMAT",
        "1" if promoted.get("remat", False) else "0") != "0"
    # resolved AFTER backend init (actual platform, not the guess):
    # default FALSE on TPU unless a sweep promoted it — the fused-BN graph
    # must never make its TPU debut inside the unattended round-end bench
    bn_fused_env = os.environ.get("TFOS_BENCH_BN_FUSED")

    # fed lanes first: each forks its IPC manager server and spawns its
    # feeder, and both must exist before this process starts the
    # accelerator runtime and its threads.  The counter-lane's feeder
    # (row-list wire + np.stack consumer) just blocks on its full ring
    # until its lane runs.  A set-up failure is a failure of the run.
    fed_ctx = fed_ctx_rows = None
    if os.environ.get("TFOS_BENCH_FED", "1") != "0":
        columnar = os.environ.get("TFOS_BENCH_FED_COLUMNAR", "1") != "0"
        fed_ctx = _fed_setup(batch, image, steps, columnar=columnar)
        if columnar and os.environ.get("TFOS_BENCH_FED_AB", "1") != "0":
            fed_ctx_rows = _fed_setup(batch, image, steps,
                                      columnar=False, tag="-rows")

    init_t0 = time.perf_counter()
    import jax
    import jax.numpy as jnp
    import optax

    from tensorflowonspark_tpu.models import resnet

    dev = jax.devices()[0]
    telemetry.record_span("bench/backend_init",
                          time.perf_counter() - init_t0,
                          platform=dev.platform)
    if (dev.platform != "cpu") != on_tpu:
        sized_for = "an accelerator" if on_tpu else "the CPU"
        raise RuntimeError(
            f"bench sized its workload for {sized_for} "
            f"but jax came up on {dev.platform!r}: toy sizes never run "
            "under chip names, nor the reverse (set JAX_PLATFORMS, or "
            "TFOS_TPU_CHIPS_PER_HOST where the chips' device nodes are "
            "not visible)")
    extra = {}

    from jax import lax

    # init under one jit program: eager init is hundreds of tiny
    # dispatches, each compiled on its own
    opt = optax.sgd(0.1, momentum=0.9)

    @jax.jit
    def init_all(key):
        params, state = resnet.init(key, depth=50, num_classes=1000)
        return params, state, opt.init(params)

    params, state, opt_state = init_all(jax.random.PRNGKey(0))
    bn_fused = (bn_fused_env != "0") if bn_fused_env is not None \
        else bool(promoted.get("bn_fused", not on_tpu))
    step_fn = resnet.make_train_step(opt, depth=50, stem_s2d=stem_s2d,
                                     remat=remat, bn_fused=bn_fused)

    rng = np.random.default_rng(0)
    images = jnp.asarray(rng.random((batch, image, image, 3), dtype=np.float32),
                         dtype=jnp.bfloat16)
    labels = jnp.asarray(rng.integers(0, 1000, batch), dtype=jnp.int32)

    # Chain `steps` train steps inside one jit (lax.scan): one dispatch,
    # one result fetch — honest device time, no per-step host round-trips
    # (and immune to async-dispatch timing artifacts).
    @jax.jit
    def run_steps(params, state, opt_state, images, labels):
        def body(carry, _):
            p, s, o = carry
            p, s, o, loss, _acc = step_fn(p, s, o, images, labels)
            return (p, s, o), loss

        (p, s, o), losses = lax.scan(body, (params, state, opt_state),
                                     None, length=steps)
        return losses[-1]

    with telemetry.span("bench/resnet_scan", batch=batch, image=image,
                        steps=steps):
        dt, loss = _time_scanned(run_steps, params, state, opt_state,
                                 images, labels)
    imgs_per_sec = batch * steps / dt
    # fwd+bwd ≈ 3x forward FLOPs
    flops_per_img = 3.0 * resnet.flops_per_image(50, image)
    achieved = imgs_per_sec * flops_per_img
    mfu = _mfu(achieved, dev)

    extra.update({
        "images_per_sec_per_chip": round(imgs_per_sec, 1),
        "batch": batch, "image": image, "steps": steps,
        "stem_s2d": stem_s2d, "remat": remat, "bn_fused": bn_fused,
        "device": str(dev), "platform": dev.platform,
        "loss": loss,
    })
    if fed_ctx is not None:
        # the north-star metric is *fed* (InputMode.SPARK-ingestion)
        # throughput: feeder process -> shm ring -> DataFeed -> device
        try:
            with telemetry.span("bench/fed", batch=batch, image=image):
                extra["fed"] = _fed_run(fed_ctx, step_fn, params, state,
                                        opt_state)
        except Exception as e:  # noqa: BLE001 - report, don't mask resnet
            extra["fed"] = {"error": str(e)[:200]}
    if fed_ctx_rows is not None:
        # row-wire counter-lane: same train step, pickled row lists +
        # np.stack consumer — the A/B lands in ONE bench line
        try:
            # the first fed lane DONATED the train state; re-init
            # (compile-cached, so this is one cheap dispatch)
            p2, s2, o2 = init_all(jax.random.PRNGKey(0))
            with telemetry.span("bench/fed_rows", batch=batch,
                                image=image):
                extra["fed_rows"] = _fed_run(
                    fed_ctx_rows, step_fn, p2, s2, o2,
                    loop_ips=extra.get("fed", {}).get(
                        "loop_images_per_sec"),
                    xfer_ips=extra.get("fed", {}).get(
                        "transfer_images_per_sec"))
        except Exception as e:  # noqa: BLE001
            extra["fed_rows"] = {"error": str(e)[:200]}
        a = extra.get("fed", {}).get("images_per_sec_per_chip")
        b = extra.get("fed_rows", {}).get("images_per_sec_per_chip")
        if a and b:
            extra["fed_rows"]["columnar_speedup"] = round(a / b, 3)

    if os.environ.get("TFOS_BENCH_TRANSFORMER", "1") != "0":
        try:
            with telemetry.span("bench/transformer"):
                extra["transformer"] = _transformer_bench(dev, on_tpu)
        except Exception as e:  # noqa: BLE001 - secondary metric only
            extra["transformer"] = {"error": str(e)[:200]}

    # BASELINE.json configs 2/4/5: TFRecord direct read, segmentation,
    # batch inference — small first-number runs, each independent
    for name, fn in (("tfrecord_read", _tfrecord_bench),
                     ("segmentation", _segmentation_bench),
                     ("batch_inference", _inference_bench),
                     ("serve", _serve_bench),
                     ("elastic_serve", _elastic_serve_bench),
                     ("deploy", _deploy_bench),
                     ("decode", _decode_bench),
                     ("serve_fabric", _fabric_bench),
                     ("data", _data_bench),
                     ("elastic", _elastic_bench),
                     ("actors", _actors_bench)):
        if os.environ.get(f"TFOS_BENCH_{name.upper()}", "1") != "0":
            try:
                with telemetry.span(f"bench/{name}"):
                    extra[name] = fn(dev, on_tpu)
                if name in _CPU_PINNED_LANES:
                    extra[name]["platform"] = "cpu"
            except Exception as e:  # noqa: BLE001 - secondary metric only
                extra[name] = {"error": str(e)[:200]}

    telemetry.flush()
    try:
        # watchtower roll-up (anomalies seen across every lane's monitor,
        # max straggler skew) — a non-lane key like run_stamp, proven
        # ignored by bench_check in tests/test_health.py
        from tensorflowonspark_tpu.obs import health as _health

        health_block = _health.process_summary()
    except Exception as e:  # noqa: BLE001 - the artifact line must go out
        health_block = {"error": str(e)[:200]}
    print(json.dumps({
        "metric": "resnet50_train_mfu",
        # null on a CPU: it has no peak in the table, and a CPU run
        # yields counts and correctness, never a utilization
        "value": mfu,
        "unit": "fraction_of_peak",
        "vs_baseline": None if mfu is None else round(mfu / 0.50, 4),
        "extra": extra,
        "health": health_block,
        **run_stamp(),
    }))


def _time_scanned(run, *args):
    """Compile+warm one jitted scanned-steps fn, then time a second call.
    Returns (seconds, last_loss) — the shared harness for every model
    section in this file."""
    loss = float(run(*args))  # compile + warmup
    t0 = time.perf_counter()
    loss = float(run(*args))
    return time.perf_counter() - t0, loss


def _mfu(flops_per_sec, dev, digits=4):
    """Share of ``dev``'s bf16 peak from THE table
    (utils.metrics.PEAK_FLOPS).  None on a CPU, which has no entry; an
    accelerator the table does not know raises there — never a default
    peak."""
    from tensorflowonspark_tpu.utils import metrics as M

    peak = M.peak_flops(dev)
    return None if peak is None else round(flops_per_sec / peak, digits)


def _transformer_bench(dev, on_tpu):
    """Secondary metric: decoder-only transformer train-step throughput
    with the pallas flash-attention kernel (tokens/sec/chip + MFU)."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax import lax

    from tensorflowonspark_tpu.models import transformer
    from tensorflowonspark_tpu.utils import metrics as M

    promoted = (_promoted_config().get("transformer", {})
                if on_tpu else {})
    if on_tpu:
        # base config fits one v5e with f32 adam state; the sweep's
        # winner (scripts/sweep_transformer.py --promote) can raise
        # batch / change flash blocks / enable remat via
        # bench_config.json's "transformer" section.  attn="reference"
        # is the sweep's recorded fallback when the compiled pallas
        # forward failed on this backend.
        cfg = transformer.Config(
            vocab_size=16384, dim=1024, n_layers=8, n_heads=8,
            max_seq=int(promoted.get("seq", 2048)), dtype="bfloat16",
            attn_impl=promoted.get("attn", "flash"),
        )
        batch, steps = int(promoted.get("batch", 8)), 10
    else:
        cfg = transformer.Config(
            vocab_size=512, dim=128, n_layers=2, n_heads=4, max_seq=128,
            dtype="float32", attn_impl="reference",
        )
        batch, steps = 2, 3
    # bool or the selective policy name "dots" — pass through (int 1
    # must coerce: `1 in (True,)` is True but `1 is True` is not)
    remat = promoted.get("remat", False)
    if remat != "dots":
        remat = bool(remat)
    ce_impl = ("blockwise" if promoted.get("ce") == "block" else "dense")
    attn_fn = None
    if (promoted.get("block_q") or promoted.get("block_kv")) \
            and promoted.get("attn", "flash") == "flash":
        import functools

        from tensorflowonspark_tpu import ops

        attn_fn = functools.partial(
            ops.flash_attention, causal=True,
            block_q=int(promoted.get("block_q", 512)),
            block_kv=int(promoted.get("block_kv", 512)),
            bwd_impl=promoted.get("bwd", "xla"))

    opt = optax.adam(1e-3)

    @jax.jit
    def init_all(key):
        params = transformer.init(key, cfg)
        return params, opt.init(params)

    params, opt_state = init_all(jax.random.PRNGKey(0))
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size,
                                          (batch, cfg.max_seq)),
        jnp.int32,
    )

    @jax.jit
    def run(params, opt_state, tokens):
        def body(carry, _):
            p, o = carry
            loss, grads = jax.value_and_grad(transformer.loss_fn)(
                p, tokens, cfg, attn_fn=attn_fn, remat=remat,
                ce_impl=ce_impl, ce_block=min(2048, cfg.vocab_size),
            )
            updates, o = opt.update(grads, o)
            return (optax.apply_updates(p, updates), o), loss
        (p, o), losses = lax.scan(body, (params, opt_state), None,
                                  length=steps)
        return losses[-1]

    dt, loss = _time_scanned(run, params, opt_state, tokens)
    toks_per_sec = batch * cfg.max_seq * steps / dt
    flops_per_tok = M.transformer_flops_per_token(cfg)
    out = {
        "tokens_per_sec_per_chip": round(toks_per_sec, 1),
        "mfu": _mfu(toks_per_sec * flops_per_tok, dev),
        # honest denominator for causal-skipping kernels: attention
        # counted at the algorithmically required (causal) half
        "mfu_causal_flops": _mfu(
            toks_per_sec * M.transformer_flops_per_token(cfg, causal=True),
            dev),
        "dim": cfg.dim, "layers": cfg.n_layers, "seq": cfg.max_seq,
        "batch": batch, "loss": loss,
    }
    if remat:
        out["remat"] = remat
    if ce_impl != "dense":
        out["ce"] = "block"  # same spelling as the promoted config
    if promoted:
        out["promoted"] = {k: promoted[k] for k in sorted(promoted)}
    return out


def _tfrecord_bench(dev, on_tpu):
    """BASELINE config #2: InputMode.TENSORFLOW equivalent — TFRecord
    direct read -> host decode/batch -> device train (MNIST shape)."""
    import shutil
    import tempfile

    import jax
    import optax

    from tensorflowonspark_tpu import dfutil, recordio
    from tensorflowonspark_tpu.models import mnist

    n_rec = 8192 if on_tpu else 1024
    batch = 256 if on_tpu else 64
    tmp = tempfile.mkdtemp(prefix="tfos_bench_tfr_")
    try:
        rng = np.random.default_rng(0)
        feats = rng.random((n_rec, 784)).astype(np.float32)
        labels = rng.integers(0, 10, n_rec).astype(np.int64)
        path = os.path.join(tmp, "part-r-00000")
        with recordio.TFRecordWriter(path) as w:
            for i in range(n_rec):
                w.write(recordio.encode_example({
                    "image": ("float", feats[i].tolist()),
                    "label": ("int64", [int(labels[i])]),
                }))

        # read+decode rate (records/s) through the production reader
        # (schema inferred once, then per-record decode — dfutil.py:140-163)
        t0 = time.perf_counter()
        rows, _schema = dfutil.load_tfrecords(None, tmp)
        read_dt = time.perf_counter() - t0
        assert len(rows) == n_rec

        # bulk columnar load (the TPU-first direct-read fast path):
        # one C pass -> dense arrays, np-sliced into device batches below
        t0 = time.perf_counter()
        cols = dfutil.load_tfrecords_columnar(tmp)
        col_dt = time.perf_counter() - t0
        imgs_all = cols["image"].reshape(-1, 28, 28, 1)
        labels_all = cols["label"].astype(np.int32)
        assert imgs_all.shape[0] == n_rec

        params = mnist.init_params(jax.random.PRNGKey(0))
        opt = optax.sgd(0.1, momentum=0.9)
        opt_state = opt.init(params)
        step = jax.jit(mnist.make_train_step(opt), donate_argnums=(0, 1))

        def batches():
            for i in range(0, n_rec - batch + 1, batch):
                yield imgs_all[i:i + batch], labels_all[i:i + batch]

        # warmup/compile on the first batch
        it = batches()
        x, y = next(it)
        params, opt_state, loss, _ = step(params, opt_state, x, y)
        float(loss)  # value-fetch barriers (PERF.md r4)
        t0 = time.perf_counter()
        n_img = 0
        for x, y in it:
            params, opt_state, loss, _ = step(params, opt_state, x, y)
            n_img += len(y)
        float(loss)
        dt = time.perf_counter() - t0
        return {
            "decode_records_per_sec": round(n_rec / read_dt, 1),
            "columnar_records_per_sec": round(n_rec / col_dt, 1),
            "train_images_per_sec": round(n_img / dt, 1) if n_img else None,
            "records": n_rec, "batch": batch,
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _segmentation_bench(dev, on_tpu):
    """BASELINE config #4: MobileNetV2-UNet segmentation train step."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax import lax

    from tensorflowonspark_tpu.models import segmentation

    batch, size, steps = (16, 256, 10) if on_tpu else (2, 64, 2)

    opt = optax.adam(1e-3)

    @jax.jit
    def init_all(key):
        params, state = segmentation.init(key, num_classes=21)
        return params, state, opt.init(params)

    params, state, opt_state = init_all(jax.random.PRNGKey(0))
    step_fn = segmentation.make_train_step(opt)

    rng = np.random.default_rng(0)
    images = jnp.asarray(rng.random((batch, size, size, 3), np.float32),
                         jnp.float32)
    masks = jnp.asarray(rng.integers(0, 21, (batch, size, size)), jnp.int32)

    @jax.jit
    def run(params, state, opt_state, images, masks):
        def body(carry, _):
            p, s, o = carry
            p, s, o, loss = step_fn(p, s, o, images, masks)
            return (p, s, o), loss
        (_, _, _), losses = lax.scan(
            body, (params, state, opt_state), None, length=steps)
        return losses[-1]

    dt, loss = _time_scanned(run, params, state, opt_state, images, masks)
    from tensorflowonspark_tpu.utils import metrics as M

    ips = batch * steps / dt
    # MFU counts fwd+bwd ≈ 3x forward (resnet-lane convention); the
    # reported flops field stays forward-only to match the
    # metrics.segmentation_flops_per_image helper and flops_per_row
    fwd_flops = M.segmentation_flops_per_image(size, num_classes=21)
    return {
        "images_per_sec_per_chip": round(ips, 1),
        "mfu": _mfu(ips * 3.0 * fwd_flops, dev),
        "fwd_flops_per_image": fwd_flops,
        "batch": batch, "image": size, "steps": steps, "loss": loss,
    }


def _inference_bench(dev, on_tpu):
    """BASELINE config #5: Spark-ML-style cached-model batch inference
    through pipeline._run_model (marshalling + device forward)."""
    import shutil
    import tempfile

    import jax

    from tensorflowonspark_tpu import pipeline as P
    from tensorflowonspark_tpu.models import mnist
    from tensorflowonspark_tpu.utils import checkpoint as ckpt

    n_rows = 16384 if on_tpu else 1024
    tmp = tempfile.mkdtemp(prefix="tfos_bench_inf_")
    try:
        params = mnist.init_params(jax.random.PRNGKey(0))
        export = os.path.join(tmp, "export")
        ckpt.export_model(export, params, metadata={
            "predict": "tensorflowonspark_tpu.models.mnist:predict",
        })
        rng = np.random.default_rng(0)
        rows = [(list(map(float, r)),)
                for r in rng.random((n_rows, 784), np.float32)]
        args = P.Namespace({
            "export_dir": export, "batch_size": 1024,
            "input_mapping": {"features": "image"},
            "output_mapping": {"prediction": "pred"},
        })
        run = P._run_model(args)
        warm = run(iter(rows[:1024]))  # load + compile
        assert len(warm) == 1024
        t0 = time.perf_counter()
        out = run(iter(rows))
        dt = time.perf_counter() - t0
        assert len(out) == n_rows and "pred" in out[0]
        from tensorflowonspark_tpu.utils import metrics as M

        rps = n_rows / dt
        flops = M.mnist_inference_flops_per_row()  # forward only
        return {"rows_per_sec": round(rps, 1),
                "mfu": _mfu(rps * flops, dev, digits=6),
                "fwd_flops_per_row": flops,
                "rows": n_rows, "batch": 1024}
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _serve_bench(dev, on_tpu):
    """Online-serving lane (TFOS_BENCH_SERVE=0 to skip): a 2-replica
    CPU service under OPEN-LOOP Poisson load — latency p50/p99, req/s,
    shed rate, micro-batch coalescing and the per-bucket compile counts
    (docs/serving.md).  Open loop (serving/decode/loadgen.py) replaced
    the old closed-loop client burst: a closed loop self-throttles when
    the server slows, hiding queueing collapse; arrivals now fire on a
    seeded schedule regardless of outstanding requests, so the p99 is
    the one the SLO is written against.  TFOS_BENCH_SERVE_RPS sets the
    offered rate, TFOS_BENCH_SERVE_N the request count; the legacy
    TFOS_BENCH_SERVE_CLIENTS x TFOS_BENCH_SERVE_REQUESTS pair survives
    as a deprecated alias for the total when TFOS_BENCH_SERVE_N is
    unset.

    Replicas are pinned to the CPU regardless of the bench device: a
    chip belongs to one process, and the bench parent holds it.  The
    result says ``"platform": "cpu"`` until the lane runs in a
    chip-owning process of its own (ROADMAP Design 1).
    """
    import shutil
    import tempfile

    import jax

    from tensorflowonspark_tpu import serving
    from tensorflowonspark_tpu.models import mnist
    from tensorflowonspark_tpu.serving.decode import run_open_loop
    from tensorflowonspark_tpu.utils import checkpoint as ckpt

    replicas = int(os.environ.get("TFOS_BENCH_SERVE_REPLICAS", "2"))
    # deprecated alias: CLIENTS x REQUESTS was the closed-loop total
    clients = int(os.environ.get("TFOS_BENCH_SERVE_CLIENTS", "64"))
    per_client = int(os.environ.get("TFOS_BENCH_SERVE_REQUESTS", "6"))
    n_requests = int(os.environ.get("TFOS_BENCH_SERVE_N",
                                    str(clients * per_client)))
    rate_rps = float(os.environ.get("TFOS_BENCH_SERVE_RPS", "120"))
    tmp = tempfile.mkdtemp(prefix="tfos_bench_serve_")
    try:
        params = mnist.init_params(jax.random.PRNGKey(0))
        export = os.path.join(tmp, "export")
        ckpt.export_model(export, params, metadata={
            "predict": "tensorflowonspark_tpu.models.mnist:serve_predict",
        })
        spec = serving.ModelSpec(export_dir=export)
        rng = np.random.default_rng(0)
        images = rng.random((64, 28, 28, 1), np.float32)

        with serving.Server(
            spec, num_replicas=replicas, max_batch=32, max_delay_ms=5,
            env={"JAX_PLATFORMS": "cpu"},
        ) as srv:
            client = srv.client()
            # warmup: first predicts pay jax import + bucket-1 compile
            for _ in range(2):
                client.predict({"image": images[0]}, timeout=120)

            def request(i):
                # one loadgen arrival = one trace root; the in-process
                # client shares the arrival thread so the replica-bound
                # serve/predict span joins this tree via the TLS stack
                with telemetry.trace_span(telemetry.BENCH_REQUEST,
                                          lane="serve", req=i):
                    return client.predict(
                        {"image": images[i % len(images)]}, timeout=120)

            stats = run_open_loop(
                request,
                rate_rps=rate_rps, n_requests=n_requests, seed=0,
                shed_exc=serving.Overloaded)
            summ = srv.summary(include_replicas=True)

        out = {
            "requests": stats["requests"],
            "req_per_sec": stats["completed_rps"],
            "offered_rps": stats["offered_rps"],
            "p50_ms": stats["latency_p50_ms"],
            "p99_ms": stats["latency_p99_ms"],
            "shed": stats["shed"],
            "shed_rate": summ.get("shed_rate"),
            "mean_device_batch": summ.get("mean_device_batch"),
            "buckets": summ.get("buckets"),
            "replicas": replicas,
            "client_errors": stats["errors"],
        }
        compiles = {}
        for st in (summ.get("replica_stats") or {}).values():
            for sig, n in (st.get("compiles") or {}).items():
                compiles[sig] = compiles.get(sig, 0) + n
        if compiles:
            out["compiles"] = compiles
        return out
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _elastic_serve_bench(dev, on_tpu):
    """Elastic-serving lane (TFOS_BENCH_ELASTIC_SERVE=0 to skip): the
    serve lane's open-loop Poisson load against a 2-replica
    degrade-by-resize pool, with one replica SIGKILLed a third of the
    way through the arrival schedule (docs/serving.md "Degrade by
    resize").  Reports the degraded-window p99, the pool resize time
    and ``dropped`` — client-visible request errors, which the
    zero-drop contract pins at 0 (sheds are counted separately; they
    are explicit 503s, not drops).  Replicas are CPU-forced like the
    serve lane: this measures failover choreography, not the chip.
    """
    import shutil
    import signal
    import tempfile
    import threading

    import jax

    from tensorflowonspark_tpu import serving
    from tensorflowonspark_tpu.models import mnist
    from tensorflowonspark_tpu.serving.decode import run_open_loop
    from tensorflowonspark_tpu.utils import checkpoint as ckpt

    n_requests = int(os.environ.get("TFOS_BENCH_ELASTIC_SERVE_N", "240"))
    rate_rps = float(os.environ.get("TFOS_BENCH_ELASTIC_SERVE_RPS", "80"))
    tmp = tempfile.mkdtemp(prefix="tfos_bench_eserve_")
    try:
        params = mnist.init_params(jax.random.PRNGKey(0))
        export = os.path.join(tmp, "export")
        ckpt.export_model(export, params, metadata={
            "predict": "tensorflowonspark_tpu.models.mnist:serve_predict",
        })
        spec = serving.ModelSpec(export_dir=export)
        rng = np.random.default_rng(0)
        images = rng.random((64, 28, 28, 1), np.float32)

        with serving.Server(
            spec, num_replicas=2, max_batch=32, max_delay_ms=5,
            elastic=True,
            env={"JAX_PLATFORMS": "cpu"},
        ) as srv:
            client = srv.client()
            for _ in range(2):
                client.predict({"image": images[0]}, timeout=120)

            kill_at = max(1, n_requests // 3)
            killed = {"pid": None}
            deg_lock = threading.Lock()
            deg_ms = []

            def request(i):
                if i == kill_at and killed["pid"] is None:
                    live = srv.pool.live_replicas()
                    victim = srv.pool.replica_pids()[live[0]]
                    killed["pid"] = victim
                    os.kill(victim, signal.SIGKILL)
                with telemetry.trace_span(telemetry.BENCH_REQUEST,
                                          lane="elastic_serve", req=i):
                    t0 = time.perf_counter()
                    row = client.predict(
                        {"image": images[i % len(images)]}, timeout=120)
                    if srv.pool.degraded:
                        with deg_lock:
                            deg_ms.append((time.perf_counter() - t0) * 1e3)
                    return row and None

            stats = run_open_loop(
                request,
                rate_rps=rate_rps, n_requests=n_requests, seed=0,
                shed_exc=serving.Overloaded)
            # regrow: the engine respawn adopts live params, the pool
            # reshards back to full capacity — wait for it so the lane
            # reports the restored state, not a race
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                if (len(srv.pool.live_replicas()) == 2
                        and not srv.pool.degraded):
                    break
                time.sleep(0.2)
            pool = srv.pool.describe()

        deg_sorted = sorted(deg_ms)
        deg_p99 = (deg_sorted[min(len(deg_sorted) - 1,
                                  round(0.99 * (len(deg_sorted) - 1)))]
                   if deg_sorted else None)
        return {
            "requests": stats["requests"],
            "req_per_sec": stats["completed_rps"],
            "offered_rps": stats["offered_rps"],
            "p50_ms": stats["latency_p50_ms"],
            "p99_ms": stats["latency_p99_ms"],
            # degraded-window latency; falls back to overall p99 when
            # the resize outran every in-window arrival (samples says so)
            "degraded_p99_ms": (round(deg_p99, 3) if deg_p99 is not None
                                else stats["latency_p99_ms"]),
            "degraded_samples": len(deg_sorted),
            "resize_ms": pool["last_resize_ms"],
            "resizes": pool["resizes"],
            "generation": pool["generation"],
            "adoptions": pool["adoptions"],
            "regrown": pool["live"],
            "shed": stats["shed"],
            "dropped": stats["errors"],
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _deploy_probe_predict(params, inputs):
    """Module-level probe model for the deploy lane (cloudpickled into
    the CPU replicas): answers with the params version that served it."""
    x = np.asarray(inputs["x"])
    return {"version": np.full(x.shape[0],
                               float(np.asarray(params["version"])))}


def _deploy_bench(dev, on_tpu):
    """Blessed-deployment lane (TFOS_BENCH_DEPLOY=0 to skip): the serve
    lane's open-loop Poisson load against a 3-replica CPU pool while the
    deployment loop (workloads/deploy_loop.py) walks one full staged
    promotion and one full auto-rollback (docs/deployment.md).  Reports
    the end-to-end commit latency of each transition (candidate blessed
    -> pool converged), the under-rollout p99, and ``dropped`` —
    client-visible request errors across both transitions, which the
    zero-drop contract pins at 0 (bench_check gates it).  Replicas are
    CPU-forced like the serve lanes: this measures rollout
    choreography, not the chip."""
    import shutil
    import tempfile
    import threading

    from tensorflowonspark_tpu import serving
    from tensorflowonspark_tpu.serving.decode import run_open_loop
    from tensorflowonspark_tpu.utils import checkpoint as ckpt
    from tensorflowonspark_tpu.workloads.deploy_loop import DeployLoop

    n_requests = int(os.environ.get("TFOS_BENCH_DEPLOY_N", "240"))
    rate_rps = float(os.environ.get("TFOS_BENCH_DEPLOY_RPS", "60"))
    burn_secs = float(os.environ.get("TFOS_BENCH_DEPLOY_BURN", "1.0"))
    tmp = tempfile.mkdtemp(prefix="tfos_bench_deploy_")
    try:
        d = os.path.join(tmp, "ckpt")

        def publish(step, score):
            # trainer + promotion-gate surrogate: checkpoint arrives
            # already blessed (the gate itself is timed in the e2e test
            # lane, not here — this lane times the rollout)
            ckpt.save_checkpoint(
                d, {"version": np.array(float(step))}, step=step)
            ckpt.bless_checkpoint(d, step, score=score)

        publish(1, 0.5)
        spec = serving.ModelSpec(predict=_deploy_probe_predict,
                                 ckpt_dir=d, jit=False)
        x = np.zeros(8, np.float32)
        with serving.Server(
            spec, num_replicas=3, max_batch=32, max_delay_ms=5,
            env={"JAX_PLATFORMS": "cpu"},
        ) as srv:
            client = srv.client()
            for _ in range(2):
                client.predict({"x": x}, timeout=120)

            loop = DeployLoop(srv.pool, d, pct=50, canary_count=1,
                              burn_secs=burn_secs, min_samples=1,
                              lat_tol=20.0)
            loop.pump()  # bootstrap: pin the pool at step 1
            stop = threading.Event()

            def pumper():
                while not stop.is_set():
                    try:
                        loop.pump()
                    except Exception:  # noqa: BLE001 - lane must finish
                        pass
                    stop.wait(0.05)

            pump_thread = threading.Thread(target=pumper, daemon=True)
            pump_thread.start()

            def request(i):
                with telemetry.trace_span(telemetry.BENCH_REQUEST,
                                          lane="deploy", req=i):
                    return client.predict({"x": x}, timeout=120)

            def wait_for(cond, what, timeout=60):
                deadline = time.monotonic() + timeout
                while time.monotonic() < deadline:
                    if cond():
                        return
                    time.sleep(0.05)
                raise RuntimeError(f"deploy lane: {what} never landed "
                                   f"({loop.status()})")

            # phase 1: a clean candidate canaries and promotes under load
            t1 = time.perf_counter()
            publish(2, 0.45)
            stats1 = run_open_loop(
                request, rate_rps=rate_rps, n_requests=n_requests,
                seed=0, shed_exc=serving.Overloaded)
            wait_for(lambda: loop.promotions >= 2, "promotion")
            promote_s = time.perf_counter() - t1

            # phase 2: a regressed candidate auto-rolls back under load
            t2 = time.perf_counter()
            publish(3, 50.0)  # 100x the blessed score: eval regression
            stats2 = run_open_loop(
                request, rate_rps=rate_rps, n_requests=n_requests,
                seed=1, shed_exc=serving.Overloaded)
            wait_for(lambda: loop.rollbacks >= 1, "rollback")
            rollback_s = time.perf_counter() - t2
            stop.set()
            pump_thread.join(timeout=10)
            watermark = srv.pool.watermark()

        return {
            "requests": stats1["requests"] + stats2["requests"],
            "req_per_sec": round((stats1["completed_rps"]
                                  + stats2["completed_rps"]) / 2, 3),
            "p99_ms": max(stats1["latency_p99_ms"],
                          stats2["latency_p99_ms"]),
            "promote_s": round(promote_s, 3),
            "rollback_s": round(rollback_s, 3),
            "promotions": loop.promotions,
            "rollbacks": loop.rollbacks,
            "watermark": watermark,
            "shed": stats1["shed"] + stats2["shed"],
            "dropped": stats1["errors"] + stats2["errors"],
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _decode_bench(dev, on_tpu):
    """Autoregressive-decode lane (TFOS_BENCH_DECODE=0 to skip): a
    2-replica continuous-batching decode service under open-loop
    Poisson session arrivals — TTFT p50/p99, per-token-gap p50/p99 and
    aggregate tokens/s, the three SLO numbers docs/serving.md defines
    for the decode tier.

    TFOS_BENCH_DECODE_PREFIX (default 0.6) is the fraction of sessions
    that share one of a small pool of long system prompts; the lane runs
    a second arm with prefix sharing disabled on the same trace and
    reports its TTFT p50 as ``nosharing_ttft_p50_ms`` (the paged-cache
    win is TTFT p50 strictly below that arm plus a nonzero
    ``prefix_hit_rate`` / ``prefill_tokens_saved``).

    Like the serve lane, replicas are pinned to the CPU: the bench
    parent holds the chip.
    """
    import shutil
    import tempfile

    import jax

    from tensorflowonspark_tpu import serving
    from tensorflowonspark_tpu.models import transformer as _tfm
    from tensorflowonspark_tpu.serving.decode import (run_open_loop,
                                                      shared_prefix_prompts)
    from tensorflowonspark_tpu.utils import checkpoint as ckpt

    replicas = int(os.environ.get("TFOS_BENCH_DECODE_REPLICAS", "2"))
    slots = int(os.environ.get("TFOS_BENCH_DECODE_SLOTS", "8"))
    n_sessions = int(os.environ.get("TFOS_BENCH_DECODE_N", "24"))
    rate_rps = float(os.environ.get("TFOS_BENCH_DECODE_RPS", "4"))
    max_tokens = int(os.environ.get("TFOS_BENCH_DECODE_TOKENS", "16"))
    prefix_frac = float(os.environ.get("TFOS_BENCH_DECODE_PREFIX", "0.6"))
    cfg = _tfm.Config(vocab_size=512, dim=128, n_layers=2, n_heads=4,
                      max_seq=128, dtype="float32", attn_impl="reference")
    tmp = tempfile.mkdtemp(prefix="tfos_bench_decode_")
    try:
        params = _tfm.init(jax.random.PRNGKey(0), cfg)
        export = os.path.join(tmp, "export")
        ckpt.export_model(export, params, metadata={})
        prompts, pool = shared_prefix_prompts(
            n_sessions, vocab_size=cfg.vocab_size,
            prefix_frac=prefix_frac, seed=0)
        warm = pool[0] + prompts[0][-4:]
        # same pool prefix, full-width tail: compiles the trie-matched
        # extend bucket (tail bucket 16, 4 shared blocks) the measured
        # shared sessions land in
        warm_tail = pool[0] + pool[1][:16]

        def _prefix_stats(srv):
            out = {"prefix_hits": 0, "prefix_tokens_saved": 0}
            for rep in srv.summary(
                    include_replicas=True)["replica_stats"].values():
                d = (rep or {}).get("decode") or {}
                for k in out:
                    out[k] += int(d.get(k) or 0)
            return out

        def _arm(sharing):
            spec = serving.ModelSpec(
                export_dir=export,
                decode=serving.DecodeSpec(cfg, slots=slots,
                                          max_tokens=max_tokens,
                                          prefix_sharing=sharing))
            with serving.Server(
                spec, num_replicas=replicas, request_timeout=300,
                env={"JAX_PLATFORMS": "cpu"},
            ) as srv:
                # warmup: pay jax import + prefill/decode_step compiles
                # on every replica before the clock starts; two
                # same-prefix generations per replica also seed the trie
                # and warm the matched extend path when sharing is on
                for _ in range(2 * replicas):
                    srv.generate(warm, max_tokens=2, timeout=300)
                for _ in range(replicas):
                    srv.generate(warm_tail, max_tokens=2, timeout=300)
                base = _prefix_stats(srv)

                def session(i):
                    with telemetry.trace_span(telemetry.BENCH_REQUEST,
                                              lane="decode", req=i):
                        out = srv.generate(prompts[i % len(prompts)],
                                           max_tokens=max_tokens,
                                           timeout=300)
                    return {"ttft_ms": out.get("ttft_ms"),
                            "token_ms": out.get("token_ms"),
                            "tokens": len(out.get("tokens") or ())}

                stats = run_open_loop(session, rate_rps=rate_rps,
                                      n_requests=n_sessions, seed=0,
                                      shed_exc=serving.Overloaded)
                after = _prefix_stats(srv)
            return stats, {k: after[k] - base[k] for k in after}

        stats, pstats = _arm(True)
        nosharing, _ = _arm(False)

        completed = max(1, stats["completed"])
        return {
            "sessions": stats["requests"],
            "completed": stats["completed"],
            "shed": stats["shed"],
            "errors": stats["errors"],
            "offered_rps": stats["offered_rps"],
            "tokens": stats.get("tokens", 0),
            "tokens_per_sec": stats.get("tokens_per_sec", 0.0),
            "ttft_p50_ms": stats.get("ttft_p50_ms"),
            "ttft_p99_ms": stats.get("ttft_p99_ms"),
            "tok_p50_ms": stats.get("tok_p50_ms"),
            "tok_p99_ms": stats.get("tok_p99_ms"),
            "prefix_frac": prefix_frac,
            "prefix_hits": pstats["prefix_hits"],
            "prefix_hit_rate": round(
                pstats["prefix_hits"] / completed, 4),
            "prefill_tokens_saved": pstats["prefix_tokens_saved"],
            "nosharing_ttft_p50_ms": nosharing.get("ttft_p50_ms"),
            "replicas": replicas,
            "slots": slots,
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _fabric_bench(dev, on_tpu):
    """Pod-scale fabric lane (TFOS_BENCH_SERVE_FABRIC=0 to skip): the
    decode lane's open-loop Poisson sessions against a multi-host
    fabric (``Server(fabric=True)``, >=2 host processes) with stable
    per-session route ids, while (a) the autoscaler grows replicas
    1 -> N under the induced queueing and (b) the host an affinity-bound
    session targets is SIGKILLed a third of the way through the arrival
    schedule (docs/serving.md "Pod-scale fabric").  Reports p99 across
    the whole run (bench_check gates no-regression as replicas scale),
    ``dropped`` — client-visible errors, pinned at 0 by the zero-drop
    contract — plus ``affinity_hit_rate`` and the actuated
    ``scale_ups``.  Hosts are CPU-forced like every serving lane: this
    measures fabric choreography, not the chip."""
    import shutil
    import signal
    import tempfile
    import threading

    import jax

    from tensorflowonspark_tpu import serving
    from tensorflowonspark_tpu.models import transformer as _tfm
    from tensorflowonspark_tpu.serving.decode import (run_open_loop,
                                                      session_route_ids)
    from tensorflowonspark_tpu.utils import checkpoint as ckpt

    hosts = int(os.environ.get("TFOS_BENCH_FABRIC_HOSTS", "2"))
    n_sessions = int(os.environ.get("TFOS_BENCH_FABRIC_N", "48"))
    rate_rps = float(os.environ.get("TFOS_BENCH_FABRIC_RPS", "16"))
    max_tokens = int(os.environ.get("TFOS_BENCH_FABRIC_TOKENS", "12"))
    route_sessions = int(os.environ.get("TFOS_BENCH_FABRIC_SESSIONS", "8"))
    cfg = _tfm.Config(vocab_size=61, dim=32, n_layers=2, n_heads=2,
                      max_seq=64, dtype="float32", attn_impl="reference")
    tmp = tempfile.mkdtemp(prefix="tfos_bench_fabric_")
    try:
        params = _tfm.init(jax.random.PRNGKey(0), cfg)
        export = os.path.join(tmp, "export")
        ckpt.export_model(export, params, metadata={})
        spec = serving.ModelSpec(
            export_dir=export,
            decode=serving.DecodeSpec(cfg, slots=4, max_tokens=max_tokens))
        rng = np.random.default_rng(0)
        prompts = [rng.integers(1, cfg.vocab_size,
                                size=5 + i % 8).tolist()
                   for i in range(n_sessions)]
        ids = session_route_ids(n_sessions, sessions=route_sessions,
                                seed=1)
        # low=0.0 suppresses mid-run scale-DOWN so the lane measures a
        # clean 1 -> N growth; the router's LIFO retire is the slow
        # lane's business (tests/test_fabric.py)
        with serving.Server(
            spec, fabric=True, fabric_hosts=hosts, replicas_per_host=1,
            request_timeout=300, decode_queue_max=4 * n_sessions,
            autoscale={"min_replicas": 1, "max_replicas": 3,
                       "high": 1.5, "low": 0.0, "cooldown": 1.0,
                       "tick_secs": 0.2},
            env={"JAX_PLATFORMS": "cpu"},
        ) as srv:
            # warmup: pay jax import + prefill/decode compiles on every
            # host before the clock starts, and bind the kill victim
            for _ in range(2 * hosts):
                srv.generate(prompts[0], max_tokens=2, timeout=300)
            srv.generate(prompts[0], max_tokens=2, timeout=300,
                         route_id=ids[0])
            victim = srv.pool.affinity_binding(ids[0])[0]
            kill_at = max(1, n_sessions // 3)
            killed = {"pid": None}

            def session(i, route_id):
                if i == kill_at and killed["pid"] is None:
                    pid = srv.pool.host_pids().get(victim)
                    if pid:
                        killed["pid"] = pid
                        os.kill(pid, signal.SIGKILL)
                with telemetry.trace_span(telemetry.BENCH_REQUEST,
                                          lane="serve_fabric", req=i):
                    out = srv.generate(prompts[i], max_tokens=max_tokens,
                                       timeout=300, route_id=route_id)
                return {"ttft_ms": out.get("ttft_ms"),
                        "tokens": len(out.get("tokens") or ()),
                        "affinity": out.get("affinity")}

            stats = run_open_loop(session, rate_rps=rate_rps,
                                  n_requests=n_sessions, seed=0,
                                  shed_exc=serving.Overloaded,
                                  route_fn=ids.__getitem__)
            # regrow: wait for the killed host's respawn so the lane
            # reports the restored fabric, not a race
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                if len(srv.pool.live_replicas()) == hosts:
                    break
                time.sleep(0.2)
            desc = srv.pool.describe()

        return {
            "sessions": stats["requests"],
            "completed": stats["completed"],
            "req_per_sec": stats["completed_rps"],
            "offered_rps": stats["offered_rps"],
            "p50_ms": stats["latency_p50_ms"],
            "p99_ms": stats["latency_p99_ms"],
            "ttft_p50_ms": stats.get("ttft_p50_ms"),
            "ttft_p99_ms": stats.get("ttft_p99_ms"),
            "tokens_per_sec": stats.get("tokens_per_sec", 0.0),
            "shed": stats["shed"],
            "dropped": stats["errors"],
            "affinity_hit_rate": stats.get("affinity_hit_rate", 0.0),
            "affinity_hits": stats.get("affinity_hits", 0),
            "affinity_fallbacks": stats.get("affinity_fallbacks", 0),
            "hosts": hosts,
            "replicas_final": desc["replicas"],
            "scale_ups": desc["scale_ups"],
            "redispatched": desc["redispatched"],
            "respawns": desc["respawns"],
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _data_bench(dev, on_tpu):
    """Input-pipeline lane (TFOS_BENCH_DATA=0 to skip): host-side rec/s
    for the three feeding tiers over the same 784-float TFRecord shards —
    (a) raw ``dfutil.iter_tfrecords_columnar``, (b) the composed data/
    pipeline graph (interleave + map + batch + prefetch), (c) a mini
    in-process data service serving one consumer over the manager wire
    (queue transport, ledger-less).  Host-side only: never touches jax
    or the device, so it is safe alongside a TPU claim (docs/data.md)."""
    import secrets
    import shutil
    import tempfile
    import threading

    from tensorflowonspark_tpu import data, dfutil, recordio
    from tensorflowonspark_tpu import manager as tfmanager
    from tensorflowonspark_tpu.data import service as dsvc
    from tensorflowonspark_tpu.feed import DataFeed

    n = int(os.environ.get("TFOS_BENCH_DATA_RECORDS", "8192"))
    width = 784
    batch = 256
    per = max(1, n // 4)
    tmp = tempfile.mkdtemp(prefix="tfos_bench_data_")
    try:
        rng = np.random.default_rng(0)
        for s in range(4):
            base = rng.random((per, width), dtype=np.float32)
            with recordio.TFRecordWriter(
                    os.path.join(tmp, f"part-{s:05d}")) as w:
                for i in range(per):
                    w.write(recordio.encode_example(
                        {"x": ("float", base[i].tolist()),
                         "y": ("int64", [s * per + i])}))
        total = 4 * per
        out = {"records": total, "width": width, "batch": batch}

        t0 = time.perf_counter()
        seen = 0
        for cols in dfutil.iter_tfrecords_columnar(tmp, batch):
            seen += len(cols["y"])
        out["raw_records_per_sec"] = round(seen / (time.perf_counter() - t0),
                                           1)

        pipe = (data.from_tfrecords(tmp, block_size=batch)
                .interleave(cycle_length=2)
                .map(lambda b: {"x": b["x"] * (1.0 / 255.0), "y": b["y"]})
                .batch(batch)
                .prefetch(4))
        t0 = time.perf_counter()
        seen = 0
        for blk in pipe.blocks():
            seen += len(blk["y"])
        out["pipeline_records_per_sec"] = round(
            seen / (time.perf_counter() - t0), 1)

        # mini data service: one trainer stream over the manager queue,
        # drained by an in-process DataFeed consumer thread
        authkey = secrets.token_bytes(16)
        mgr = tfmanager.start(authkey, ["input", "output", "error"])
        meta = {"executor_id": 0, "host": "localhost", "job_name": "worker",
                "addr": list(mgr.address), "authkey": authkey.hex()}
        svc = dsvc.DataService(
            pipe, cluster_info=[meta],
            cluster_meta={"server_addr": ("127.0.0.1", 1)},
            qname="input", num_workers=1, worker_index=0)
        feed = DataFeed(mgr, train_mode=True,
                        input_mapping={"x": "x", "y": "y"})
        got = [0]

        def drain():
            while got[0] < total:
                cols = feed.next_batch_columns(batch)
                got[0] += len(cols.get("y", ()))

        t0 = time.perf_counter()
        consumer = threading.Thread(target=drain, daemon=True)
        consumer.start()
        svc.run()
        consumer.join(timeout=120)
        dt = time.perf_counter() - t0
        mgr.set("state", "stopped")
        out["service_records_per_sec"] = round(got[0] / dt, 1)
        out["service_records"] = got[0]

        # dynamic-split dispatch over the same wire: board + provider +
        # one DynamicDataService worker, FCFS split claims (ISSUE 19)
        from tensorflowonspark_tpu.data import splits as dsplits

        bkey = secrets.token_bytes(16)
        bmgr = tfmanager.start(bkey, [])
        akey = secrets.token_bytes(16)
        amgr = tfmanager.start(akey, ["input", "output", "error"])
        ameta = {"executor_id": 0, "host": "localhost",
                 "job_name": "worker", "addr": list(amgr.address),
                 "authkey": akey.hex()}
        board = dsplits.SplitBoard(bmgr, "input")
        board.set_plan([0])

        class _Ctx:
            def __init__(self, m):
                self.mgr = m
                self._kv = {}

            def kv_get(self, k):
                return self._kv.get(k)

            def kv_set(self, k, v):
                self._kv[k] = v

        ictx = _Ctx(bmgr)
        provider = dsplits.SplitProvider("input", server_addr=None,
                                         num_epochs=1, window=16)
        provider.on_start(ictx)
        dyn = dsvc.DynamicDataService(
            pipe, cluster_info=[ameta],
            cluster_meta={dsvc.SPLIT_BOARD_META: {
                "address": tuple(bmgr.address), "authkey": bkey}},
            qname="input", worker_index=0, use_cache=False)
        # ledger-less board: completion needs the provider to see done
        # splits, which NullLedgerClient never reports — drain by count
        dfeed = DataFeed(amgr, train_mode=True,
                         input_mapping={"x": "x", "y": "y"})
        dgot = [0]

        def ddrain():
            while dgot[0] < total:
                cols = dfeed.next_batch_columns(batch)
                dgot[0] += len(cols.get("y", ()))

        stop_tick = threading.Event()

        def dtick():
            while not stop_tick.is_set() and not board.complete():
                provider.on_tick(ictx)
                time.sleep(0.02)

        t0 = time.perf_counter()
        dconsumer = threading.Thread(target=ddrain, daemon=True)
        dworker = threading.Thread(target=dyn.run, daemon=True)
        ticker = threading.Thread(target=dtick, daemon=True)
        dconsumer.start()
        dworker.start()
        ticker.start()
        dconsumer.join(timeout=120)
        dt = time.perf_counter() - t0
        # ledger-less lane: completion is declared here, not by the
        # provider — lets the worker exit instead of idling on claims
        board.set_complete()
        stop_tick.set()
        dworker.join(timeout=30)
        amgr.set("state", "stopped")
        out["dynamic_records_per_sec"] = round(dgot[0] / dt, 1)
        out["dynamic_records"] = dgot[0]

        # shared epoch cache: decode once, replay from memory/spill
        from tensorflowonspark_tpu.data import cache as dcache

        epoch_cache = dcache.EpochCache(pipe)
        t0 = time.perf_counter()
        seen = sum(len(b["y"]) for b in epoch_cache.blocks_range())
        out["cache_cold_records_per_sec"] = round(
            seen / (time.perf_counter() - t0), 1)
        t0 = time.perf_counter()
        seen = sum(len(b["y"]) for b in epoch_cache.blocks_range())
        hit_rps = seen / (time.perf_counter() - t0)
        epoch_cache.close()
        out["cache_hit_records_per_sec"] = round(hit_rps, 1)
        if out["pipeline_records_per_sec"]:
            # the ISSUE 19 shared-cache gate: second consumer reads at
            # >= 5x the cold pipeline rec/s
            out["cache_hit_speedup"] = round(
                hit_rps / out["pipeline_records_per_sec"], 2)

        # straggler A/B (TFOS_BENCH_DATA_STRAGGLER=0 to skip): the
        # stress_fed service-dynamic lane in a scrubbed-CPU subprocess
        # (host-only: spawns consumer processes, never touches jax)
        if os.environ.get("TFOS_BENCH_DATA_STRAGGLER", "1") != "0":
            import subprocess
            import sys

            env = dict(os.environ)
            env["JAX_PLATFORMS"] = "cpu"
            root = os.path.dirname(os.path.abspath(__file__))
            proc = subprocess.run(
                [sys.executable,
                 os.path.join(root, "scripts", "stress_fed.py"),
                 "--mode", "service-dynamic"],
                capture_output=True, text=True, timeout=300, cwd=root,
                env=env)
            line = None
            for ln in reversed(proc.stdout.splitlines()):
                ln = ln.strip()
                if ln.startswith("{"):
                    line = json.loads(ln)
                    break
            if proc.returncode or line is None:
                out["straggler_error"] = (proc.stderr or proc.stdout)[-200:]
            else:
                out["straggler_ratio"] = line["straggler_ratio"]
                out["straggler_speedup"] = line["straggler_speedup"]
        return out
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _elastic_bench(dev, on_tpu):
    """Elastic-runtime lane (TFOS_BENCH_ELASTIC=0 to skip): mesh build /
    resize / reshard / cross-mesh restore latencies on 8 fake CPU
    devices (docs/elastic.md).  Runs scripts/bench_elastic.py in a
    SUBPROCESS with a scrubbed CPU env so it never contends for the TPU
    claim the main bench process may hold."""
    import subprocess
    import sys

    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
    })
    root = os.path.dirname(os.path.abspath(__file__))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "scripts", "bench_elastic.py")],
        capture_output=True, text=True, timeout=600, cwd=root, env=env)
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    if proc.returncode or not lines:
        raise RuntimeError(
            f"bench_elastic rc={proc.returncode}: "
            f"{(proc.stderr or proc.stdout)[-300:]}")
    return json.loads(lines[-1])


def _actors_bench(dev, on_tpu):
    """Actor-substrate micro-lane (TFOS_BENCH_ACTORS=0 to skip): ask
    round-trip latency through the mailbox wire and SIGKILL->respawn
    resume time on a 2-member EchoActor group (docs/actors.md).
    Members run with a scrubbed CPU env and never import jax, so the
    lane is safe alongside a TPU claim the main process holds."""
    from tensorflowonspark_tpu.actors import (
        ActorSystem, EchoActor, SupervisionPolicy,
    )

    n = int(os.environ.get("TFOS_BENCH_ACTORS_N", "200"))
    pol = SupervisionPolicy(heartbeat_secs=0.2, stale_secs=5.0,
                            tick_secs=0.1)
    with ActorSystem(2, env={"JAX_PLATFORMS": "cpu"}) as system:
        g = system.spawn(EchoActor(), "bench", count=2, policy=pol)
        for i in range(10):  # warm the wire (queue proxies, pickler)
            g.ask("echo", i).result(60)
        lat = []
        for i in range(n):
            t0 = time.perf_counter()
            g.ask("echo", i).result(60)
            lat.append((time.perf_counter() - t0) * 1e3)
        lat.sort()
        # failover clock: SIGKILL member 0, time until the supervisor
        # has observed the respawn AND the slot answers again
        pid0 = g.ask("pid", index=0).result(60)
        t0 = time.perf_counter()
        g.tell("crash", index=0)
        resumed = None
        while time.perf_counter() - t0 < 120:
            try:
                changed = g.ask("pid", index=0).result(10) != pid0
            except Exception:  # noqa: BLE001 - mid-failover ask may fail
                changed = False
            if changed and g.respawns_observed >= 1:
                resumed = (time.perf_counter() - t0) * 1e3
                break
        if resumed is None:
            raise RuntimeError("member never respawned within 120s")
        return {
            "asks": n,
            "ask_p50_ms": round(lat[n // 2], 3),
            "ask_p99_ms": round(lat[min(n - 1, int(n * 0.99))], 3),
            "respawn_resume_ms": round(resumed, 1),
        }


if __name__ == "__main__":
    main()
