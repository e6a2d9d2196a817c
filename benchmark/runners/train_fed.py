"""Runner ``train_fed``: a fed training job through the program's normal
entry points, ``TFCluster.run(LocalEngine(E), main_fun, ...,
input_mode=InputMode.SPARK)`` + ``cluster.train(partitions)``.

One runner serves every fed-training cell.  What differs between cells
is data: the traffic mix says how many executors, chips per executor,
records per partition and warm-up steps; the configuration names a model
adapter (benchmark/models/<adapter>.py) that knows how to make records
from a seed, build the jitted step from the program's model code, count
the FLOPs an item requires and call the plain reference.

Process shape (chip_smoke.py's): this driver process never initializes a
jax backend.  Each executor's trainer (``main_fun`` below) owns its chip,
times its own window and writes its facts to a file; the driver merges
them.  The trainer ends the job with ``feed.terminate()``.
"""

import glob
import json
import math
import os
import time

from benchmark.lib import manifest as M


def say(msg):
    print(f"[bench:train_fed] {msg}", flush=True)


def _write_json(path, obj):
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(obj, f)
    os.replace(tmp, path)


def adapter_of(ctx):
    return M.load_module(os.path.join(
        ctx["root"], "benchmark", "models", ctx["config"]["adapter"] + ".py"))


# -- the trainer: runs in the executor's forked trainer process --------------

def main_fun(ctx, node):
    """``main_fun(args, ctx)`` of the program's API; ``node`` is the
    program's node context.  Owns the chip."""
    t_entered = time.time()
    import contextlib

    import jax
    import numpy as np

    from tensorflowonspark_tpu import tpu_info
    from tensorflowonspark_tpu.infeed import device_feed, synchronized
    from tensorflowonspark_tpu.parallel import local_to_global, make_mesh
    from tensorflowonspark_tpu.utils.metrics import TrainMetrics

    env = node.jax_initialize()
    device = tpu_info.device_facts()
    attach_s = time.time() - t_entered
    if not ctx["rehearse"] and device["platform"] != "tpu":
        raise RuntimeError(f"no accelerator: jax came up on {device}")
    nproc = max(env["num_processes"], 1)
    mesh = make_mesh({"data": -1})
    mix, seconds = ctx["mix"], float(ctx["seconds"])
    trainer = adapter_of(ctx).build(ctx, mesh, nproc)
    annotate = (jax.profiler.TraceAnnotation if ctx["trace"]
                else lambda _name: contextlib.nullcontext())

    t0 = time.perf_counter()
    state = trainer.init()
    init_s = time.perf_counter() - t0

    metrics = TrainMetrics()
    feed = node.get_data_feed(train_mode=True, metrics=metrics,
                              input_mapping=trainer.input_mapping)
    seen_ids, first_host = [], []

    def collate(cols):
        batch, ids = trainer.collate(cols)
        seen_ids.append(ids)
        if not first_host:
            first_host.append(tuple(np.array(x) for x in batch))
        return batch

    # "close": when the window may close; "end": when the stream ends
    # (later than "close" only in a traced run); "close_now": the batch
    # just handed out is the first one AFTER the window
    clock = {"close": None, "end": None, "close_now": False}
    gap_s, grace_s = float(mix["burst_gap_s"]), float(mix["burst_grace_s"])

    def until_deadline(it):
        """The window's end is decided HERE, inside what synchronized()
        wraps: in a job of several processes the first one whose time is
        up ends the stream for all of them on the same step.

        A feed that delivers in bursts (the feeder's chunk is 1,024
        records, four batches at once, then nothing for half a second)
        must be measured over whole bursts: a window that ends wherever
        the clock says counts 165, 169 or 173 steps in 40 s (my chip run,
        PR23).  So once ``--seconds`` have passed the window closes at the
        next batch that was WAITED for longer than ``burst_gap_s`` (the
        start of a burst; that batch is not part of the window), at the
        latest ``burst_grace_s`` later, and at once if no batch of the
        window was ever waited for that long."""
        saw_gap = False
        try:
            while True:
                t_wait = time.perf_counter()
                item = next(it, None)
                if item is None:
                    return
                now = time.perf_counter()
                gap = now - t_wait >= gap_s
                if clock["end"] is not None and now >= clock["end"] \
                        and clock["close"] is None:
                    return  # the traced slice is over
                if clock["close"] is not None and now >= clock["close"] \
                        and (gap or not saw_gap
                             or now >= clock["close"] + grace_s):
                    if not ctx["trace"]:
                        return  # this batch is not part of the window
                    clock.update(close=None, close_now=True,
                                 end=now + trace_s)
                elif clock["close"] is not None:
                    saw_gap = saw_gap or gap
                yield item
        finally:
            it.close()  # reaps the prefetch thread and its staged batches

    stream = device_feed(feed, trainer.per_process_batch, collate=collate,
                         depth=int(mix["feed_depth"]), columnar=True,
                         placement=lambda b: local_to_global(mesh, b))
    warm_steps = int(mix["warmup_steps"])
    inflight = int(mix["max_inflight_steps"])
    trace_s = float(mix["trace_seconds"])
    trace_here = bool(ctx["trace"]) and jax.process_index() == 0
    trace_dir = os.path.join(
        ctx["work"], f"trace-{node.job_name}-{node.task_index}")
    losses, step = [], 0
    win, phase = {}, "warm"
    step_fn, mem = None, None

    def close_window():
        # the window ends in a value fetch of the last step's loss
        float(losses[-1])
        win["t1"] = time.perf_counter()
        win["steps"] = step - win["step0"]
        win["infeed_wait_s"] = metrics.infeed_time
        win["metrics_step_time_s"] = metrics.step_time

    it = synchronized(until_deadline(stream), feed=feed)
    while True:
        with annotate("bench/wait_batch"):
            batch = next(it, None)
        if batch is None:
            break
        if clock["close_now"]:
            # traced run: the window closes before this batch, and the
            # feed goes on with the profiler on, so that the window itself
            # is the same in both kinds of run
            clock["close_now"] = False
            close_window()
            phase = "trace"
            if trace_here:
                opts = jax.profiler.ProfileOptions()
                # the Python tracer hooks every call of every thread and
                # slowed the feed's consumer thirteenfold (my chip run,
                # PR23); the benchmark's spans are TraceMe events
                opts.python_tracer_level = 0
                opts.host_tracer_level = 2
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
        if step_fn is None:
            t0 = time.perf_counter()
            step_fn, mem = trainer.compile(state, batch)
            compile_s = time.perf_counter() - t0
        with annotate("bench/dispatch_step"):
            state, loss = step_fn(state, batch)
        losses.append(loss)
        last_batch = batch
        step += 1
        if len(losses) > inflight:
            # bound the host's run-ahead without ever draining the device:
            # wait for the loss of ``inflight`` steps ago, never the newest
            # (unbounded, the host queued 33 steps = 22 s of work ahead and
            # a 10 s window lasted 32 s; my chip run, PR23)
            with annotate("bench/wait_inflight"):
                losses[-inflight - 1].block_until_ready()
        if phase == "warm" and step == warm_steps:
            # warm-up ends in a value fetch: the device is idle, every
            # program is compiled, and the window starts now
            float(loss)
            metrics.reset()
            win["t0"] = time.perf_counter()
            win["t0_wall"] = time.time()
            win["step0"] = step
            clock["close"] = win["t0"] + seconds
            phase = "window"
        if phase != "warm":
            metrics.step(trainer.per_process_batch * nproc)
    if phase == "warm":
        raise RuntimeError(f"the feed ended after {step} steps, before the "
                           f"window opened (warm-up is {warm_steps})")
    if phase == "window":
        close_window()
    else:
        float(losses[-1])  # the traced slice ends with the device drained
        if trace_here:
            jax.profiler.stop_trace()
    final_loss = float(losses[-1])
    if win["t1"] < win["t0"] + seconds - 1.0:  # a peer may end it a step early
        raise RuntimeError(
            "the feed ran dry before the window closed: the mix provisions "
            f"{mix['provision_records_per_s']} records/s, and the trainer "
            "consumed faster; add a mix that provisions more")
    win_steps = win["steps"]
    win_s = win["t1"] - win["t0"]
    # what is left of the partitions is not worth generating: the record
    # source looks for this file before it starts a partition
    open(ctx["stop_flag"], "w").close()
    if str(node.mgr.get("state")) != "terminating":
        feed.terminate()  # ends the job: the feeders stop and drain

    # the same compiled step, re-dispatched on one resident batch: what
    # the chip does when nothing has to be fed
    n_res = max(2, math.ceil(float(mix["resident_seconds"])
                             / (win_s / win_steps)))
    if nproc > 1:
        from jax.experimental import multihost_utils

        n_res = int(multihost_utils.broadcast_one_to_all(np.int32(n_res)))
    float(loss)
    t0 = time.perf_counter()
    for _ in range(n_res):
        state, loss = step_fn(state, last_batch)
    float(loss)
    res_s = time.perf_counter() - t0

    all_losses = np.asarray(jax.device_get(losses), np.float64)
    first_loss = float(all_losses[0])
    stats = jax.local_devices()[0].memory_stats() or {}
    del state, losses, last_batch, batch
    reference = trainer.reference_check(first_host[0], first_loss)

    ids = np.concatenate(seen_ids) if seen_ids else np.zeros((0,), np.int64)
    facts = {
        "task_index": node.task_index, "job_name": node.job_name,
        "process_index": jax.process_index(), "num_processes": nproc,
        "device": device, "t_entered": t_entered,
        "t_window_wall": win["t0_wall"],
        "attach_s": attach_s, "init_s": init_s, "compile_s": compile_s,
        "window_s": win_s, "window_steps": win_steps,
        "global_batch": trainer.per_process_batch * nproc,
        "items_per_record": trainer.items_per_record,
        "resident_steps": n_res, "resident_s": res_s,
        "infeed_wait_s": win["infeed_wait_s"],
        "metrics_step_time_s": win["metrics_step_time_s"],
        "first_loss": first_loss, "final_loss": final_loss,
        "losses_finite": bool(np.all(np.isfinite(all_losses))),
        "reference": reference,
        "flops_per_item": trainer.flops_per_item,
        "program_flops_per_item": trainer.program_flops_per_item,
        "memory_stats_peak_bytes": stats.get("peak_bytes_in_use"),
        "memory_program_bytes": mem,
        "ids": ids.tolist(),
        "trace_dir": trace_dir if ctx["trace"] else None,
    }
    _write_json(os.path.join(
        ctx["work"], f"node-{node.job_name}-{node.task_index}.json"), facts)


# -- the driver --------------------------------------------------------------

def check_ids(ids, per_part):
    """Every record consumed exactly once, in partition order, up to the
    terminate point.  Returns (attempted, failed): ``failed`` counts ids
    seen twice, ids out of order inside their partition, records missing
    from a partition that was followed by another, and partitions out of
    order."""
    failed = 0
    seen = set()
    last_part, expect = -1, 0
    for i in ids:
        part, off = divmod(i, per_part)
        if i in seen:
            failed += 1
            continue
        seen.add(i)
        if part != last_part:
            if last_part >= 0 and expect != per_part:
                failed += per_part - expect      # lost from the last one
            if part < last_part:
                failed += 1                      # partitions out of order
            last_part, expect = part, 0
        if off != expect:
            failed += 1
        expect = off + 1
    return len(ids), failed


def run(ctx):
    from tensorflowonspark_tpu import cluster as TFCluster
    from tensorflowonspark_tpu.cluster import InputMode
    from tensorflowonspark_tpu.engine import LocalEngine

    from benchmark.lib import peaks, trace as T

    mix = ctx["mix"]
    executors = int(mix["executors"])
    per_part = int(mix["records_per_partition"])
    # enough partitions for warm-up, window and margin at the rate the
    # mix provisions for; every partition left over when the trainer
    # terminates is still a task for the engine, so they are not endless
    horizon = float(ctx["seconds"]) + float(mix["provision_margin_s"])
    parts = math.ceil(horizon * float(mix["provision_records_per_s"])
                      / per_part)
    ctx["stop_flag"] = os.path.join(ctx["work"], "feed.stop")
    source = adapter_of(ctx).record_source(ctx, per_part)
    chips_each = int(mix.get("chips_per_process", 1))
    exec_env = {"TFOS_SLICE_HEALTH": "strict"}
    if ctx["rehearse"]:
        exec_env["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={chips_each}"
    engine = LocalEngine(executors, env=exec_env)
    t_run = time.time()
    try:
        cluster = TFCluster.run(
            engine, main_fun, ctx, num_executors=executors,
            num_chips=chips_each,
            input_mode=InputMode.SPARK, master_node="chief")
        ds = engine.parallelize(list(range(parts)), parts).map_partitions(
            source)
        cluster.train(ds, num_epochs=1)
        cluster.shutdown(grace_secs=1)
    finally:
        engine.stop()
    nodes = []
    for path in sorted(glob.glob(os.path.join(ctx["work"], "node-*.json"))):
        with open(path) as f:
            nodes.append(json.load(f))
    if len(nodes) != executors:
        raise RuntimeError(f"{len(nodes)} of {executors} trainers reported")
    chief = next(n for n in nodes if n["process_index"] == 0)

    attempted = failed = 0
    all_ids = set()
    for n in nodes:
        a, f = check_ids(n["ids"], per_part)
        attempted += a
        failed += f + len(all_ids.intersection(n["ids"]))
        all_ids.update(n["ids"])
        del n["ids"]
    correct = failed == 0 and attempted > 0
    for n in nodes:
        if not n["losses_finite"]:
            correct = False
            say(f"node {n['job_name']}:{n['task_index']}: a loss was not finite")
        if not n["reference"]["ok"]:
            correct = False
        say(f"node {n['job_name']}:{n['task_index']} reference: {n['reference']}")
    if len({(n["first_loss"], n["final_loss"], n["window_steps"])
            for n in nodes}) != 1:
        correct = False
        say("the processes of one job disagree on loss or step count: "
            + str([(n["first_loss"], n["final_loss"], n["window_steps"])
                   for n in nodes]))

    device = dict(chief["device"])
    reduced = None
    if not ctx["rehearse"] or device["platform"] == "tpu":
        peak = peaks.peak(device["kind"]) * device["count"]
    else:
        peak = None
    if ctx["trace"] and chief["trace_dir"]:
        path = T.find_xplane(chief["trace_dir"])
        if path:
            reduced = T.reduce(T.read_xplane(path),
                               is_collective=T.is_collective_op)
    mem = max(n["memory_program_bytes"] or 0 for n in nodes)
    device["memory_peak_bytes"] = max(
        mem, max(n["memory_stats_peak_bytes"] or 0 for n in nodes))

    items = chief["window_steps"] * chief["global_batch"] \
        * chief["items_per_record"]
    win_s = max(n["window_s"] for n in nodes)
    res_items_s = (chief["resident_steps"] * chief["global_batch"]
                   * chief["items_per_record"]
                   / max(n["resident_s"] for n in nodes))
    facts = {
        "correct": correct, "attempted": attempted, "failed": failed,
        "device": device, "trace": reduced, "peak_flops": peak,
        "setup_s": min(n["t_window_wall"] for n in nodes) - ctx["t_start"],
        "boot_s": max(n["t_entered"] for n in nodes) - t_run,
        "window_s": win_s, "window_items": items,
        "window_steps": chief["window_steps"],
        "flops_per_item": chief["flops_per_item"],
        "program_flops_per_item": chief["program_flops_per_item"],
        "resident_items_per_s": res_items_s,
        "resident_step_s": max(n["resident_s"] for n in nodes)
        / chief["resident_steps"],
        "infeed_wait_s": max(n["infeed_wait_s"] for n in nodes),
        "metrics_step_time_s": chief["metrics_step_time_s"],
        "nodes": nodes,
    }
    rate = items / win_s
    say(f"device {device}; window {win_s:.3f}s, {chief['window_steps']} "
        f"steps, {items} items: {rate:.1f} items/s "
        f"({rate / device['count']:.1f} per chip); resident "
        f"{res_items_s:.1f} items/s, step "
        f"{facts['resident_step_s'] * 1e3:.2f} ms; records {attempted}, "
        f"lost/duplicated {failed}; chip attach {chief['attach_s']:.1f}s, "
        f"init {chief['init_s']:.1f}s, compile "
        f"{chief['compile_s']:.1f}s, boot {facts['boot_s']:.1f}s, set-up "
        f"{facts['setup_s']:.1f}s")
    say(f"memory: program (arguments + outputs + temporaries - aliased) "
        f"{mem} B, memory_stats peak "
        f"{[n['memory_stats_peak_bytes'] for n in nodes]} B")
    if peak:
        say(f"train_mfu {rate * chief['flops_per_item'] / peak:.4f} with "
            f"the benchmark's {chief['flops_per_item']:.4g} FLOPs/item; "
            f"the program's formula ({chief['program_flops_per_item']:.4g}"
            f" FLOPs/item) would print "
            f"{rate * chief['program_flops_per_item'] / peak:.4f}")
    if reduced:
        say(T.describe(reduced))
    return facts
