"""Fed-path CONSUMER stress bench (VERDICT r3 next #6c): real feeder
process -> shm ring -> DataFeed, drained with NO device compute, so the
number is the consumer-side ceiling (records/s) that bounds fed training
throughput on a chip.

Two modes, A/B-able in one run:
  rows     — row-list chunks + next_batch + np.stack collate (the
             round-2/3 hot path; PERF.md measured its np.stack wall at
             ~12k img/s single-threaded at 224px)
  columnar — ColumnChunk wire format (flattened uint8 image columns) +
             next_batch_columns dense pull (round-4 fast path)

Usage: python scripts/stress_fed.py [--batch 256] [--image 224]
           [--steps 24] [--mode both|rows|columnar|pipeline|service-dynamic]
Prints one JSON line per mode:
  {"mode", "records_per_sec", "batches", "batch", "image"}

``--mode service-dynamic`` runs the straggler A/B of the data service
(ISSUE 19 acceptance): T consumer processes with one seeded
``--slow-factor``x slower (faults.py ``feed.get:delay``), an epoch
served three ways — dynamic dispatch homogeneous, dynamic with the
straggler, static ``shard(rank,T)`` with the straggler — printing
``straggler_ratio`` (dynamic-straggler / homogeneous, gate <= 1.5) and
``straggler_speedup`` (static-straggler / dynamic-straggler).

``--mode pipeline`` runs the composed-pipeline A/B on the 784-float
workload (ISSUE 5 acceptance): a per-record fed feeder (row append +
columnar encode, the node.train closure idiom) vs the data/ pipeline
graph (vectorized map -> batch -> prefetch -> ColumnChunk) pushing the
SAME ring drained by the SAME DataFeed consumer; prints
``pipeline_vs_fed`` speedup (>= 1.0 means the composed pipeline
matches/beats the fed path)."""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tensorflowonspark_tpu.utils import telemetry  # noqa: E402


def _f784_feeder_main(ring_name, mgr_addr, authkey_hex, total, width):
    """Fed-baseline feeder for the 784-float workload: the per-record
    row-append loop + columnar chunk encoder, exactly the node.train
    feeder idiom (node.py) — the cost model the composed pipeline has to
    match or beat."""
    import numpy as np

    import bench
    from tensorflowonspark_tpu import manager as tfmanager
    from tensorflowonspark_tpu import node as tfnode
    from tensorflowonspark_tpu.recordio import shm as shmq

    if telemetry.enabled():
        telemetry.configure(node_id=f"feeder-{os.getpid()}", role="feeder")
    encode = tfnode._ChunkEncoder()
    mgr = tfmanager.connect(tuple(mgr_addr), bytes.fromhex(authkey_hex))
    ring = shmq.ShmQueue(ring_name, create=False, producer=True)
    rng = np.random.default_rng(0)
    pool = 2 * bench.FED_CHUNK
    vecs = [rng.random(width, dtype=np.float32) for _ in range(pool)]
    sent = 0
    chunk = []
    with telemetry.span("feeder/push", records=total, columnar=True):
        while sent < total:
            chunk.append((vecs[sent % pool] * (1.0 / 255.0),
                          sent % 1000))
            sent += 1
            if len(chunk) >= bench.FED_CHUNK:
                ring.put(encode(chunk))
                chunk = []
        if chunk:
            ring.put(encode(chunk))
        ring.put(None)  # end-of-feed marker
    ring.close()
    mgr.set("feeder_done", 1)
    telemetry.flush()


def _pipeline_feeder_main(ring_name, mgr_addr, authkey_hex, total, width):
    """Composed-pipeline feeder: the same 784-float workload through the
    data/ graph — vectorized map, batch, prefetch — emitting ColumnChunk
    blocks straight onto the ring (no per-record python)."""
    import numpy as np

    import bench
    from tensorflowonspark_tpu import data
    from tensorflowonspark_tpu import manager as tfmanager
    from tensorflowonspark_tpu.recordio import shm as shmq

    if telemetry.enabled():
        telemetry.configure(node_id=f"feeder-{os.getpid()}", role="feeder")
    mgr = tfmanager.connect(tuple(mgr_addr), bytes.fromhex(authkey_hex))
    ring = shmq.ShmQueue(ring_name, create=False, producer=True)
    rng = np.random.default_rng(0)
    x = rng.random((total, width), dtype=np.float32)
    y = (np.arange(total, dtype=np.int64) % 1000)
    pipe = (data.from_arrays({"image": x, "label": y},
                             block_size=bench.FED_CHUNK)
            .map(lambda b: {"image": b["image"] * (1.0 / 255.0),
                            "label": b["label"]})
            .batch(bench.FED_CHUNK)
            .prefetch(4))
    with telemetry.span("feeder/push", records=total, pipeline=True):
        for chunk in pipe.chunks():
            ring.put(chunk)
        ring.put(None)  # end-of-feed marker
    ring.close()
    mgr.set("feeder_done", 1)
    telemetry.flush()


def run_f784(mode, batch, width, steps):
    """One 784-float lane: mode 'fed784' (row feeder) or 'pipeline784'
    (composed graph), drained by the identical DataFeed consumer."""
    import numpy as np

    import bench
    from tensorflowonspark_tpu.feed import DataFeed

    target = (_pipeline_feeder_main if mode == "pipeline784"
              else _f784_feeder_main)
    fed = bench._fed_setup(batch, 0, steps, tag=f"-{mode}", target=target,
                           extra=(width,), rec_bytes=width * 4)
    feed = DataFeed(fed["mgr"], train_mode=True,
                    input_mapping={"image": "image", "label": "label"})
    n_batches = 0
    n_records = 0
    t0 = None
    dt = 0.0
    try:
        while not feed.should_stop():
            cols = feed.next_batch_columns(batch)
            vecs = cols["image"]
            labels = np.asarray(cols["label"], np.int32)
            n = len(labels)
            if n == 0:
                continue
            assert vecs.shape[1] == width, vecs.shape
            if t0 is None:  # skip the first batch (warmup)
                t0 = time.perf_counter()
            else:
                n_batches += 1
                n_records += n
        dt = time.perf_counter() - t0 if t0 is not None else 0.0
    finally:
        fed["proc"].join(timeout=10)
        if fed["proc"].is_alive():
            fed["proc"].kill()
        fed["mgr"].set("state", "stopped")
        fed["ring"].close()
    rps = n_records / dt if dt > 0 else 0.0
    return {"mode": mode, "records_per_sec": round(rps, 1),
            "batches": n_batches, "batch": batch, "width": width}


def _service_consumer_main(mgr_addr, authkey_hex, batch, plan, done_key):
    """One trainer-side consumer for the service A/B: drains its feed
    queue through DataFeed with a seeded per-chunk cost (the faults.py
    delay machinery), so consumption — not serving — is the bottleneck
    and the dispatch policy is what the wall-clock measures."""
    import os as _os

    if plan:
        _os.environ["TFOS_FAULT_PLAN"] = plan
        _os.environ.pop("TFOS_FAULT_EXECUTOR", None)
    from tensorflowonspark_tpu import manager as tfmanager
    from tensorflowonspark_tpu.feed import DataFeed

    mgr = tfmanager.connect(tuple(mgr_addr), bytes.fromhex(authkey_hex))
    feed = DataFeed(mgr, train_mode=True,
                    input_mapping={"x": "x", "y": "y"})
    mgr.set("consumer_ready", 1)  # keep process spawn out of the clock
    n = 0
    while not feed.should_stop():
        n += len(feed.next_batch_columns(batch)["y"])
    mgr.set(done_key, n)


class _InlineCtx:
    """Actor-context stand-in to tick SplitProvider in this process."""

    def __init__(self, mgr):
        self.mgr = mgr
        self._kv = {}

    def kv_get(self, key):
        return self._kv.get(key)

    def kv_set(self, key, value):
        self._kv[key] = value


def _run_service_lane(dispatch, trainers, slow_rank, n_blocks, block,
                      delay, slow_factor, split_blocks):
    """One measured epoch through the data service: T consumer processes
    (one optionally ``slow_factor``x slower), serving from this process
    under the given dispatch policy.  Returns wall-clock seconds from
    serve start to the last consumer's exit."""
    import multiprocessing as mp
    import secrets
    import threading

    import numpy as np

    from tensorflowonspark_tpu import data, rendezvous
    from tensorflowonspark_tpu import manager as tfmanager
    from tensorflowonspark_tpu.data import service as dsvc
    from tensorflowonspark_tpu.data import splits as dsplits

    n = n_blocks * block
    arrays = {
        "x": np.zeros((n, 16), dtype=np.float32),
        "y": np.arange(n, dtype=np.int64),
    }
    pipe = data.from_arrays(arrays, block_size=block)
    keys = [secrets.token_bytes(16) for _ in range(trainers)]
    mgrs = [tfmanager.start(k, ["input", "output", "error"]) for k in keys]
    server = rendezvous.Server(1)
    addr = server.start()
    cluster_info = [
        {"executor_id": i, "host": "localhost", "job_name": "worker",
         "addr": list(m.address), "authkey": k.hex()}
        for i, (m, k) in enumerate(zip(mgrs, keys))
    ]
    ctx_mp = mp.get_context("spawn")
    procs = []
    t_wall = None
    try:
        for rank, (m, k) in enumerate(zip(mgrs, keys)):
            d = delay * (slow_factor if rank == slow_rank else 1.0)
            plan = f"feed.get:delay({d})@*"
            p = ctx_mp.Process(
                target=_service_consumer_main,
                args=(tuple(m.address), k.hex(), block, plan, "consumed"),
                daemon=True)
            p.start()
            procs.append(p)
        deadline = time.time() + 60
        while not all(m.get("consumer_ready") for m in mgrs):
            if time.time() > deadline:
                raise RuntimeError("consumers failed to come up")
            time.sleep(0.05)
        t0 = time.perf_counter()
        if dispatch == "dynamic":
            bkey = secrets.token_bytes(16)
            bmgr = tfmanager.start(bkey, [])
            board = dsplits.SplitBoard(bmgr, "input")
            board.set_plan([0])
            ictx = _InlineCtx(bmgr)
            provider = dsplits.SplitProvider(
                "input", server_addr=addr, num_epochs=1,
                window=4 * trainers)
            provider.on_start(ictx)
            meta = {"server_addr": addr,
                    dsvc.SPLIT_BOARD_META: {
                        "address": tuple(bmgr.address), "authkey": bkey}}
            stop = threading.Event()

            def _tick():
                while not stop.is_set() and not board.complete():
                    provider.on_tick(ictx)
                    time.sleep(0.02)

            ticker = threading.Thread(target=_tick, daemon=True)
            ticker.start()
            try:
                dsvc.DynamicDataService(
                    pipe, cluster_info, meta, worker_index=0,
                    split_blocks=split_blocks, feed_timeout=120,
                    use_cache=False).run()
            finally:
                stop.set()
                ticker.join(timeout=5)
                bmgr.shutdown()
        else:
            dsvc.DataService(
                pipe, cluster_info, {"server_addr": addr},
                num_workers=1, worker_index=0,
                feed_timeout=120).run()
        for m in mgrs:
            m.get_queue("input").put(None)  # end-of-feed
        for p in procs:
            p.join(timeout=120)
        t_wall = time.perf_counter() - t0
        consumed = sum(m.get("consumed") or 0 for m in mgrs)
        assert consumed == n, (
            f"{dispatch}: consumed {consumed} of {n} records")
    finally:
        for p in procs:
            if p.is_alive():
                p.kill()
        server.stop()
        for m in mgrs:
            m.shutdown()
    return t_wall


def run_service_dynamic(trainers=4, slow_factor=4.0, n_blocks=160,
                        block=64, delay=0.025, split_blocks=4,
                        queue_cap=2):
    """The straggler A/B (ISSUE 19 acceptance): one consumer
    ``slow_factor``x slower than its siblings.  Static ``shard(rank,T)``
    must stretch the epoch toward ``slow_factor``x; FCFS split dispatch
    keeps it near the homogeneous baseline because the slow trainer
    simply claims fewer splits (gate: ratio <= 1.5).

    A small per-trainer backlog cap (TFOS_DATA_QUEUE_CAP) is what turns
    queue depth into a drain-rate signal — a deep queue would equalize
    LENGTHS, not rates, and hand the slow trainer a fat tail."""
    prev_cap = os.environ.get("TFOS_DATA_QUEUE_CAP")
    os.environ["TFOS_DATA_QUEUE_CAP"] = str(queue_cap)
    try:
        homog = _run_service_lane("dynamic", trainers, slow_rank=-1,
                                  n_blocks=n_blocks, block=block,
                                  delay=delay, slow_factor=slow_factor,
                                  split_blocks=split_blocks)
        dyn = _run_service_lane("dynamic", trainers, slow_rank=0,
                                n_blocks=n_blocks, block=block,
                                delay=delay, slow_factor=slow_factor,
                                split_blocks=split_blocks)
        static = _run_service_lane("static", trainers, slow_rank=0,
                                   n_blocks=n_blocks, block=block,
                                   delay=delay, slow_factor=slow_factor,
                                   split_blocks=split_blocks)
    finally:
        if prev_cap is None:
            os.environ.pop("TFOS_DATA_QUEUE_CAP", None)
        else:
            os.environ["TFOS_DATA_QUEUE_CAP"] = prev_cap
    return {
        "mode": "service-dynamic",
        "trainers": trainers,
        "slow_factor": slow_factor,
        "records": n_blocks * block,
        "homogeneous_s": round(homog, 3),
        "dynamic_straggler_s": round(dyn, 3),
        "static_straggler_s": round(static, 3),
        "straggler_ratio": round(dyn / homog, 2) if homog else 0.0,
        "straggler_speedup": round(static / dyn, 2) if dyn else 0.0,
    }


def run_mode(mode, batch, image, steps):
    import numpy as np

    import bench
    from tensorflowonspark_tpu.feed import DataFeed

    fed = bench._fed_setup(batch, image, steps,
                           columnar=(mode == "columnar"), tag=f"-{mode}")
    feed = DataFeed(fed["mgr"], train_mode=True,
                    input_mapping={"image": "image", "label": "label"})
    n_batches = 0
    n_records = 0
    t0 = None
    dt = 0.0
    try:
        while not feed.should_stop():
            if mode == "columnar":
                cols = feed.next_batch_columns(batch)
                imgs = cols["image"]
                labels = np.asarray(cols["label"], np.int32)
            else:
                cols = feed.next_batch(batch)
                if not cols["image"]:
                    continue
                imgs = np.stack(cols["image"])
                labels = np.asarray(cols["label"], np.int32)
            n = len(labels)
            if n == 0:
                continue
            assert imgs.shape[1:] == (image, image, 3), imgs.shape
            if t0 is None:  # skip the first batch (warmup/compile-free)
                t0 = time.perf_counter()
            else:
                n_batches += 1
                n_records += n
        # stop the clock BEFORE teardown: proc.join/ring.close cost
        # 100ms+ and would deflate short runs' records_per_sec
        dt = time.perf_counter() - t0 if t0 is not None else 0.0
    finally:
        fed["proc"].join(timeout=10)
        if fed["proc"].is_alive():
            fed["proc"].kill()
        fed["mgr"].set("state", "stopped")
        fed["ring"].close()
    rps = n_records / dt if dt > 0 else 0.0
    return {"mode": mode, "records_per_sec": round(rps, 1),
            "batches": n_batches, "batch": batch, "image": image}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--image", type=int, default=224)
    ap.add_argument("--steps", type=int, default=24)
    ap.add_argument("--mode", choices=("both", "rows", "columnar",
                                       "pipeline", "service-dynamic"),
                    default="both")
    ap.add_argument("--width", type=int, default=784,
                    help="record width for the --mode pipeline A/B lane")
    ap.add_argument("--trainers", type=int, default=4,
                    help="consumer count for --mode service-dynamic")
    ap.add_argument("--slow-factor", type=float, default=4.0,
                    help="straggler slowdown for --mode service-dynamic")
    args = ap.parse_args()
    if os.environ.get(telemetry.DIR_ENV):
        # opt-in spans, same schema/dir layout as bench.py and the
        # cluster nodes (feed/wait comes from DataFeed when enabled)
        telemetry.configure(node_id="stress-fed", role="stress")
    if args.mode == "service-dynamic":
        with telemetry.span(f"stress_fed/{args.mode}",
                            trainers=args.trainers,
                            slow_factor=args.slow_factor) as sp:
            r = run_service_dynamic(trainers=args.trainers,
                                    slow_factor=args.slow_factor)
            sp.add(straggler_ratio=r["straggler_ratio"],
                   straggler_speedup=r["straggler_speedup"])
        print(json.dumps(r), flush=True)
        telemetry.flush()
        return
    if args.mode == "pipeline":
        results = []
        for m in ("fed784", "pipeline784"):
            with telemetry.span(f"stress_fed/{m}", batch=args.batch,
                                width=args.width, steps=args.steps) as sp:
                r = run_f784(m, args.batch, args.width, args.steps)
                if "records_per_sec" in r:
                    sp.add(records_per_sec=r["records_per_sec"])
            print(json.dumps(r), flush=True)
            results.append(r)
        if all("records_per_sec" in r for r in results):
            a, b = (results[0]["records_per_sec"],
                    results[1]["records_per_sec"])
            if a:
                print(json.dumps({"pipeline_vs_fed": round(b / a, 2)}),
                      flush=True)
        telemetry.flush()
        return
    modes = (["rows", "columnar"] if args.mode == "both" else [args.mode])
    results = []
    for m in modes:
        with telemetry.span(f"stress_fed/{m}", batch=args.batch,
                            image=args.image, steps=args.steps) as sp:
            r = run_mode(m, args.batch, args.image, args.steps)
            if "records_per_sec" in r:
                sp.add(records_per_sec=r["records_per_sec"])
        print(json.dumps(r), flush=True)
        results.append(r)
    if len(results) == 2 and all("records_per_sec" in r for r in results):
        a, b = results[0]["records_per_sec"], results[1]["records_per_sec"]
        if a:
            print(json.dumps({"columnar_speedup": round(b / a, 2)}),
                  flush=True)
    telemetry.flush()


if __name__ == "__main__":
    main()
