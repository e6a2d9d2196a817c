"""ResNet-50/ImageNet-224 via InputMode.SPARK ingestion — the literal
north-star workload (BASELINE.json config #3; reference shape:
examples/resnet/resnet_cifar_dist.py:144-148 scaled to ImageNet).

spark-submit (genuine Spark cluster):

    spark-submit --master $MASTER \\
        --conf spark.executor.instances=4 \\
        examples/resnet/resnet_imagenet_spark.py \\
        --cluster_size 4 --batch_size 1024 \\
        --data_dir hdfs:///imagenet/tfrecords --epochs 1

local engine (TPU VM / laptop, no Spark install):

    python examples/resnet/resnet_imagenet_spark.py \\
        --cluster_size 2 --batch_size 64 --steps 20   # synthetic data

The training loop is the framework's fast path: columnar shm-ring feed →
DataFeed → infeed.device_feed (double-buffered host→HBM staging) → a
donated, mesh-sharded jit train step; gradients all-reduce over ICI.

For JPEG TFRecords, run ``examples/resnet/imagenet_data_setup.py`` once
first: python-side PIL decode is GIL-bound (~700 img/s measured) and
would starve the chip, so the setup tool decodes in parallel across
engine executors into raw uint8 records this loop feeds at memory speed
(the in-loop decode below remains as a fallback for ad-hoc runs).
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main_fun(args, ctx):
    import numpy as np
    import jax
    import optax

    from tensorflowonspark_tpu.infeed import device_feed, synchronized
    from tensorflowonspark_tpu.models import resnet
    from tensorflowonspark_tpu.parallel import (
        batch_sharding, local_to_global, make_mesh, shard_train_state,
    )
    from tensorflowonspark_tpu.utils import checkpoint as ckpt
    from tensorflowonspark_tpu.utils.metrics import TrainMetrics

    env = ctx.jax_initialize()
    mesh = make_mesh({"data": -1})
    image = args["image_size"]

    opt = optax.sgd(args["lr"], momentum=0.9)

    # one jitted init program: eager init is hundreds of tiny dispatches,
    # each compiled on its own
    @jax.jit
    def init_all(key):
        params, state = resnet.init(key, depth=50,
                                    num_classes=args["num_classes"])
        return params, state, opt.init(params)

    params, state, opt_state = init_all(jax.random.PRNGKey(0))

    ckpt_dir = os.path.join(args["model_dir"], "ckpt")
    restored, step = ckpt.restore_latest(ckpt_dir)
    if restored is not None:
        params, state = restored["params"], restored["state"]
        opt_state = ckpt.unpack_pytree(restored["opt"], opt_state)

    (params, state, opt_state), (p_sh, s_sh, o_sh) = shard_train_state(
        mesh, params, state, opt_state
    )
    step_fn = jax.jit(
        resnet.make_train_step(opt, depth=50,
                               accum_steps=args.get("accum_steps", 1)),
        in_shardings=(p_sh, s_sh, o_sh, batch_sharding(mesh),
                      batch_sharding(mesh)),
        out_shardings=(p_sh, s_sh, o_sh, None, None),
        donate_argnums=(0, 1, 2),
    )

    per_proc = args["batch_size"] // max(env["num_processes"], 1)
    metrics = TrainMetrics(
        flops_per_item=3 * resnet.flops_per_image(50, image)
    )
    feed = ctx.get_data_feed(
        train_mode=True, metrics=metrics,
        input_mapping={"image": "image", "label": "label"},
    )

    def collate(cols):
        # uint8 HWC records; normalization runs on device inside the
        # step.  Under columnar pull cols are already dense arrays, so
        # asarray + reshape are zero-copy views; with a row-path feeder
        # the same code degrades to one stack/copy.
        imgs = np.asarray(cols["image"], dtype=np.uint8).reshape(
            -1, image, image, 3
        )
        labels = np.asarray(cols["label"], dtype=np.int32)
        return imgs, labels

    def save(step):
        ckpt.save_checkpoint(
            ckpt_dir,
            {"params": params, "state": state,
             "opt": ckpt.pack_pytree(opt_state)},
            step,
        )

    loss = acc = 0.0
    # synchronized(): all processes stop on the same step at end of
    # feed even when ragged tails leave them different batch counts —
    # no stranded all-reduce, no reference-style "90% of steps" trick
    for imgs, labels in synchronized(device_feed(
        feed, per_proc, collate=collate, depth=2, columnar=True,
        placement=lambda b: local_to_global(mesh, b),
    ), feed=feed):
        params, state, opt_state, loss, acc = step_fn(
            params, state, opt_state, imgs, labels
        )
        step += 1
        metrics.step(len(labels) * env["num_processes"])
        if step % 20 == 0 and ctx.task_index == 0:
            r = metrics.report()
            print(f"step {step}: loss={float(loss):.4f} acc={float(acc):.3f} "
                  f"img/s={r.get('items_per_sec', 0):.0f} "
                  f"mfu={r.get('mfu', 0):.3f} "
                  f"stall={r['infeed_stall_frac']:.3f}", flush=True)
        if step % args["save_every"] == 0 and ckpt.is_chief(ctx):
            save(step)

    if ckpt.is_chief(ctx):
        save(step)
        r = metrics.report()
        print(f"final: step={step} img/s={r.get('items_per_sec', 0):.0f} "
              f"mfu={r.get('mfu', 0):.3f} stall={r['infeed_stall_frac']:.3f}",
              flush=True)


def _records(args, engine):
    """Training rows: ImageNet TFRecords (image/class bytes, dfutil
    schema) when --data_dir is given, else synthetic uint8 tensors."""
    import numpy as np

    if args.data_dir:
        from tensorflowonspark_tpu import dfutil

        ds, schema = dfutil.load_tfrecords(
            engine, args.data_dir,
            binary_features=("image", "image/encoded"),
            # stripe shard files across workers at the SOURCE: fewer
            # shards than workers must not starve feeds (synchronized
            # stop at step 0) nor fall into the record-level
            # repartition below, which materializes every encoded image
            # through the driver
            min_partitions=args.cluster_size,
        )
        image = args.image_size

        # ONE definition of record validity, shared with the prep tool
        # (imagenet_data_setup.py): raw uint8 "image"/"label" or
        # TF-official JPEG "image/encoded"/"image/class/label" (1-based)
        import imagenet_records

        if ds.num_partitions < args.cluster_size:
            # min_partitions striping should prevent this; keep a
            # belt-and-braces fallback for exotic sources.  Rebalances
            # the ENCODED records (before decode) but materializes them
            # through the driver on the local engine — load_tfrecords'
            # striping is the production path.
            print(f"WARNING: {ds.num_partitions} data partition(s) for "
                  f"{args.cluster_size} workers; repartitioning",
                  flush=True)
            ds = ds.repartition(args.cluster_size * 2)
        # stream the partition through the native threaded JPEG decoder
        # in bounded chunks: the batch call amortizes thread fan-out,
        # while chunking keeps peak memory at one chunk of encoded+
        # decoded records instead of the whole partition at once
        def decode_stream(it, chunk=256):
            batch = []
            for rec in it:
                batch.append(rec)
                if len(batch) >= chunk:
                    yield from imagenet_records.decode_records_batch(
                        batch, image)
                    batch = []
            if batch:
                yield from imagenet_records.decode_records_batch(
                    batch, image)

        return ds.map_partitions(decode_stream)
    rng = np.random.default_rng(0)
    n = args.batch_size * args.steps
    pool = [rng.integers(0, 256, (args.image_size, args.image_size, 3),
                         dtype=np.uint8) for _ in range(32)]
    rows = [(pool[i % len(pool)], int(i % args.num_classes))
            for i in range(n)]
    return engine.parallelize(rows, args.cluster_size * 2)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--cluster_size", type=int, default=2)
    p.add_argument("--batch_size", type=int, default=256,
                   help="global batch (split across workers)")
    p.add_argument("--image_size", type=int, default=224)
    p.add_argument("--num_classes", type=int, default=1000)
    p.add_argument("--steps", type=int, default=20,
                   help="synthetic-data steps when --data_dir is absent")
    p.add_argument("--epochs", type=int, default=1)
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--save_every", type=int, default=200)
    p.add_argument("--accum_steps", type=int, default=1,
                   help="gradient-accumulation microbatches per step "
                        "(effective batch beyond one chip's HBM)")
    p.add_argument("--data_dir", default=None,
                   help="TFRecord dir (file://, hdfs://, gs://)")
    p.add_argument("--model_dir", default="/tmp/resnet_imagenet")
    args = p.parse_args()

    from tensorflowonspark_tpu import cluster as TFCluster, configure_logging
    from tensorflowonspark_tpu.cluster import InputMode

    configure_logging()
    try:  # under spark-submit: federate the real Spark cluster
        from pyspark import SparkContext

        from tensorflowonspark_tpu.engine import SparkEngine

        engine = SparkEngine(SparkContext.getOrCreate())
    except ImportError:  # no Spark: the built-in executor pool
        from tensorflowonspark_tpu.engine import LocalEngine

        engine = LocalEngine(
            args.cluster_size,
            # executors inherit the caller\'s platform; on the CPU each gets
            # one host device
            env={"XLA_FLAGS": "--xla_force_host_platform_device_count=1"},
        )

    cluster = TFCluster.run(
        engine, main_fun,
        {"batch_size": args.batch_size, "lr": args.lr,
         "image_size": args.image_size, "num_classes": args.num_classes,
         "model_dir": args.model_dir, "save_every": args.save_every,
         "accum_steps": args.accum_steps},
        num_executors=args.cluster_size, input_mode=InputMode.SPARK,
        master_node="chief",
    )
    cluster.train(_records(args, engine), num_epochs=args.epochs)
    cluster.shutdown(grace_secs=5)
    engine.stop()


if __name__ == "__main__":
    main()
