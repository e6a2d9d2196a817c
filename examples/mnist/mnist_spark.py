"""MNIST, InputMode.SPARK: engine partitions stream into distributed training.

Parity workload: reference examples/mnist/keras/mnist_spark.py — a driver
that starts a cluster, feeds partitioned records through DataFeed, trains
data-parallel, and lets the chief export.  The porting story holds: the
model/training code below is plain JAX; the cluster plumbing is ~10 lines.

Run (no Spark needed — built-in engine):
    python examples/mnist/mnist_spark.py --cluster_size 2 --steps 40

With pyspark installed, pass a SparkContext instead of LocalEngine.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def main_fun(args, ctx):
    """Runs on every cluster node (the user's `map_fun`)."""
    import numpy as np
    import jax
    import optax

    from tensorflowonspark_tpu.models import mnist
    from tensorflowonspark_tpu.parallel import make_mesh, local_to_global
    from tensorflowonspark_tpu.utils import checkpoint as ckpt

    from tensorflowonspark_tpu import infeed
    from tensorflowonspark_tpu.utils import metrics as M

    env = ctx.jax_initialize()
    mesh = make_mesh({"data": -1})
    params = mnist.init_params(jax.random.PRNGKey(0))
    opt = optax.sgd(args["lr"], momentum=0.9)
    opt_state = opt.init(params)
    step_fn = jax.jit(mnist.make_train_step(opt))

    # double-buffered device staging + infeed-stall accounting: the
    # background thread collates/stages batch t+1 while t trains
    tm = M.TrainMetrics(window=10)
    feed = ctx.get_data_feed(train_mode=True, metrics=tm)
    per_proc = args["batch_size"] // max(env["num_processes"], 1)

    def collate(batch):
        images = np.stack([b[0] for b in batch]).astype(np.float32)
        labels = np.asarray([b[1] for b in batch], dtype=np.int32)
        return images, labels

    step = loss = acc = 0
    # synchronized: every process stops on the same step at end of feed
    # even with ragged tails (the reference's "90% of steps" trick,
    # mnist_spark.py:58-66, replaced by a principled global stop)
    for gi, gl in infeed.synchronized(infeed.device_feed(
        feed, per_proc, collate=collate,
        placement=lambda b: local_to_global(mesh, b),
    ), feed=feed):
        params, opt_state, loss, acc = step_fn(params, opt_state, gi, gl)
        tm.step(items=per_proc)
        step += 1
        if step % 10 == 0 and ctx.task_index == 0:
            print(f"step {step}: loss={float(loss):.4f} acc={float(acc):.3f} "
                  f"metrics={tm.report()}")

    if ckpt.is_chief(ctx):  # chief-only persistence (compat.py:10-17 parity)
        ckpt.save_checkpoint(os.path.join(args["model_dir"], "ckpt"), params, step)
        ckpt.export_model(os.path.join(args["model_dir"], "export"), params, ctx)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--cluster_size", type=int, default=2)
    p.add_argument("--batch_size", type=int, default=64)
    p.add_argument("--steps", type=int, default=40, help="steps of data to feed")
    p.add_argument("--epochs", type=int, default=1)
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--model_dir", default="/tmp/mnist_model")
    p.add_argument("--mnist_csv", default=None,
                   help="optional CSV dir (label,pix...); default synthetic")
    args = p.parse_args()

    import numpy as np

    from tensorflowonspark_tpu import cluster as TFCluster
    from tensorflowonspark_tpu.cluster import InputMode
    from tensorflowonspark_tpu.engine import LocalEngine
    from tensorflowonspark_tpu import configure_logging

    configure_logging()
    n = args.batch_size * args.steps
    rng = np.random.default_rng(0)
    if args.mnist_csv:
        rows = []
        for fname in sorted(os.listdir(args.mnist_csv)):
            with open(os.path.join(args.mnist_csv, fname)) as f:
                for line in f:
                    vals = np.fromstring(line, dtype=np.float32, sep=",")
                    rows.append((vals[1:].reshape(28, 28, 1) / 255.0, int(vals[0])))
        records = rows
    else:
        images = rng.random((n, 28, 28, 1), dtype=np.float32)
        q = np.stack(
            [images[:, :14, :14, 0].mean((1, 2)), images[:, :14, 14:, 0].mean((1, 2)),
             images[:, 14:, :14, 0].mean((1, 2)), images[:, 14:, 14:, 0].mean((1, 2))],
            axis=-1)
        labels = (np.argmax(q, axis=-1) * 2 + (q.sum(-1) > 2.0)).astype(np.int32)
        records = list(zip(list(images), list(labels)))

    engine = LocalEngine(
        args.cluster_size,
        # executors inherit the caller\'s platform; on the CPU each gets
        # one host device
        env={"XLA_FLAGS": "--xla_force_host_platform_device_count=1"},
    )
    cluster = TFCluster.run(
        engine, main_fun,
        {"batch_size": args.batch_size, "lr": args.lr, "model_dir": args.model_dir},
        num_executors=args.cluster_size, input_mode=InputMode.SPARK,
        master_node="chief",
    )
    ds = engine.parallelize(records, args.cluster_size * 2)
    cluster.train(ds, num_epochs=args.epochs, feed_timeout=600)
    cluster.shutdown(grace_secs=5)
    engine.stop()
    print("export:", os.path.join(args.model_dir, "export"))


if __name__ == "__main__":
    main()
