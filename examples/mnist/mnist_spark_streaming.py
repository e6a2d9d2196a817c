"""MNIST streaming: online training over a stream of micro-batches
(parity: reference examples/mnist/estimator/mnist_spark_streaming.py —
DStream feeding with graceful STOP via the rendezvous server; stop it
from another shell with examples/utils/stop_streaming.py).

    python examples/mnist/mnist_spark_streaming.py --cluster_size 2 \\
        --micro_batches 20
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def main_fun(args, ctx):
    import numpy as np
    import jax
    import optax

    from tensorflowonspark_tpu.models import mnist
    from tensorflowonspark_tpu.parallel import make_mesh, local_to_global
    from tensorflowonspark_tpu.utils import checkpoint as ckpt

    env = ctx.jax_initialize()
    mesh = make_mesh({"data": -1})
    params = mnist.init_params(jax.random.PRNGKey(0))
    opt = optax.sgd(args["lr"], momentum=0.9)
    opt_state = opt.init(params)
    step_fn = jax.jit(mnist.make_train_step(opt))

    feed = ctx.get_data_feed(train_mode=True)
    per_proc = max(args["batch_size"] // max(env["num_processes"], 1), 1)
    step = 0
    while not feed.should_stop():
        batch = feed.next_batch(per_proc)
        if not batch:
            continue
        images = np.stack([b[0] for b in batch]).astype(np.float32)
        labels = np.asarray([b[1] for b in batch], dtype=np.int32)
        if len(batch) < per_proc:  # pad the short tail of a micro-batch
            reps = -(-per_proc // len(batch))
            images = np.tile(images, (reps, 1, 1, 1))[:per_proc]
            labels = np.tile(labels, reps)[:per_proc]
        gi, gl = local_to_global(mesh, (images, labels))
        params, opt_state, loss, acc = step_fn(params, opt_state, gi, gl)
        step += 1
        if step % 10 == 0 and ctx.task_index == 0:
            print(f"stream step {step}: loss={float(loss):.4f}")

    if ckpt.is_chief(ctx):
        ckpt.export_model(
            os.path.join(args["model_dir"], "export"), params, ctx,
            metadata={"predict": "tensorflowonspark_tpu.models.mnist:predict"},
        )


def micro_batch_stream(engine, args):
    """A generator of datasets — the DStream analogue.  A real Spark
    deployment passes the DStream's RDDs; here micro-batches arrive on a
    timer."""
    from mnist_data_setup import synthetic_mnist

    for i in range(args.micro_batches):
        images, labels = synthetic_mnist(args.batch_size * 2, seed=i)
        records = list(zip(list(images), list(labels)))
        yield engine.parallelize(records, args.cluster_size)
        time.sleep(args.interval)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--cluster_size", type=int, default=2)
    p.add_argument("--batch_size", type=int, default=32)
    p.add_argument("--micro_batches", type=int, default=20)
    p.add_argument("--interval", type=float, default=0.0,
                   help="seconds between micro-batches")
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--model_dir", default="/tmp/mnist_model_streaming")
    args = p.parse_args()

    from tensorflowonspark_tpu import cluster as TFCluster, configure_logging
    from tensorflowonspark_tpu.cluster import InputMode
    from tensorflowonspark_tpu.engine import LocalEngine

    configure_logging()
    engine = LocalEngine(
        args.cluster_size,
        env={"PYTHONPATH": os.path.dirname(os.path.abspath(__file__)),
             "XLA_FLAGS": "--xla_force_host_platform_device_count=1"},
    )
    cluster = TFCluster.run(
        engine, main_fun,
        {"batch_size": args.batch_size, "lr": args.lr,
         "model_dir": args.model_dir},
        num_executors=args.cluster_size, input_mode=InputMode.SPARK,
        master_node="chief",
    )
    host, port = cluster.cluster_meta["server_addr"]
    print(f"rendezvous server at {host}:{port} — stop early with:\n"
          f"  python examples/utils/stop_streaming.py {host} {port}")
    cluster.train_stream(micro_batch_stream(engine, args))
    cluster.shutdown(grace_secs=5)
    engine.stop()
    print("export:", os.path.join(args.model_dir, "export"))


if __name__ == "__main__":
    main()
