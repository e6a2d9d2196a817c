"""MNIST, InputMode.TENSORFLOW over TFRecords: each worker reads a disjoint
subset of the TFRecord shards through the native record reader
(parity: reference examples/mnist/keras/mnist_tf_ds.py, which builds a
sharded tf.data pipeline from HDFS TFRecords and resolves paths with
``ctx.absolute_path`` :41).

    python examples/mnist/mnist_data_setup.py --output /tmp/mnist
    python examples/mnist/mnist_tf_ds.py --data_dir /tmp/mnist/tfr
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def main_fun(args, ctx):
    import numpy as np
    import jax
    import optax

    from tensorflowonspark_tpu import dfutil
    from tensorflowonspark_tpu.models import mnist
    from tensorflowonspark_tpu.parallel import make_mesh, local_to_global
    from tensorflowonspark_tpu.utils import checkpoint as ckpt

    env = ctx.jax_initialize()
    mesh = make_mesh({"data": -1})

    # shard the part files over workers (hosts own disjoint file sets)
    data_dir = ctx.absolute_path(args["data_dir"])
    if data_dir.startswith("file://"):  # local FS: strip scheme for os IO
        data_dir = data_dir[len("file://"):]
    files = sorted(
        os.path.join(data_dir, f) for f in os.listdir(data_dir)
        if f.startswith("part-")
    )[ctx.task_index::ctx.num_workers]
    # bulk columnar load over this worker's shard subset: one C pass per
    # shard straight into dense arrays (~5x the per-row decode loop);
    # empty parts are skipped and cross-shard schema drift errors clearly
    cols = dfutil.load_tfrecords_columnar(files)
    if not cols:
        raise RuntimeError(
            f"worker {ctx.task_index}/{ctx.num_workers} got no data: "
            f"shard subset {files or '(empty)'} — fewer non-empty part "
            "files than workers?")
    images = np.asarray(cols["image"], np.float32).reshape(-1, 28, 28, 1)
    labels = np.asarray(cols["label"], np.int32)
    assert labels.ndim == 1, f"expected scalar labels, got {labels.shape}"
    print(f"worker {ctx.task_index}: {len(images)} examples from "
          f"{len(files)} shards")

    params = mnist.init_params(jax.random.PRNGKey(0))
    opt = optax.sgd(args["lr"], momentum=0.9)
    opt_state = opt.init(params)
    step_fn = jax.jit(mnist.make_train_step(opt))
    per_proc = args["batch_size"] // max(env["num_processes"], 1)
    rng = np.random.default_rng(ctx.task_index)
    loss = acc = 0.0
    for step in range(1, args["steps"] + 1):
        idx = rng.integers(0, len(images), per_proc)
        gi, gl = local_to_global(mesh, (images[idx], labels[idx]))
        params, opt_state, loss, acc = step_fn(params, opt_state, gi, gl)
        if step % 10 == 0 and ctx.task_index == 0:
            print(f"step {step}: loss={float(loss):.4f} acc={float(acc):.3f}")

    if ckpt.is_chief(ctx):
        ckpt.export_model(
            os.path.join(args["model_dir"], "export"), params, ctx,
            metadata={"predict": "tensorflowonspark_tpu.models.mnist:predict"},
        )
    return float(acc)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--cluster_size", type=int, default=2)
    p.add_argument("--batch_size", type=int, default=64)
    p.add_argument("--steps", type=int, default=40)
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--data_dir", default="/tmp/mnist/tfr")
    p.add_argument("--model_dir", default="/tmp/mnist_model_tf_ds")
    args = p.parse_args()

    from tensorflowonspark_tpu import cluster as TFCluster, configure_logging
    from tensorflowonspark_tpu.cluster import InputMode
    from tensorflowonspark_tpu.engine import LocalEngine

    configure_logging()
    engine = LocalEngine(
        args.cluster_size,
        # executors inherit the caller\'s platform; on the CPU each gets
        # one host device
        env={"XLA_FLAGS": "--xla_force_host_platform_device_count=1"},
    )
    cluster = TFCluster.run(
        engine, main_fun, vars(args), num_executors=args.cluster_size,
        input_mode=InputMode.TENSORFLOW, master_node="chief",
    )
    cluster.shutdown(grace_secs=2)
    engine.stop()
    print("export:", os.path.join(args.model_dir, "export"))


if __name__ == "__main__":
    main()
