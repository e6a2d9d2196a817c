"""MNIST via the ML Pipeline API: TFEstimator.fit -> TFModel.transform
(parity: reference examples/mnist/keras/mnist_pipeline.py — Estimator
trains over InputMode.SPARK feeding, chief exports, Model runs
cached-model batch inference per worker).

    python examples/mnist/mnist_pipeline.py --cluster_size 2 --steps 30
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def train_fun(args, ctx):
    import numpy as np
    import jax
    import optax

    from tensorflowonspark_tpu.models import mnist
    from tensorflowonspark_tpu.parallel import make_mesh, local_to_global
    from tensorflowonspark_tpu.utils import checkpoint as ckpt

    env = ctx.jax_initialize()
    mesh = make_mesh({"data": -1})
    params = mnist.init_params(jax.random.PRNGKey(0))
    opt = optax.sgd(args.lr, momentum=0.9)
    opt_state = opt.init(params)
    step_fn = jax.jit(mnist.make_train_step(opt))

    feed = ctx.get_data_feed(train_mode=True)
    per_proc = args.batch_size // max(env["num_processes"], 1)
    while not feed.should_stop():
        batch = feed.next_batch(per_proc)
        if len(batch) < per_proc:
            continue
        # rows arrive as (image-flat-784, label) tuples from the dataset
        images = np.asarray([b[0] for b in batch], np.float32).reshape(
            -1, 28, 28, 1
        )
        labels = np.asarray([b[1] for b in batch], np.int32)
        gi, gl = local_to_global(mesh, (images, labels))
        params, opt_state, loss, acc = step_fn(params, opt_state, gi, gl)

    if ckpt.is_chief(ctx):
        ckpt.export_model(
            args.export_dir, params, ctx,
            metadata={"predict": "tensorflowonspark_tpu.models.mnist:predict"},
        )


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--cluster_size", type=int, default=2)
    p.add_argument("--batch_size", type=int, default=64)
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--epochs", type=int, default=1)
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--export_dir", default="/tmp/mnist_pipeline/export")
    args = p.parse_args()

    import numpy as np

    from tensorflowonspark_tpu import configure_logging, pipeline
    from tensorflowonspark_tpu.engine import LocalEngine
    from mnist_data_setup import synthetic_mnist

    configure_logging()
    images, labels = synthetic_mnist(args.batch_size * args.steps)
    rows = [
        (img.ravel().tolist(), int(lbl)) for img, lbl in zip(images, labels)
    ]

    engine = LocalEngine(
        args.cluster_size,
        # executors inherit the caller\'s platform; on the CPU each gets
        # one host device
        env={"XLA_FLAGS": "--xla_force_host_platform_device_count=1"},
    )
    ds = engine.parallelize(rows, args.cluster_size * 2)

    estimator = (
        pipeline.TFEstimator(train_fun, vars(args))
        .setClusterSize(args.cluster_size)
        .setEpochs(args.epochs)
        .setBatchSize(args.batch_size)
        .setExportDir(args.export_dir)
    )
    model = estimator.fit(ds)

    model = (
        model.setBatchSize(args.batch_size)
        .setInputMapping({"image": "x"})
        .setOutputMapping({"prediction": "pred"})
    )
    test_rows = [{"image": r[0], "label": r[1]} for r in rows[:256]]
    preds = model.transform(engine.parallelize(test_rows, 2)).collect()
    correct = sum(
        int(p["pred"]) == r["label"] for p, r in zip(preds, test_rows)
    )
    engine.stop()
    print(f"accuracy on {len(preds)} training rows: {correct / len(preds):.3f}")


if __name__ == "__main__":
    main()
