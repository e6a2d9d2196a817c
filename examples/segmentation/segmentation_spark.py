"""U-Net segmentation, cluster-fed — step 3 of the conversion ladder
(parity: reference examples/segmentation/segmentation_spark.py: the
dist version's training loop, with the input pipeline swapped for the
cluster DataFeed and an extra ~10 lines of launch plumbing).

    python examples/segmentation/segmentation_spark.py --cluster_size 2 \\
        --steps 6
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def main_fun(args, ctx):
    import numpy as np
    import jax
    import optax

    from tensorflowonspark_tpu.models import segmentation
    from tensorflowonspark_tpu.parallel import local_to_global, make_mesh
    from tensorflowonspark_tpu.utils import checkpoint as ckpt

    env = ctx.jax_initialize()
    mesh = make_mesh({"data": -1})
    params, state = segmentation.init(
        jax.random.PRNGKey(0), num_classes=3, width=args["width"]
    )
    opt = optax.adam(args["lr"])
    opt_state = opt.init(params)
    step_fn = jax.jit(segmentation.make_train_step(opt))

    feed = ctx.get_data_feed(train_mode=True)
    per_proc = args["batch_size"] // max(env["num_processes"], 1)
    step = 0
    while not feed.should_stop():
        batch = feed.next_batch(per_proc)
        if len(batch) < per_proc:
            continue
        images = np.stack([b[0] for b in batch]).astype(np.float32)
        masks = np.stack([b[1] for b in batch]).astype(np.int32)
        gi, gm = local_to_global(mesh, (images, masks))
        params, state, opt_state, loss = step_fn(
            params, state, opt_state, gi, gm
        )
        step += 1
        if step % 5 == 0 and ctx.task_index == 0:
            print(f"step {step}: loss={float(loss):.4f}")

    if ckpt.is_chief(ctx):
        ckpt.save_checkpoint(
            os.path.join(args["model_dir"], "ckpt"), params, step
        )


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--cluster_size", type=int, default=2)
    p.add_argument("--batch_size", type=int, default=8)
    p.add_argument("--steps", type=int, default=6)
    p.add_argument("--image_size", type=int, default=64)
    p.add_argument("--width", type=float, default=0.5)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--model_dir", default="/tmp/segmentation_model")
    args = p.parse_args()

    from tensorflowonspark_tpu import cluster as TFCluster, configure_logging
    from tensorflowonspark_tpu.cluster import InputMode
    from tensorflowonspark_tpu.engine import LocalEngine
    from segmentation import synthetic_pets

    configure_logging()
    images, masks = synthetic_pets(
        args.batch_size * args.steps, hw=args.image_size
    )
    records = list(zip(list(images), list(masks)))

    engine = LocalEngine(
        args.cluster_size,
        # executors inherit the caller\'s platform; on the CPU each gets
        # one host device
        env={"XLA_FLAGS": "--xla_force_host_platform_device_count=1"},
    )
    cluster = TFCluster.run(
        engine, main_fun,
        {"batch_size": args.batch_size, "lr": args.lr,
         "width": args.width, "model_dir": args.model_dir},
        num_executors=args.cluster_size, input_mode=InputMode.SPARK,
        master_node="chief",
    )
    cluster.train(engine.parallelize(records, args.cluster_size * 2))
    cluster.shutdown(grace_secs=5)
    engine.stop()


if __name__ == "__main__":
    main()
