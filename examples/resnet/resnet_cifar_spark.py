"""ResNet/CIFAR-10 distributed training via engine feeding
(parity: reference examples/resnet/resnet_cifar_spark.py +
resnet_cifar_dist.py — the "<10 lines to port" story: the model/training
code is the plain single-process JAX from models/resnet.py; only the
main_fun wrapper and the cluster launch below are framework-specific).

    python examples/resnet/resnet_cifar_spark.py --cluster_size 2 \\
        --steps 10 --depth 20
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def main_fun(args, ctx):
    import numpy as np
    import jax
    import optax

    from tensorflowonspark_tpu.models import resnet
    from tensorflowonspark_tpu.parallel import (
        batch_sharding, local_to_global, make_mesh, shard_train_state,
    )
    from tensorflowonspark_tpu.utils import checkpoint as ckpt

    env = ctx.jax_initialize()
    mesh = make_mesh({"data": -1})

    params, state = resnet.init(
        jax.random.PRNGKey(0), depth=args["depth"], num_classes=10,
        width=16, small_inputs=True,
    )
    opt = optax.sgd(args["lr"], momentum=0.9)
    opt_state = opt.init(params)

    # resume-from-checkpoint (the framework's recovery contract: restart
    # the job, pick up params/BN-state/optimizer/step from the newest
    # checkpoint in model_dir)
    ckpt_dir = os.path.join(args["model_dir"], "ckpt")
    restored, start_step = ckpt.restore_latest(ckpt_dir)
    if restored is not None:
        params = restored["params"]
        state = restored["state"]
        opt_state = ckpt.unpack_pytree(restored["opt"], opt_state)

    (params, state, opt_state), (p_sh, s_sh, o_sh) = shard_train_state(
        mesh, params, state, opt_state
    )
    step_fn = jax.jit(
        resnet.make_train_step(opt, depth=args["depth"], small_inputs=True),
        in_shardings=(p_sh, s_sh, o_sh, batch_sharding(mesh),
                      batch_sharding(mesh)),
        out_shardings=(p_sh, s_sh, o_sh, None, None),
        donate_argnums=(0, 1, 2),
    )

    feed = ctx.get_data_feed(train_mode=True)
    per_proc = args["batch_size"] // max(env["num_processes"], 1)
    save_every = args.get("save_every", 25)

    def save(step):
        ckpt.save_checkpoint(
            ckpt_dir,
            {"params": params, "state": state,
             "opt": ckpt.pack_pytree(opt_state)},
            step,
        )

    step = start_step
    while not feed.should_stop():
        batch = feed.next_batch(per_proc)
        if len(batch) < per_proc:
            continue
        images = np.stack([b[0] for b in batch]).astype(np.float32)
        labels = np.asarray([b[1] for b in batch], dtype=np.int32)
        gi, gl = local_to_global(mesh, (images, labels))
        params, state, opt_state, loss, acc = step_fn(
            params, state, opt_state, gi, gl
        )
        step += 1
        if step % 5 == 0 and ctx.task_index == 0:
            print(f"step {step}: loss={float(loss):.4f} acc={float(acc):.3f}")
        if step % save_every == 0 and ckpt.is_chief(ctx):
            save(step)

    if ckpt.is_chief(ctx):
        save(step)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--cluster_size", type=int, default=2)
    p.add_argument("--batch_size", type=int, default=64)
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--epochs", type=int, default=1)
    p.add_argument("--depth", type=int, default=20,
                   help="CIFAR plans: 20/32/44/56/110")
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--model_dir", default="/tmp/resnet_cifar")
    args = p.parse_args()

    import numpy as np

    from tensorflowonspark_tpu import cluster as TFCluster, configure_logging
    from tensorflowonspark_tpu.cluster import InputMode
    from tensorflowonspark_tpu.engine import LocalEngine

    configure_logging()
    # synthetic CIFAR-shaped data (no egress in this environment)
    rng = np.random.default_rng(0)
    n = args.batch_size * args.steps
    images = rng.random((n, 32, 32, 3), dtype=np.float32)
    labels = (images.mean((1, 2, 3)) * 10).astype(np.int32) % 10
    records = list(zip(list(images), list(labels)))

    engine = LocalEngine(
        args.cluster_size,
        # executors inherit the caller\'s platform; on the CPU each gets
        # one host device
        env={"XLA_FLAGS": "--xla_force_host_platform_device_count=1"},
    )
    cluster = TFCluster.run(
        engine, main_fun,
        {"batch_size": args.batch_size, "lr": args.lr,
         "depth": args.depth, "model_dir": args.model_dir},
        num_executors=args.cluster_size, input_mode=InputMode.SPARK,
        master_node="chief",
    )
    cluster.train(engine.parallelize(records, args.cluster_size * 2),
                  num_epochs=args.epochs)
    cluster.shutdown(grace_secs=5)
    engine.stop()


if __name__ == "__main__":
    main()
