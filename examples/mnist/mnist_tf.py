"""MNIST, InputMode.TENSORFLOW: every node reads its own data shard directly
(parity: reference examples/mnist/keras/mnist_tf.py — no feeders; the
cluster only provides rendezvous + roles and each worker builds its own
input pipeline).

    python examples/mnist/mnist_tf.py --cluster_size 2 --steps 40
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def main_fun(args, ctx):
    import numpy as np
    import jax
    import optax

    from mnist_data_setup import synthetic_mnist
    from tensorflowonspark_tpu.models import mnist
    from tensorflowonspark_tpu.parallel import make_mesh, local_to_global

    env = ctx.jax_initialize()
    mesh = make_mesh({"data": -1})

    # host-sharded input pipeline: each process owns a disjoint slice.
    # Shard by the contiguous SPMD process id, NOT ctx.task_index —
    # task_index is per-job, so with master_node="chief" chief:0 and
    # worker:0 would both read shard 0 and one shard would go unread.
    images, labels = synthetic_mnist(args["num_examples"], seed=0)
    shard = (np.arange(len(images)) % env["num_processes"]
             == env["process_id"])
    images, labels = images[shard], labels[shard]

    params = mnist.init_params(jax.random.PRNGKey(0))
    opt = optax.sgd(args["lr"], momentum=0.9)
    opt_state = opt.init(params)
    step_fn = jax.jit(mnist.make_train_step(opt))

    per_proc = args["batch_size"] // max(env["num_processes"], 1)
    rng = np.random.default_rng(ctx.task_index)
    loss = acc = 0.0
    for step in range(1, args["steps"] + 1):
        idx = rng.integers(0, len(images), per_proc)
        gi, gl = local_to_global(
            mesh, (images[idx], labels[idx].astype(np.int32))
        )
        params, opt_state, loss, acc = step_fn(params, opt_state, gi, gl)
        if step % 10 == 0 and ctx.task_index == 0:
            print(f"step {step}: loss={float(loss):.4f} acc={float(acc):.3f}")

    from tensorflowonspark_tpu.utils import checkpoint as ckpt

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
    p.add_argument("--num_examples", type=int, default=2048)
    p.add_argument("--model_dir", default="/tmp/mnist_model_tf")
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
        engine, main_fun, vars(args), num_executors=args.cluster_size,
        input_mode=InputMode.TENSORFLOW, master_node="chief",
    )
    cluster.shutdown(grace_secs=2)
    engine.stop()
    print("export:", os.path.join(args.model_dir, "export"))


if __name__ == "__main__":
    main()
