"""MNIST with a sidecar evaluator node (``eval_node=True``).

Parity: reference examples/mnist/estimator/mnist_tf.py:107 — the
estimator example runs `train_and_evaluate` with a dedicated evaluator
task (`TFCluster.run(..., eval_node=True)`, reference
examples/mnist/estimator/mnist_tf.py:116).  The TPU-first re-design
keeps the role but drops the Estimator machinery: the chief writes
step-stamped checkpoints (utils.checkpoint.save_checkpoint) and the
evaluator is a sidecar loop that polls the checkpoint dir, evaluates
each new step on a held-out set, and appends one JSON line per
evaluation — the TF2 `SidecarEvaluator` pattern, no train-loop
coupling.

    python examples/mnist/mnist_eval.py --cluster_size 3 --steps 40

cluster_size counts ALL nodes: 1 evaluator + 1 chief + workers.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def _eval_loop(args, ctx):
    """Evaluator role: not part of the SPMD job (owns no chips); polls
    checkpoints until the chief publishes the DONE marker, then drains
    whatever checkpoint is newest and exits."""
    import numpy as np
    import jax

    from mnist_data_setup import synthetic_mnist
    from tensorflowonspark_tpu.models import mnist
    from tensorflowonspark_tpu.utils import checkpoint as ckpt

    images, labels = synthetic_mnist(args["num_examples"], seed=1)  # held-out
    apply_fn = jax.jit(mnist.apply)
    log_path = os.path.join(args["model_dir"], "eval_results.jsonl")
    done_path = os.path.join(args["model_dir"], "DONE")
    ckpt_dir = os.path.join(args["model_dir"], "ckpt")

    seen = -1
    deadline = time.monotonic() + args["eval_timeout"]
    while True:
        latest = ckpt.latest_checkpoint(ckpt_dir)
        step = ckpt.step_of(latest) if latest else -1
        if latest and step > seen:
            params = ckpt.load_checkpoint(latest)
            logits = np.asarray(apply_fn(params, images))
            acc = float((logits.argmax(-1) == labels).mean())
            rec = {"step": step, "accuracy": acc, "examples": len(labels)}
            with open(log_path, "a") as f:
                f.write(json.dumps(rec) + "\n")
            print(f"evaluator: step {step} accuracy={acc:.3f}", flush=True)
            seen = step
            # the timeout bounds IDLE time, not total run time: a long
            # training run with steady checkpoints is healthy progress
            deadline = time.monotonic() + args["eval_timeout"]
            continue  # immediately re-check: never sleep behind a backlog
        if os.path.exists(done_path):
            # ack AFTER draining the newest checkpoint: the chief blocks
            # on this marker so shutdown can never kill a mid-flight
            # final evaluation (the evaluator child is a daemon process)
            tmp = os.path.join(args["model_dir"], f".eval_done.{os.getpid()}")
            with open(tmp, "w") as f:
                f.write(str(seen))
            os.replace(tmp, os.path.join(args["model_dir"], "EVAL_DONE"))
            print(f"evaluator: DONE after step {seen}", flush=True)
            return seen
        if time.monotonic() > deadline:
            raise TimeoutError(
                f"evaluator: no DONE marker within {args['eval_timeout']}s")
        time.sleep(0.2)


def main_fun(args, ctx):
    if ctx.job_name == "evaluator":
        return _eval_loop(args, ctx)

    import numpy as np
    import jax
    import optax

    from mnist_data_setup import synthetic_mnist
    from tensorflowonspark_tpu.models import mnist
    from tensorflowonspark_tpu.parallel import make_mesh, local_to_global
    from tensorflowonspark_tpu.utils import checkpoint as ckpt

    env = ctx.jax_initialize()
    mesh = make_mesh({"data": -1})

    # shard by the contiguous SPMD process id, NOT ctx.task_index:
    # task_index is per-job, so with a chief role chief:0 and worker:0
    # would both select shard 0 and one shard would never be trained
    images, labels = synthetic_mnist(args["num_examples"], seed=0)
    shard = (np.arange(len(images)) % env["num_processes"]
             == env["process_id"])
    images, labels = images[shard], labels[shard]

    params = mnist.init_params(jax.random.PRNGKey(0))
    opt = optax.sgd(args["lr"], momentum=0.9)
    opt_state = opt.init(params)
    step_fn = jax.jit(mnist.make_train_step(opt))

    ckpt_dir = os.path.join(args["model_dir"], "ckpt")
    per_proc = args["batch_size"] // max(env["num_processes"], 1)
    rng = np.random.default_rng(ctx.task_index)
    loss = acc = 0.0
    for step in range(1, args["steps"] + 1):
        idx = rng.integers(0, len(images), per_proc)
        gi, gl = local_to_global(
            mesh, (images[idx], labels[idx].astype(np.int32)))
        params, opt_state, loss, acc = step_fn(params, opt_state, gi, gl)
        if step % args["ckpt_steps"] == 0 and ckpt.is_chief(ctx):
            ckpt.save_checkpoint(ckpt_dir, params, step)

    if ckpt.is_chief(ctx):
        if args["steps"] % args["ckpt_steps"] != 0:
            ckpt.save_checkpoint(ckpt_dir, params, args["steps"])
        # atomic DONE publish AFTER the final checkpoint: the evaluator
        # drains the newest step before honoring the marker
        tmp = os.path.join(args["model_dir"], f".done.{os.getpid()}")
        with open(tmp, "w") as f:
            f.write("done")
        os.replace(tmp, os.path.join(args["model_dir"], "DONE"))
        # hold the worker slot open until the evaluator acks: shutdown
        # fires once workers return, and must not reap a final eval
        ack = os.path.join(args["model_dir"], "EVAL_DONE")
        deadline = time.monotonic() + args["eval_timeout"]
        while not os.path.exists(ack):
            if time.monotonic() > deadline:
                raise TimeoutError("evaluator never acked DONE")
            time.sleep(0.2)
    return float(acc)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--cluster_size", type=int, default=3,
                   help="total nodes: 1 evaluator + 1 chief + workers")
    p.add_argument("--batch_size", type=int, default=64)
    p.add_argument("--steps", type=int, default=40)
    p.add_argument("--ckpt_steps", type=int, default=10)
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--num_examples", type=int, default=2048)
    p.add_argument("--eval_timeout", type=float, default=300.0)
    p.add_argument("--model_dir", default="/tmp/mnist_model_eval")
    args = p.parse_args()

    from tensorflowonspark_tpu import cluster as TFCluster, configure_logging
    from tensorflowonspark_tpu.cluster import InputMode
    from tensorflowonspark_tpu.engine import LocalEngine

    configure_logging()
    os.makedirs(args.model_dir, exist_ok=True)
    # a reused model_dir must start clean: a stale DONE/EVAL_DONE pair
    # makes the evaluator exit immediately and the chief's ack-wait pass
    # on the previous run's marker, and old checkpoints (step >= this
    # run's) would shadow every new one under the `step > seen` rule
    import contextlib
    import shutil

    for marker in ("DONE", "EVAL_DONE", "eval_results.jsonl"):
        with contextlib.suppress(FileNotFoundError):
            os.remove(os.path.join(args.model_dir, marker))
    shutil.rmtree(os.path.join(args.model_dir, "ckpt"), ignore_errors=True)
    engine = LocalEngine(
        args.cluster_size,
        env={"PYTHONPATH": os.path.dirname(os.path.abspath(__file__)),
             "XLA_FLAGS": "--xla_force_host_platform_device_count=1"},
    )
    cluster = TFCluster.run(
        engine, main_fun, vars(args), num_executors=args.cluster_size,
        input_mode=InputMode.TENSORFLOW, master_node="chief",
        eval_node=True,
    )
    cluster.shutdown(grace_secs=2)
    engine.stop()
    log = os.path.join(args.model_dir, "eval_results.jsonl")
    with open(log) as f:
        evals = [json.loads(ln) for ln in f]
    print(f"evaluations: {[(e['step'], round(e['accuracy'], 3)) for e in evals]}")


if __name__ == "__main__":
    main()
