"""Minimum end-to-end slice (SURVEY.md §7 step 5):

MNIST CNN, InputMode.SPARK, 2 executor processes, TRUE multi-controller
data-parallel training — each executor joins one JAX SPMD job over CPU
(gloo collectives), the batch is mesh-sharded, XLA all-reduces the
gradients (the MultiWorkerMirroredStrategy parity path), and the chief
exports the model.

Parity: reference test_pipeline.py:89-172 + examples/mnist/keras/
mnist_spark.py (DataFeed generator → strategy.fit).
"""

import os

import numpy as np
import pytest

from tensorflowonspark_tpu import cluster as TFCluster
from tensorflowonspark_tpu.cluster import InputMode
from tensorflowonspark_tpu.engine import LocalEngine

BATCH = 64
STEPS = 30


def mnist_main(args, ctx):
    # runs inside the background training process on each executor
    import jax
    import jax.numpy as jnp
    import optax

    from tensorflowonspark_tpu.models import mnist
    from tensorflowonspark_tpu.parallel import make_mesh, local_to_global
    from tensorflowonspark_tpu.utils import checkpoint as ckpt
    from tensorflowonspark_tpu.utils.metrics import TrainMetrics

    env = ctx.jax_initialize()
    assert env["num_processes"] == 2, env
    assert jax.process_count() == 2

    mesh = make_mesh({"data": -1})
    params = mnist.init_params(jax.random.PRNGKey(0))
    opt = optax.sgd(0.05, momentum=0.9)
    opt_state = opt.init(params)
    step_fn = jax.jit(mnist.make_train_step(opt))

    # metrics feed both report() and (when TFOS_TELEMETRY_DIR is set)
    # the train/step + tfos/feed/ring_wait spans that trace_merge aggregates
    metrics = TrainMetrics()
    feed = ctx.get_data_feed(train_mode=True, metrics=metrics)
    losses = []
    per_proc = BATCH // env["num_processes"]
    while not feed.should_stop():
        batch = feed.next_batch(per_proc)
        if len(batch) < per_proc:
            continue  # drop ragged tail (global-stop handled by None marker)
        images = np.stack([b[0] for b in batch]).astype(np.float32)
        labels = np.asarray([b[1] for b in batch], dtype=np.int32)
        gimages, glabels = local_to_global(mesh, (images, labels))
        params, opt_state, loss, acc = step_fn(params, opt_state, gimages, glabels)
        losses.append(float(loss))
        metrics.step(per_proc)

    assert len(losses) >= 5, f"too few steps ran: {len(losses)}"
    first, last = np.mean(losses[:3]), np.mean(losses[-3:])
    with open("losses.txt", "w") as f:
        f.write(f"{first} {last} {len(losses)}")
    assert last < first, f"loss did not decrease: {first} -> {last}"
    ckpt.export_model(os.path.join(args["model_dir"], "export"), params, ctx)


@pytest.mark.slow
def test_mnist_spark_mode_e2e(tmp_path, monkeypatch):
    from tensorflowonspark_tpu.utils import telemetry

    # opt-in telemetry for the whole run (driver + executors + trainers):
    # the acceptance path is this e2e followed by scripts/trace_merge.py
    telemetry_dir = tmp_path / "telemetry"
    monkeypatch.setenv(telemetry.DIR_ENV, str(telemetry_dir))
    engine = LocalEngine(
        2,
        env={
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        },
    )
    try:
        cluster = TFCluster.run(
            engine,
            mnist_main,
            {"model_dir": str(tmp_path)},
            num_executors=2,
            input_mode=InputMode.SPARK,
            master_node="chief",
        )
        # synthetic, learnable dataset (see models.mnist.synthetic_batch)
        rng = np.random.default_rng(0)
        n = BATCH * STEPS
        images = rng.random((n, 28, 28, 1), dtype=np.float32)
        q = np.stack(
            [
                images[:, :14, :14, 0].mean((1, 2)),
                images[:, :14, 14:, 0].mean((1, 2)),
                images[:, 14:, :14, 0].mean((1, 2)),
                images[:, 14:, 14:, 0].mean((1, 2)),
            ],
            axis=-1,
        )
        labels = (np.argmax(q, axis=-1) * 2 + (q.sum(-1) > 2.0)).astype(np.int32)
        records = list(zip(list(images), list(labels)))
        ds = engine.parallelize(records, 4)
        cluster.train(ds, num_epochs=1, feed_timeout=240)
        cluster.shutdown(grace_secs=5)
        export = os.path.join(tmp_path, "export")
        assert os.path.exists(os.path.join(export, "params.npz")), (
            "chief did not export the model"
        )
        from tensorflowonspark_tpu.utils.checkpoint import load_exported

        params, meta = load_exported(export)
        assert meta["format"] == "tfos-tpu-export-v1"
        assert params["conv1"]["w"].shape == (3, 3, 1, 32)

        # --- telemetry: drained run dir -> Chrome trace + summary -------
        runs = [d for d in os.listdir(telemetry_dir)
                if d.startswith("run-")]
        assert len(runs) == 1, f"expected one drained run dir: {runs}"
        import subprocess
        import sys

        proc = subprocess.run(
            [sys.executable,
             os.path.join(os.path.dirname(os.path.dirname(
                 os.path.abspath(__file__))), "scripts", "trace_merge.py"),
             str(telemetry_dir)],
            capture_output=True, text=True,
            env=dict(os.environ, PYTHONPATH=""), timeout=120)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        import json

        trace = json.loads(
            (telemetry_dir / "trace.json").read_text())
        names = {e["name"] for e in trace["traceEvents"]}
        assert {"cluster/start", "node/boot", "train/step",
                "tfos/feed/ring_wait", "checkpoint/export"} <= names
        # per-node step percentiles + infeed-stall fraction made it into
        # the summary for both training nodes (master_node="chief")
        assert "chief-0" in proc.stdout and "worker-0" in proc.stdout
        assert "p50_ms" in proc.stdout and "stall" in proc.stdout
    finally:
        engine.stop()
        for k in (telemetry.NODE_ENV, telemetry.ROLE_ENV,
                  telemetry.SPOOL_ENV):
            os.environ.pop(k, None)  # cluster.run pinned driver identity
