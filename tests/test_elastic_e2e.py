"""Elastic resize-and-reshard acceptance (slow lane): a SIGKILLed
executor with a ZERO respawn budget and ``min_executors=1`` must shrink
the cluster to the survivor, which resumes from a checkpoint written
under the 8-device fold on a 4-device mesh (accum 2x) with loss
continuity and exactly-once feed accounting (docs/elastic.md).

The rigid cousin (full-strength respawn recovery) is
test_fault_tolerance_e2e.py; this file is the path where healing is
impossible and the cluster re-forms over what survives.
"""

import glob
import json
import os

import numpy as np
import pytest

from tensorflowonspark_tpu import cluster as TFCluster
from tensorflowonspark_tpu.cluster import InputMode
from tensorflowonspark_tpu.engine import LocalEngine
from tensorflowonspark_tpu.utils import faults, telemetry

pytestmark = [pytest.mark.slow, pytest.mark.elastic, pytest.mark.faults]

N_PART = 4
PER_PART = 320
CHUNK = 64  # 5 puts/partition; executor 1's 6th put = its 2nd partition
LOGICAL = 8  # virtual mesh: data=8, on 4*num_workers fake devices


def elastic_mnist_main(args, ctx):
    """MNIST CNN through the elastic runtime.  Each incarnation sees
    ``4 * num_workers`` of its executor's 8 fake CPU devices — 8 before
    the kill (accum 1), 4 after the shrink to one worker (accum 2) —
    for the SAME logical ``data=8`` mesh, and resumes through the
    resharding restore path."""
    import jax
    import jax.numpy as jnp
    import optax

    from tensorflowonspark_tpu.models import mnist
    from tensorflowonspark_tpu.utils import checkpoint as ckpt

    devices = jax.devices()[: 4 * ctx.num_workers]
    rt = ctx.elastic_runtime({"data": LOGICAL}, devices=devices)
    ckpt_dir = os.path.join(args["model_dir"], f"worker-{ctx.task_index}")
    log_path = os.path.join(args["model_dir"],
                            f"losses-{ctx.task_index}.jsonl")

    params = mnist.init_params(jax.random.PRNGKey(0))
    opt = optax.sgd(0.05, momentum=0.9)
    saved, start = ctx.restore_latest(
        ckpt_dir, target_shardings=lambda t: rt.fsdp_sharding(t))
    if saved is not None:
        params = saved["params"]  # fresh opt state after restart is fine
    else:
        params = rt.reshard(params)
    opt_state = opt.init(params)
    step_fn = jax.jit(mnist.make_train_step(opt))

    feed = ctx.get_data_feed(train_mode=True)
    step = start
    while not feed.should_stop():
        batch = feed.next_batch(32)
        if not batch:
            continue
        images = jax.device_put(
            np.stack([b[0] for b in batch]).astype(np.float32),
            rt.batch_sharding())
        labels = jax.device_put(
            np.asarray([b[1] for b in batch], dtype=np.int32),
            rt.batch_sharding())
        params, opt_state, loss, acc = step_fn(
            params, opt_state, images, labels)
        step += 1
        ckpt.save_checkpoint(
            ckpt_dir, {"params": params, "loss": jnp.asarray(float(loss))},
            step)
        with open(log_path, "a") as f:
            f.write(json.dumps({
                "epoch": ctx.epoch, "step": step, "loss": float(loss),
                "devices": rt.layout.n_physical,
                "accum": rt.layout.accum_steps,
            }) + "\n")


def _synthetic_records(n):
    rng = np.random.default_rng(0)
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
    return list(zip(list(images), list(labels)))


def _read_all(root):
    text = ""
    for path in glob.glob(os.path.join(str(root), "**", "*"), recursive=True):
        if os.path.isfile(path):
            with open(path, errors="replace") as f:
                text += f.read()
    return text


def test_kill_one_executor_resumes_on_smaller_mesh(tmp_path, monkeypatch):
    telemetry_dir = tmp_path / "telemetry"
    monkeypatch.setenv(telemetry.DIR_ENV, str(telemetry_dir))
    monkeypatch.chdir(tmp_path)
    # healing impossible: zero respawn budget (read by the DRIVER-side
    # engine at construction) forces the elastic shrink path
    monkeypatch.setenv("TFOS_EXECUTOR_RESPAWNS", "0")
    engine = LocalEngine(2, env={
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "TFOS_FEED_CHUNK": str(CHUNK),
        faults.PLAN_ENV: "feed.put:kill@6",
        faults.EXECUTOR_ENV: "1",
    })
    model_dir = tmp_path / "model"
    try:
        cluster = TFCluster.run(
            engine, elastic_mnist_main, {"model_dir": str(model_dir)},
            num_executors=2, input_mode=InputMode.SPARK,
            restarts=1, min_executors=1,
        )
        ds = engine.parallelize(_synthetic_records(N_PART * PER_PART), N_PART)
        cluster.train(ds, num_epochs=1, feed_timeout=240)

        assert cluster._restarts_used == 1
        # the cluster re-formed over the single survivor
        assert cluster.meta["cluster_template"] == {"worker": [0]}
        assert cluster.meta["num_executors"] == 1
        assert len(cluster.cluster_info) == 1
        # exactly-once feed accounting: every partition consumed exactly
        # once across both incarnations (the ledger re-fed only the
        # partitions the dead executor never finished)
        assert cluster.server.fed_partitions("input") == list(range(N_PART))
        cluster.shutdown(grace_secs=2)
    finally:
        engine.stop()
        for k in (telemetry.NODE_ENV, telemetry.ROLE_ENV,
                  telemetry.SPOOL_ENV):
            os.environ.pop(k, None)

    # the survivor trained in BOTH incarnations: epoch 0 on the 8-device
    # fold (accum 1), epoch 1 on the 4-device fold (accum 2), resuming
    # from the resharded checkpoint (step continuity) with its loss
    # continuing below the cold-start loss (value continuity)
    lines = [json.loads(ln) for ln in
             (model_dir / "losses-0.jsonl").read_text().splitlines()]
    e0 = [ln for ln in lines if ln["epoch"] == 0]
    e1 = [ln for ln in lines if ln["epoch"] == 1]
    assert e0 and e1, f"missing incarnation logs: {len(e0)}/{len(e1)}"
    assert all(ln["devices"] == 8 and ln["accum"] == 1 for ln in e0)
    assert all(ln["devices"] == 4 and ln["accum"] == 2 for ln in e1)
    assert e1[0]["step"] > 1, f"post-resize run restarted: {e1[0]}"
    assert e1[0]["loss"] < e0[0]["loss"], (
        f"loss continuity broken: resumed at {e1[0]['loss']:.4f} vs "
        f"cold start {e0[0]['loss']:.4f}")

    # resize is visible in telemetry: the cluster re-template, the
    # rendezvous requirement change, and the node-side runtime build
    raw = _read_all(telemetry_dir)
    for ev in ("cluster/resize", "rendezvous/resize", "elastic/from_context"):
        assert ev in raw, f"telemetry event {ev} missing from drained run"
