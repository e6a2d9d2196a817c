"""Elastic-runtime bench driver: one JSON line on stdout.

Run by bench.py's ``elastic`` lane in a SUBPROCESS with
``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8``:
the lane resizes a mesh of VIRTUAL devices, so it is CPU-only by design
and never competes with the bench parent for the chip.
A real file because the engine's spawn start method cannot import
heredoc drivers.

Measures the elastic hot path on fake CPU devices: build an 8-virtual
mesh, shard the mnist train state, resize 8 -> 4 physical (accum x2),
reshard the live state, and resume a checkpoint cross-mesh through
``restore_any(target_shardings=...)`` (docs/elastic.md).
"""

import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import jax
    import jax.numpy as jnp
    import optax

    from tensorflowonspark_tpu import elastic
    from tensorflowonspark_tpu.models import mnist
    from tensorflowonspark_tpu.utils import checkpoint as ckpt

    devices = jax.devices()
    if len(devices) < 8:
        print(json.dumps({"error": f"need 8 fake devices, "
                                   f"got {len(devices)}"}))
        return 1

    spec = elastic.TrainSpec({"data": 8}, global_batch=256)
    t0 = time.perf_counter()
    rt = elastic.ElasticRuntime(spec, devices=devices[:8])
    build_ms = (time.perf_counter() - t0) * 1e3

    params = mnist.init_params(jax.random.PRNGKey(0))
    opt = optax.sgd(1e-2)
    opt_state = opt.init(params)
    (params, state, opt_state), _ = rt.shard_train_state(
        params, {"step": jnp.zeros((), jnp.int32)}, opt_state)

    tmp = tempfile.mkdtemp(prefix="tfos_bench_elastic_")
    try:
        ckpt.save_checkpoint(tmp, params, step=7)

        t0 = time.perf_counter()
        rt.resize(devices=devices[:4])
        resize_ms = (time.perf_counter() - t0) * 1e3

        t0 = time.perf_counter()
        (params, state, opt_state), _ = rt.reshard_train_state(
            params, state, opt_state)
        jax.block_until_ready(params)
        reshard_ms = (time.perf_counter() - t0) * 1e3

        t0 = time.perf_counter()
        restored, step = rt.restore(tmp)
        jax.block_until_ready(restored)
        restore_ms = (time.perf_counter() - t0) * 1e3

        sched = rt.batch_schedule()
        print(json.dumps({
            "build_ms": round(build_ms, 2),
            "resize_ms": round(resize_ms, 2),
            "reshard_ms": round(reshard_ms, 2),
            "restore_ms": round(restore_ms, 2),
            "restored_step": int(step),
            "accum_steps": sched["accum_steps"],
            "microbatch": sched["microbatch"],
            "devices": rt.layout.n_physical,
            "virtual_devices": rt.layout.n_virtual,
        }))
        return 0
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.exit(main())
