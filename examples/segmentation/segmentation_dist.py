"""U-Net segmentation on a device mesh — step 2 of the conversion ladder
(parity: reference examples/segmentation/segmentation_dist.py, which adds
TF_CONFIG + MultiWorkerMirroredStrategy; here the same delta is a mesh +
sharded batch: ~6 changed lines from segmentation.py).

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python examples/segmentation/segmentation_dist.py --steps 10
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

from segmentation import synthetic_pets


def train(args):
    import jax

    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tensorflowonspark_tpu.models import segmentation
    from tensorflowonspark_tpu.parallel import make_mesh

    mesh = make_mesh({"data": -1})                                   # (+1)
    bsh = NamedSharding(mesh, P("data"))                             # (+2)

    images, masks = synthetic_pets(args.batch_size * 4, hw=args.image_size)
    params, state = segmentation.init(
        jax.random.PRNGKey(0), num_classes=3, width=args.width
    )
    opt = optax.adam(args.lr)
    opt_state = opt.init(params)
    step_fn = jax.jit(segmentation.make_train_step(opt))

    rng = np.random.default_rng(0)
    for step in range(1, args.steps + 1):
        idx = rng.integers(0, len(images), args.batch_size)
        gi = jax.device_put(images[idx], bsh)                        # (+3)
        gm = jax.device_put(masks[idx], bsh)                         # (+4)
        params, state, opt_state, loss = step_fn(
            params, state, opt_state, gi, gm
        )
        if step % 5 == 0:
            print(f"step {step}: loss={float(loss):.4f} "
                  f"(mesh={dict(mesh.shape)})")
    return params, state


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batch_size", type=int, default=8)
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--image_size", type=int, default=64)
    p.add_argument("--width", type=float, default=0.5)
    p.add_argument("--lr", type=float, default=1e-3)
    args = p.parse_args()
    train(args)


if __name__ == "__main__":
    main()
