"""Model adapter ``resnet``: the program's ``models/resnet.py`` trained
as ``chip_smoke.train_main`` trains it (the repo's defaults: SGD with
momentum 0.9, bf16 compute, params in float32), fed raw uint8 images.

A record is ``(uint8[image, image, 3], int label)``, the shape
``chip_smoke.records_fn`` feeds; the record's global id is in the image's
first four bytes (little-endian) and the label is
``id % classes``.  Records come from a pool of seeded images made once
per feeder process, so the feeder is not held to the generator's speed.
"""

import functools
import os

from benchmark.lib import flops as F
from benchmark.lib.manifest import rehearsed
from benchmark.lib.memory import program_bytes


def sizes(ctx):
    return rehearsed(ctx["config"], ctx["rehearse"])


@functools.lru_cache(maxsize=2)
def _pool(seed, n, image):
    import numpy as np

    return np.random.default_rng([seed, 0x1AB]).integers(
        0, 256, (n, image * image * 3), dtype=np.uint8)


def record_source(ctx, per_part):
    """Partition function for the feeder task (no jax)."""
    cfg = sizes(ctx)
    seed, image, classes = ctx["seed"], cfg["image_size"], cfg["num_classes"]
    pool_n = int(ctx["mix"]["pool_records"])
    stop_flag = ctx["stop_flag"]

    def gen(parts):
        import numpy as np

        pool = _pool(seed, pool_n, image)
        for part in parts:
            if os.path.exists(stop_flag):
                return  # the trainer has ended the job: feed nothing more
            for off in range(per_part):
                rid = part * per_part + off
                rec = pool[rid % pool_n].copy()
                rec[:4] = np.frombuffer(
                    np.uint32(rid).astype("<u4").tobytes(), np.uint8)
                yield rec.reshape(image, image, 3), rid % classes

    return gen


class Trainer:
    items_per_record = 1
    input_mapping = {"image": "image", "label": "label"}

    def __init__(self, ctx, mesh, nproc):
        self.ctx, self.mesh, self.nproc = ctx, mesh, nproc
        self.cfg = sizes(ctx)
        self.per_process_batch = self.cfg["batch_per_chip"] \
            * int(ctx["mix"].get("chips_per_process", 1))
        self.flops_per_item = F.resnet_train_flops_per_image(
            self.cfg["depth"], self.cfg["image_size"],
            self.cfg["num_classes"])
        from tensorflowonspark_tpu.models import resnet

        # what the program's own count gives, for the line beside ours
        self.program_flops_per_item = 3 * resnet.flops_per_image(
            self.cfg["depth"], self.cfg["image_size"])

    def _init_fn(self):
        import jax
        import optax

        from tensorflowonspark_tpu.models import resnet

        cfg = self.cfg
        self.opt = optax.sgd(cfg["learning_rate"], momentum=cfg["momentum"])

        @jax.jit
        def init_all(key):
            params, state = resnet.init(key, depth=cfg["depth"],
                                        num_classes=cfg["num_classes"])
            return params, state, self.opt.init(params)

        return init_all

    def init(self):
        import jax

        from tensorflowonspark_tpu.parallel import shard_train_state

        state = self._init_fn()(jax.random.PRNGKey(self.ctx["seed"]))
        state, self.shardings = shard_train_state(self.mesh, *state)
        return state

    def compile(self, state, batch):
        import jax

        from tensorflowonspark_tpu.models import resnet
        from tensorflowonspark_tpu.parallel import batch_sharding

        cfg = self.cfg
        inner = resnet.make_train_step(
            self.opt, depth=cfg["depth"],
            compute_dtype=jax.numpy.dtype(cfg["compute_dtype"]))

        def step(state, batch):
            params, bn, opt_state, loss, _acc = inner(*state, *batch)
            return (params, bn, opt_state), loss

        sh = tuple(self.shardings)
        bs = batch_sharding(self.mesh)
        compiled = jax.jit(
            step, in_shardings=(sh, (bs, bs)), out_shardings=(sh, None),
            donate_argnums=(0,)).lower(state, batch).compile()
        return compiled, program_bytes(compiled)

    def collate(self, cols):
        import numpy as np

        image = self.cfg["image_size"]
        imgs = np.asarray(cols["image"], dtype=np.uint8).reshape(
            -1, image, image, 3)
        ids = np.ascontiguousarray(
            imgs.reshape(len(imgs), -1)[:, :4]).view("<u4")[:, 0]
        return ((imgs, np.asarray(cols["label"], dtype=np.int32)),
                ids.astype(np.int64))

    def reference_check(self, first_host, first_loss):
        """The trainer's loss on its first batch against the plain
        float32 reference on the same images and the same initial
        weights (made again from the seed)."""
        import jax
        import numpy as np

        from benchmark.reference import resnet as ref

        tol = self.cfg["reference_rtol"]
        if self.nproc > 1:
            from jax.experimental import multihost_utils
        params, _bn, _opt = self._init_fn()(
            jax.random.PRNGKey(self.ctx["seed"]))
        del _opt
        imgs, labels = first_host
        note = ""
        if self.nproc > 1:
            # batch norm takes its statistics over the GLOBAL batch, so
            # the reference needs every process's shard of the first batch
            imgs, labels = (np.concatenate(list(x)) for x in
                            multihost_utils.process_allgather(
                                (imgs, labels)))
            note = f" of the {self.nproc} processes together"
        with jax.default_matmul_precision("highest"):
            want = float(jax.jit(ref.loss)(params, imgs, labels))
        rel = abs(first_loss - want) / abs(want)
        return {"ok": bool(rel <= tol), "trainer_first_loss": first_loss,
                "reference_loss": want, "relative_difference": rel,
                "tolerance": tol, "what": "ResNet loss, whole first batch"
                + note}


def build(ctx, mesh, nproc):
    return Trainer(ctx, mesh, nproc)
