"""Model adapter ``decoder``: the program's ``models/transformer.py``
decoder at a published model's sizes, trained on packed token sequences
with ``loss_fn``, Adam, full remat and the pallas flash backward.

A record is ``(int id, int32[seq] tokens)`` (DataFeed maps a record's
columns to the SORTED tensor names, so ``rid`` comes before ``tokens``);
the record's global id is also in the payload: ``tokens[0] = id % vocab``, ``tokens[1] = id // vocab``.
One chip only: GSPMD cannot partition the flash ``pallas_call``.
"""

import functools
import os

from benchmark.lib import flops as F
from benchmark.lib.manifest import rehearsed
from benchmark.lib.memory import program_bytes


def sizes(ctx):
    return rehearsed(ctx["config"], ctx["rehearse"])


def model_config(cfg, max_seq=None):
    """The program's ``transformer.Config`` at the configuration's sizes."""
    from tensorflowonspark_tpu.models import transformer

    if cfg["intermediate_size"] % cfg["hidden_size"]:
        raise ValueError("intermediate_size is not a multiple of hidden_size")
    return transformer.Config(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        max_seq=max_seq or cfg["max_position_embeddings"],
        mlp_ratio=cfg["intermediate_size"] // cfg["hidden_size"],
        rope_base=float(cfg["rotary_emb_base"]),
        dtype=cfg["compute_dtype"], attn_impl="flash")


@functools.lru_cache(maxsize=2)
def _pool(seed, n, seq, vocab):
    import numpy as np

    return np.random.default_rng([seed, 0x70C]).integers(
        1, vocab, (n, seq), dtype=np.int32)


def record_source(ctx, per_part):
    cfg = sizes(ctx)
    seed, seq, vocab = ctx["seed"], cfg["seq_len"], cfg["vocab_size"]
    pool_n = int(ctx["mix"]["pool_records"])
    stop_flag = ctx["stop_flag"]

    def gen(parts):
        pool = _pool(seed, pool_n, seq, vocab)
        for part in parts:
            if os.path.exists(stop_flag):
                return  # the trainer has ended the job: feed nothing more
            for off in range(per_part):
                rid = part * per_part + off
                rec = pool[rid % pool_n].copy()
                rec[0], rec[1] = rid % vocab, rid // vocab
                yield rid, rec

    return gen


class Trainer:
    input_mapping = {"rid": "rid", "tokens": "tokens"}

    def __init__(self, ctx, mesh, nproc):
        if nproc != 1 or mesh.size != 1:
            raise RuntimeError(
                "the decoder adapter trains on one chip: GSPMD cannot "
                "partition the flash pallas_call (needs shard_map)")
        self.ctx = ctx
        self.cfg = cfg = sizes(ctx)
        self.per_process_batch = cfg["batch_per_chip"]
        self.items_per_record = cfg["seq_len"]
        self.model = model_config(cfg, max_seq=cfg["seq_len"])
        mlp = cfg["intermediate_size"] // cfg["hidden_size"]
        shape = (cfg["hidden_size"], cfg["num_hidden_layers"],
                 cfg["vocab_size"], cfg["seq_len"], mlp)
        self.flops_per_item = F.decoder_train_flops_per_token(*shape)
        from tensorflowonspark_tpu.utils import metrics

        self.program_flops_per_item = metrics.transformer_flops_per_token(
            self.model)

    def _loss(self, params, tokens):
        from tensorflowonspark_tpu import ops
        from tensorflowonspark_tpu.models import transformer

        attn = functools.partial(ops.flash_attention, causal=True,
                                 bwd_impl="pallas")
        return transformer.loss_fn(params, tokens, self.model, attn_fn=attn,
                                   remat=True)

    def _init_params(self):
        import jax

        from tensorflowonspark_tpu.models import transformer

        return jax.jit(lambda key: transformer.init(key, self.model))(
            jax.random.PRNGKey(self.ctx["seed"]))

    def init(self):
        import jax
        import optax

        self.opt = optax.adam(self.cfg["learning_rate"])
        params = self._init_params()
        return params, jax.jit(self.opt.init)(params)

    def step(self, state, batch):
        import jax
        import optax

        params, opt_state = state
        loss, grads = jax.value_and_grad(self._loss)(params, batch[0])
        updates, opt_state = self.opt.update(grads, opt_state)
        return (optax.apply_updates(params, updates), opt_state), loss

    def compile(self, state, batch):
        import jax

        compiled = jax.jit(self.step, donate_argnums=(0,)).lower(
            state, batch).compile()
        return compiled, program_bytes(compiled)

    def collate(self, cols):
        import numpy as np

        toks = np.asarray(cols["tokens"], dtype=np.int32).reshape(
            -1, self.cfg["seq_len"])
        ids = toks[:, 0].astype(np.int64) \
            + toks[:, 1].astype(np.int64) * self.cfg["vocab_size"]
        if not np.array_equal(ids, np.asarray(cols["rid"], np.int64)):
            raise RuntimeError("a record's payload id and its id column "
                               "disagree: the wire corrupted a record")
        return (toks,), ids

    def reference_check(self, first_host, first_loss):
        """The program's loss (same options as the trained step: flash
        forward, pallas backward, remat, bf16) and its gradient for two
        named leaves, against the plain float32 reference, on the first
        two sequences of the first batch with the initial weights (made
        again from the seed).  ``layers.wo`` sees the attention forward,
        ``layers.ln1`` the attention backward of every layer."""
        import jax
        import numpy as np

        from benchmark.reference import decoder as ref

        cfg = self.cfg
        tokens = first_host[0][:2]
        params = self._init_params()
        leaves = ("ln1", "wo")

        def split(fn):
            def of_leaves(sub, rest, tokens):
                p = dict(rest, layers=dict(rest["layers"], **sub))
                return fn(p, tokens)
            return jax.jit(jax.value_and_grad(of_leaves))

        sub = {k: params["layers"][k] for k in leaves}
        got_loss, got = split(self._loss)(sub, params, tokens)
        heads = cfg["num_attention_heads"]
        with jax.default_matmul_precision("highest"):
            want_loss, want = split(
                lambda p, t: ref.loss(p, t, heads))(sub, params, tokens)
        out = {"what": "decoder loss and d loss / d layers.{ln1,wo}, two "
                       "sequences, program (bf16, flash, remat) vs float32 "
                       "reference",
               "loss": float(got_loss), "reference_loss": float(want_loss),
               "loss_tolerance": cfg["reference_rtol"],
               "grad_tolerance": cfg["reference_grad_tol"],
               "trainer_first_loss": first_loss}
        out["loss_relative_difference"] = abs(
            out["loss"] - out["reference_loss"]) / abs(out["reference_loss"])
        ok = out["loss_relative_difference"] <= cfg["reference_rtol"]
        for k in leaves:
            a = np.asarray(got[k], np.float32)
            b = np.asarray(want[k], np.float32)
            err = float(np.max(np.abs(a - b)) / np.max(np.abs(b)))
            out[f"grad_{k}_max_error_over_max"] = err
            ok = ok and err <= cfg["reference_grad_tol"]
        # the trained batch is larger than two sequences, so its loss is
        # another number; at initialisation both sit at ln(vocab) +- noise
        out["ok"] = bool(ok and np.isfinite(first_loss))
        return out


def build(ctx, mesh, nproc):
    return Trainer(ctx, mesh, nproc)
