"""Long-context transformer training on a dp x seq x model mesh — the
capability the reference never had (SURVEY.md §5 "Long-context: absent"):
ring-attention sequence parallelism splits the context across devices so
the per-device attention memory is O((S/n)^2) instead of O(S^2).

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \\
        python examples/transformer/transformer_longcontext.py \\
        --seq_len 512 --steps 5
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batch_size", type=int, default=4)
    p.add_argument("--seq_len", type=int, default=512)
    p.add_argument("--steps", type=int, default=5)
    p.add_argument("--dim", type=int, default=64)
    p.add_argument("--n_layers", type=int, default=2)
    p.add_argument("--n_heads", type=int, default=4)
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--attn", choices=["ring", "zigzag", "ulysses"],
                   default="ring")
    args = p.parse_args()

    import jax

    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tensorflowonspark_tpu.models import transformer
    from tensorflowonspark_tpu.parallel import sequence_parallel_attention

    devs = jax.devices()
    n = len(devs)
    seq = max(n // 4, 1) * 2 if n >= 8 else max(n // 2, 1)
    model = 2 if n % 2 == 0 and n >= 4 else 1
    if args.attn == "ulysses":
        # ulysses re-shards seq->heads: the per-model-shard head count
        # must divide by the seq axis; the shrunk seq must also keep
        # dividing the device count (seq*model | n) or the mesh reshape
        # would fail
        while seq > 1 and ((args.n_heads // model) % seq
                           or n % (seq * model)):
            seq //= 2
    data = n // (seq * model)
    mesh = Mesh(np.array(devs).reshape(data, seq, model),
                ("data", "seq", "model"))
    print(f"mesh: {dict(mesh.shape)} for seq_len={args.seq_len}")

    cfg = transformer.Config(
        vocab_size=args.vocab, dim=args.dim, n_layers=args.n_layers,
        n_heads=args.n_heads, max_seq=args.seq_len, dtype="float32",
        attn_impl="reference",
    )
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    specs = jax.tree.map(
        lambda s: NamedSharding(mesh, s), transformer.param_specs(cfg, mesh=mesh),
        is_leaf=lambda x: isinstance(x, P),
    )
    params = jax.device_put(params, specs)
    opt = optax.adam(args.lr)
    opt_state = opt.init(params)
    if args.attn == "zigzag":
        # production zigzag: tokens are permuted ONCE per batch
        # (zigzag_lm_batch), rope positions and next-token labels are
        # explicit, and the loss runs directly on the permuted layout —
        # no per-layer gathers; the causal ring's critical path halves
        from tensorflowonspark_tpu.parallel import zigzag_permutation

        attn_fn = sequence_parallel_attention(mesh, "zigzag", causal=True)
        zz_perm = zigzag_permutation(args.seq_len, mesh.shape["seq"])
    else:
        attn_fn = sequence_parallel_attention(mesh, args.attn, causal=True)
        zz_perm = None

    @jax.jit
    def step(params, opt_state, tokens):
        toks, labels, positions = (
            transformer.zigzag_lm_batch(tokens, zz_perm)
            if zz_perm is not None else (tokens, None, None)
        )
        loss, grads = jax.value_and_grad(transformer.loss_fn)(
            params, toks, cfg, attn_fn=attn_fn, labels=labels,
            positions=positions,
        )
        updates, opt_state = opt.update(grads, opt_state)
        return optax.apply_updates(params, updates), opt_state, loss

    # token stream: next token = (2*prev + 1) % vocab — learnable pattern
    rng = np.random.default_rng(0)
    tok_sh = NamedSharding(mesh, P("data", "seq"))
    for i in range(1, args.steps + 1):
        start = rng.integers(0, args.vocab, (args.batch_size, 1))
        toks = [start]
        for _ in range(args.seq_len - 1):
            toks.append((2 * toks[-1] + 1) % args.vocab)
        tokens = jax.device_put(
            jnp.asarray(np.concatenate(toks, axis=1), jnp.int32), tok_sh
        )
        params, opt_state, loss = step(params, opt_state, tokens)
        print(f"step {i}: loss={float(loss):.4f}")


if __name__ == "__main__":
    main()
