"""Profile the transformer train step on the attached TPU and print the
top ops by self-time, grouped by fusion kind.

Same xplane pipeline as scripts/profile_resnet.py, pointed at the
flagship transformer config (dim 1024 / 8L / seq 2048, the sweep's
shape).  Defaults mirror the currently promoted bench_config.json
"transformer" section when one exists, so profiling the winner is just
``python scripts/profile_transformer.py --out TRANSFORMER_BREAKDOWN.md``.

Usage: python scripts/profile_transformer.py [--steps N] [--batch N]
    [--block-q N] [--block-kv N] [--remat {0,1,dots}]
    [--bwd {xla,pallas}] [--ce {dense,block}] [--out FILE.md]
"""

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from profile_resnet import parse_xplane, summarize  # noqa: E402


def _promoted():
    import bench

    path = bench.bench_config_path()
    if os.path.exists(path):
        try:
            with open(path) as f:
                return json.load(f).get("transformer", {})
        except (OSError, ValueError):
            pass
    return {}


def main():
    promoted = _promoted()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--batch", type=int,
                    default=int(promoted.get("batch", 32)))
    ap.add_argument("--block-q", type=int,
                    default=int(promoted.get("block_q", 512)))
    ap.add_argument("--block-kv", type=int,
                    default=int(promoted.get("block_kv", 512)))
    ap.add_argument("--remat", default=promoted.get("remat", True),
                    help="0/1 or the selective policy name dots")
    ap.add_argument("--bwd", choices=("xla", "pallas"),
                    default=promoted.get("bwd", "pallas"))
    ap.add_argument("--ce", choices=("dense", "block"),
                    default=promoted.get("ce", "dense"))
    ap.add_argument("--out", default=None,
                    help="also write the breakdown as markdown")
    args = ap.parse_args()
    remat = args.remat
    if remat in ("0", "False", False, 0):
        remat = False
    elif remat in ("1", "True", True, 1):
        remat = True
    elif remat != "dots":
        raise SystemExit(f"bad --remat {remat!r}")

    from tensorflowonspark_tpu.utils import compile_cache

    compile_cache.export_env()  # before jax reads it at import
    import jax
    import jax.numpy as jnp
    import optax
    from jax import lax

    from tensorflowonspark_tpu import ops
    from tensorflowonspark_tpu.models import transformer
    from tensorflowonspark_tpu.utils import metrics as M

    tiny = os.environ.get("TFOS_PROFILE_TINY") == "1"  # off-chip smoke
    cfg = transformer.Config(
        vocab_size=512 if tiny else 16384,
        dim=128 if tiny else 1024,
        n_layers=2 if tiny else 8,
        n_heads=4 if tiny else 8,
        max_seq=128 if tiny else 2048,
        dtype="float32" if tiny else "bfloat16",
        attn_impl="flash",
    )
    if tiny:
        args.batch = 1
        args.block_q = args.block_kv = 128
    attn_fn = functools.partial(
        ops.flash_attention, causal=True, block_q=args.block_q,
        block_kv=args.block_kv, bwd_impl=args.bwd)
    ce_impl = "blockwise" if args.ce == "block" else "dense"

    print("init...", flush=True)
    opt = optax.adam(1e-3)

    @jax.jit
    def init_all(key):
        params = transformer.init(key, cfg)
        return params, opt.init(params)

    params, opt_state = init_all(jax.random.PRNGKey(0))
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(
            0, cfg.vocab_size, (args.batch, cfg.max_seq)), jnp.int32)

    @jax.jit
    def run_steps(params, opt_state, tokens):
        def body(carry, _):
            p, o = carry
            loss, grads = jax.value_and_grad(transformer.loss_fn)(
                p, tokens, cfg, attn_fn=attn_fn, remat=remat,
                ce_impl=ce_impl, ce_block=min(2048, cfg.vocab_size))
            updates, o = opt.update(grads, o)
            return (optax.apply_updates(p, updates), o), loss
        (_, _), losses = lax.scan(body, (params, opt_state), None,
                                  length=args.steps)
        return losses[-1]

    print("compiling...", flush=True)
    float(run_steps(params, opt_state, tokens))
    t0 = time.perf_counter()
    float(run_steps(params, opt_state, tokens))
    dt = time.perf_counter() - t0
    ms_per_step = 1000 * dt / args.steps
    toks_per_sec = args.batch * cfg.max_seq / (dt / args.steps)
    peak = M.peak_flops() or 197e12
    mfu = toks_per_sec * M.transformer_flops_per_token(cfg) / peak
    print(f"step={ms_per_step:.1f}ms  tok/s={toks_per_sec:.0f}  "
          f"mfu={mfu:.4f}", flush=True)

    import shutil

    logdir = "/tmp/tfos_profile_transformer"
    shutil.rmtree(logdir, ignore_errors=True)
    jax.profiler.start_trace(logdir)
    float(run_steps(params, opt_state, tokens))
    jax.profiler.stop_trace()

    xspace = parse_xplane(logdir)
    from collections import defaultdict

    report = ["# Transformer step-time breakdown",
              "",
              f"dim={cfg.dim} layers={cfg.n_layers} seq={cfg.max_seq} "
              f"batch={args.batch} blocks=({args.block_q},{args.block_kv}) "
              f"remat={remat} bwd={args.bwd} ce={args.ce} "
              f"steps={args.steps}; measured {ms_per_step:.1f} ms/step "
              f"({toks_per_sec:.0f} tok/s, mfu {mfu:.4f}).",
              ""]
    for plane_name, totals, counts in summarize(xspace):
        total = sum(totals.values())
        print(f"\n== {plane_name}  total {total:.1f}ms over "
              f"{args.steps} steps ==")
        report += [f"## {plane_name} — {total:.1f} ms device time over "
                   f"{args.steps} steps", ""]
        groups = defaultdict(float)
        for name, ms in totals.items():
            key = name.split(".")[0].split("_")[0]
            groups[key] += ms
        report += ["| op group | ms | % |", "|---|---|---|"]
        for k, v in sorted(groups.items(), key=lambda kv: -kv[1])[:15]:
            print(f"  [group] {k:30s} {v:8.2f}ms {100 * v / total:5.1f}%")
            report.append(f"| {k} | {v:.2f} | {100 * v / total:.1f} |")
        print()
        report += ["", "| top op | ms | n | % |", "|---|---|---|---|"]
        for name, ms in sorted(totals.items(), key=lambda kv: -kv[1])[:40]:
            print(f"  {ms:8.2f}ms x{counts[name]:<4d} "
                  f"{100 * ms / total:5.1f}%  {name[:110]}")
            report.append(f"| `{name[:90]}` | {ms:.2f} | {counts[name]} "
                          f"| {100 * ms / total:.1f} |")
        report.append("")
    if args.out:
        with open(args.out, "w") as f:
            f.write("\n".join(report) + "\n")
        print(f"\nwrote {args.out}")


if __name__ == "__main__":
    main()
