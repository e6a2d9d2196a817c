"""One-process ResNet-50 perf sweep: measures several configurations under
one process: a chip belongs to one process at a time, and every new
process pays the runtime's start-up and a cold compile of its own.

Sweeps: stem (s2d vs 7x7), batch size, remat, and the BatchNorm backward
(custom-VJP fused vs plain autodiff); prints one line per config and a
final ranking.  Use TFOS_SWEEP=b256_s2d_bnf,b512_s2d_bnf,... to subset
by the names in CONFIGS below.

Usage: python scripts/sweep_resnet.py [--steps 10]
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

# (name, batch, stem_s2d, remat, bn_fused) — most promising first, so a
# run cut short still yields the configs that matter.  Module-level
# so dry-run tests can substitute tiny shapes while driving the REAL
# sweep/promote/refusal paths.  bn_fused: custom-VJP BatchNorm backward
# (two fused HBM passes; see models/layers._bn_train_fused) vs plain
# autodiff — the round-4 profile showed ~38% of the step in unfused BN
# backward multiplies.
CONFIGS = [
    ("b256_s2d_bnf", 256, True, False, True),
    ("b512_s2d_bnf", 512, True, False, True),
    ("b384_s2d_bnf", 384, True, False, True),
    ("b256_s2d", 256, True, False, False),
    ("b512_s2d_remat_bnf", 512, True, True, True),
    ("b256_7x7_bnf", 256, False, False, True),
    # r5 structural probes: r4 measured per-image throughput FALLING
    # with batch (256: 2581, 384: 2494, 512: 2444 img/s) on an
    # HBM-bound step — if capacity pressure (spills/copies) is the
    # cause, SMALLER batches should run faster per image; and remat,
    # which lost 25% with autodiff BN, re-enters with the fused-BN
    # backward's cheaper recompute.
    ("b128_s2d_bnf", 128, True, False, True),
    ("b192_s2d_bnf", 192, True, False, True),
    ("b256_s2d_remat_bnf", 256, True, True, True),
]


def config_path():
    """bench_config.json location — resolved by bench.bench_config_path
    (the single source of truth; TFOS_BENCH_CONFIG overrides)."""
    import bench

    return bench.bench_config_path()


def measure(step_fn, params, state, opt_state, images, labels, steps):
    import jax
    from jax import lax

    @jax.jit
    def run(params, state, opt_state, images, labels):
        def body(carry, _):
            p, s, o = carry
            p, s, o, loss, _ = step_fn(p, s, o, images, labels)
            return (p, s, o), loss
        (_, _, _), losses = lax.scan(
            body, (params, state, opt_state), None, length=steps)
        return losses[-1]
    return _timed(run, params, state, opt_state, images, labels, steps)


def measure_decomposed(mode, opt, cfg_kwargs, params, state, opt_state,
                       images, labels, steps):
    """TFOS_SWEEP_MODE=fwd|grad step-time decomposition (no promote):
    'fwd' scans the forward loss only; 'grad' scans value_and_grad but
    skips the optimizer update.  train - grad = optimizer cost;
    grad - fwd = backward cost.  One chip claim, no profiler."""
    import jax
    from jax import lax

    from tensorflowonspark_tpu.models import resnet

    def loss_fn(p, s, x, y):
        logits, new_s = resnet.apply(
            p, s, x, depth=50, train=True,
            compute_dtype=jax.numpy.bfloat16,
            stem_s2d=cfg_kwargs["stem_s2d"], bn_fused=cfg_kwargs["bn_fused"])
        from tensorflowonspark_tpu.models import layers as L
        return L.softmax_cross_entropy(logits, y), new_s

    if mode == "fwd":
        @jax.jit
        def run(params, state, opt_state, images, labels):
            # the loss must depend on the carry or XLA's while-loop
            # invariant code motion hoists the whole forward out of the
            # scan (train-mode BN reads only params/images).  eps is a
            # zero-valued scalar chained through the previous loss —
            # value-neutral, but it serializes the iterations.
            def body(carry, _):
                s, eps = carry
                loss, new_s = loss_fn(params, s, images + eps, labels)
                return (new_s, (0.0 * loss).astype(images.dtype)), loss
            zero = jax.numpy.zeros((), images.dtype)
            _, losses = lax.scan(body, (state, zero), None, length=steps)
            return losses[-1]
    else:  # grad
        @jax.jit
        def run(params, state, opt_state, images, labels):
            def body(carry, _):
                p, s = carry
                (loss, new_s), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(p, s, images, labels)
                # consume grads without an optimizer: fold a zero-scaled
                # update into the carry so XLA cannot DCE the backward
                p = jax.tree.map(lambda a, g: a - 0.0 * g, p, grads)
                return (p, new_s), loss
            _, losses = lax.scan(body, (params, state), None, length=steps)
            return losses[-1]
    return _timed(run, params, state, opt_state, images, labels, steps)


def _timed(run, params, state, opt_state, images, labels, steps):
    import time

    t0 = time.perf_counter()
    float(run(params, state, opt_state, images, labels))  # compile+warmup
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    float(run(params, state, opt_state, images, labels))
    dt = time.perf_counter() - t0
    return dt / steps, compile_s


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--image", type=int, default=224)
    ap.add_argument("--promote", action="store_true",
                    help="write the winning config to bench_config.json "
                         "(picked up by bench.py on TPU)")
    args = ap.parse_args()

    from tensorflowonspark_tpu.utils import compile_cache

    compile_cache.export_env()  # before jax reads it at import
    import jax
    import optax

    from tensorflowonspark_tpu.models import resnet

    dev = jax.devices()[0]
    peak = 197e12  # v5e bf16
    flops_img = 3.0 * resnet.flops_per_image(50, args.image)
    print(f"device: {dev} ({getattr(dev, 'device_kind', '?')})", flush=True)

    opt = optax.sgd(0.1, momentum=0.9)

    @jax.jit
    def init_all(key):
        params, state = resnet.init(jax.random.PRNGKey(0), depth=50,
                                    num_classes=1000)
        return params, state, opt.init(params)

    print("init...", flush=True)
    params, state, opt_state = init_all(jax.random.PRNGKey(0))
    jax.block_until_ready(params)
    print("init done", flush=True)

    configs = list(CONFIGS)
    subset = os.environ.get("TFOS_SWEEP")
    if subset:
        want = set(subset.split(","))
        configs = [c for c in configs if c[0] in want]
    # SMOKE: plumbing check (CPU) — tiny shapes AND promote refused.
    # TINY: tiny shapes only — promote logic still runs, so fake-TPU
    # dry-run tests can drive the real promote/merge/refusal branches.
    if os.environ.get("TFOS_SWEEP_SMOKE") == "1" \
            or os.environ.get("TFOS_SWEEP_TINY") == "1":
        configs = [(n, 4, s, r, bf) for n, _, s, r, bf in configs[:2]]

    # TFOS_SWEEP_MODE=fwd|grad decomposes the step (no remat support,
    # no promote: fwd/grad "mfu" is not comparable to the train metric)
    mode = os.environ.get("TFOS_SWEEP_MODE", "train")
    if mode not in ("train", "fwd", "grad"):
        raise SystemExit(f"bad TFOS_SWEEP_MODE {mode!r}")

    rng = np.random.default_rng(0)
    results = []
    by_name = {}
    for name, batch, s2d, remat, bnf in configs:
        try:
            import jax.numpy as jnp

            images = jnp.asarray(
                rng.random((batch, args.image, args.image, 3),
                           dtype=np.float32), jnp.bfloat16)
            labels = jnp.asarray(rng.integers(0, 1000, batch), jnp.int32)
            if mode == "train":
                step_fn = resnet.make_train_step(
                    opt, depth=50, stem_s2d=s2d, remat=remat, bn_fused=bnf)
                sec, compile_s = measure(
                    step_fn, params, state, opt_state, images, labels,
                    args.steps)
            elif remat:
                # decomposed builds ignore remat - timing a non-remat
                # program under a *_remat name would mislabel it (and
                # risk the HBM-pressure compile crash remat avoids)
                print(f"{name:18s} SKIPPED ({mode} mode has no remat)",
                      flush=True)
                continue
            else:
                sec, compile_s = measure_decomposed(
                    mode, opt, {"stem_s2d": s2d, "bn_fused": bnf},
                    params, state, opt_state, images, labels, args.steps)
            ips = batch / sec
            mfu = ips * flops_img / peak
            print(f"{name:18s} {mode}={sec*1e3:7.1f}ms  img/s={ips:7.0f}  "
                  f"mfu={mfu:.4f}  (compile {compile_s:.0f}s)", flush=True)
            results.append((mfu, name))
            by_name[name] = {"batch": batch, "stem_s2d": s2d, "remat": remat,
                             "bn_fused": bnf}
        except Exception as e:  # noqa: BLE001 - keep sweeping
            print(f"{name:18s} FAILED: {str(e)[:160]}", flush=True)
    for mfu, name in sorted(results, reverse=True):
        print(f"  {mfu:.4f}  {name}")
    if args.promote and results:
        import json

        if mode != "train":
            print(f"promote skipped: TFOS_SWEEP_MODE={mode} times a "
                  f"partial step - not the bench metric", flush=True)
            return
        tiny = os.environ.get("TFOS_SWEEP_TINY") == "1" and \
            os.environ.get("TFOS_SWEEP_TINY_PROMOTE_OK") != "1"
        if os.environ.get("TFOS_SWEEP_SMOKE") == "1" or tiny or \
                dev.platform == "cpu":
            # TINY shrinks configs to toy shapes too: a leftover env var
            # during a live claim must not pin the bench to batch 4
            # (dry-run tests set TFOS_SWEEP_TINY_PROMOTE_OK explicitly)
            print("promote skipped: smoke/CPU/tiny runs must not pin the "
                  "TPU bench to toy shapes", flush=True)
            return
        best_mfu, best = max(results)
        path = config_path()
        cfg_all = {}
        prior_mfu, prior_winner = 0, None
        if os.path.exists(path):  # keep other sections (e.g. transformer)
            try:
                with open(path) as f:
                    prior = json.load(f)
                prior_mfu = prior.get("mfu", 0) or 0
                prior_winner = prior.get("winner")
                cfg_all = {k: v for k, v in prior.items()
                           if isinstance(v, dict)}  # nested sections only
            except (OSError, ValueError):
                cfg_all = {}
        if prior_mfu > best_mfu:
            # a subset re-sweep must not demote a better earlier winner
            print(f"promote kept prior {prior_winner} "
                  f"(mfu {prior_mfu:.4f} > {best_mfu:.4f})", flush=True)
            return
        cfg_all.update(by_name[best], image=args.image, winner=best,
                       mfu=round(best_mfu, 4), device=str(dev))
        with open(path, "w") as f:
            json.dump(cfg_all, f, indent=1)
        print(f"promoted {best} (mfu {best_mfu:.4f}) -> {path}", flush=True)


if __name__ == "__main__":
    main()
