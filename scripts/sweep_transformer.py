"""One-process transformer perf sweep under a single TPU claim:
batch size x flash block sizes at dim 1024 / 8 layers / seq 2048
(the bench.py flagship config; bf16 logits freed ~2GB HBM, so batch 16
should now fit).

Usage: python scripts/sweep_transformer.py [--steps 8]
"""

import argparse
import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

# (name, batch, block_q, block_kv, remat, bwd, ce[, seq]) — module-level
# so dry-run tests can substitute tiny shapes while driving the REAL
# promote paths.  ce: "dense" | "block" (blockwise streamed CE — no
# [B,S,V] logits tensor, buys batch headroom without full remat).
# seq defaults to 2048 (the bench flagship); long-seq configs append an
# explicit seq — rope is position-parameterized so params are shared.
CONFIGS = [
    ("b16_q512_kv512", 16, 512, 512, False, "xla", "dense"),
    ("b16_q512_kv512_pbwd", 16, 512, 512, False, "pallas", "dense"),
    ("b8_q512_kv512", 8, 512, 512, False, "xla", "dense"),
    ("b16_q1024_kv512", 16, 1024, 512, False, "xla", "dense"),
    ("b16_q512_kv1024", 16, 512, 1024, False, "xla", "dense"),
    ("b16_q1024_kv1024", 16, 1024, 1024, False, "xla", "dense"),
    ("b32_q512_kv512", 32, 512, 512, False, "xla", "dense"),
    ("b32_q512_kv512_bce", 32, 512, 512, False, "xla", "block"),
    ("b32_q512_kv512_remat", 32, 512, 512, True, "xla", "dense"),
    ("b32_q512_kv512_remat_pbwd", 32, 512, 512, True, "pallas", "dense"),
    ("b64_q512_kv512_bce", 64, 512, 512, False, "xla", "block"),
    ("b64_q512_kv512_remat", 64, 512, 512, True, "xla", "dense"),
    ("b64_q512_kv512_remat_bce", 64, 512, 512, True, "xla", "block"),
    # r4 follow-ups around the first chip session's winner
    # (b32_q512_kv512_remat_pbwd, 0.4826): pallas bwd at other
    # batch/block points, and pallas bwd + blockwise CE together
    ("b64_q512_kv512_remat_pbwd", 64, 512, 512, True, "pallas", "dense"),
    ("b32_q1024_kv1024_remat_pbwd", 32, 1024, 1024, True, "pallas", "dense"),
    ("b64_q512_kv512_remat_pbwd_bce", 64, 512, 512, True, "pallas", "block"),
    ("b32_q512_kv512_remat_pbwd_bce", 32, 512, 512, True, "pallas", "block"),
    ("b16_q512_kv512_remat_pbwd", 16, 512, 512, True, "pallas", "dense"),
    # selective remat around the r4 winner (b64_q512_kv512_remat_pbwd,
    # 0.4874): "dots" saves matmul outputs and recomputes only the
    # elementwise chain — less recompute than full remat but more HBM
    # residency.  Configs that trip the deterministic HBM-pressure
    # compile crash die in ~6s and the sweep keeps going.
    ("b64_q512_kv512_rdots_pbwd", 64, 512, 512, "dots", "pallas", "dense"),
    ("b96_q512_kv512_rdots_pbwd", 96, 512, 512, "dots", "pallas", "dense"),
    ("b96_q512_kv512_remat_pbwd", 96, 512, 512, True, "pallas", "dense"),
    # r5: seq 4096 — the regime blockwise CE exists for (VERDICT r4 #8:
    # at seq 2048 it merely loses ~3%; at 4096 the dense [B,S,V] logits
    # tensor doubles while blockwise stays O(block)).  Same token count
    # as the b32/s2048 winner; direct dense-vs-bce A/B at each batch.
    ("b16_s4096_remat_pbwd_bce", 16, 512, 512, True, "pallas", "block", 4096),
    ("b16_s4096_remat_pbwd", 16, 512, 512, True, "pallas", "dense", 4096),
    ("b32_s4096_remat_pbwd_bce", 32, 512, 512, True, "pallas", "block", 4096),
    # follow-up if the 4096 trio wins: bigger flash blocks amortize the
    # per-block epilogue over a longer diagonal
    ("b16_s4096_q1024_kv1024_remat_pbwd_bce",
     16, 1024, 1024, True, "pallas", "block", 4096),
]


def config_path():
    """bench_config.json location — resolved by bench.bench_config_path
    (the single source of truth; TFOS_BENCH_CONFIG overrides)."""
    import bench

    return bench.bench_config_path()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--promote", action="store_true",
                    help="write the winner into bench_config.json's "
                         '"transformer" section (picked up by bench.py)')
    args = ap.parse_args()

    from tensorflowonspark_tpu.utils import compile_cache

    compile_cache.export_env()  # before jax reads it at import
    import jax
    import jax.numpy as jnp
    import optax
    from jax import lax

    from tensorflowonspark_tpu import ops
    from tensorflowonspark_tpu.models import transformer
    from tensorflowonspark_tpu.utils import metrics as M

    smoke = os.environ.get("TFOS_SWEEP_SMOKE") == "1"
    # TINY shrinks shapes like smoke but leaves the promote logic live
    # (fake-TPU dry-run tests drive the real promote/merge branches)
    tiny = smoke or os.environ.get("TFOS_SWEEP_TINY") == "1"
    cfg = transformer.Config(
        vocab_size=512 if tiny else 16384,
        dim=128 if tiny else 1024,
        n_layers=2 if tiny else 8,
        n_heads=4 if tiny else 8,
        max_seq=256 if tiny else 2048,
        dtype="float32" if tiny else "bfloat16",
        attn_impl="flash",
    )
    peak = 197e12
    flops_tok = M.transformer_flops_per_token(cfg)
    opt = optax.adam(1e-3)

    @jax.jit
    def init_all(key):
        params = transformer.init(key, cfg)
        return params, opt.init(params)

    print("init...", flush=True)
    params, opt_state = init_all(jax.random.PRNGKey(0))
    jax.block_until_ready(params)
    print("init done", flush=True)

    # pre-flight: the compiled (Mosaic-lowered) pallas forward has never
    # run before the first chip session — if it miscompiles, every config
    # here uses it and the sweep would produce NOTHING.  Probe once; on
    # failure sweep with the XLA reference attention instead (slower but
    # a number, recorded as attn="reference" for the bench to honor).
    attn_base, attn_name = ops.flash_attention, "flash"
    # probe at the BLOCK SIZES the real configs use, on random input,
    # and check numerics against the XLA reference — a kernel that
    # miscompiles only at production shapes, or compiles but returns
    # garbage, must also trip the fallback.  Inputs and the reference
    # output are computed OUTSIDE the guarded region: if plain XLA fails
    # here the backend is broken and the sweep should fail loudly, not
    # quietly demote to the slow path.
    pseq = min(1024, cfg.max_seq)
    pkeys = jax.random.split(jax.random.PRNGKey(7), 3)
    pq, pk_, pv = (jax.random.normal(
        kk, (1, pseq, cfg.n_heads, cfg.head_dim), cfg.compute_dtype)
        for kk in pkeys)
    ref_out = ops.mha_reference(pq, pk_, pv, causal=True)
    flash_probe = jax.jit(functools.partial(
        ops.flash_attention, causal=True, block_q=512, block_kv=512))
    for attempt in (1, 2):
        try:
            got = flash_probe(pq, pk_, pv)
            err = float(jnp.max(jnp.abs(
                got.astype(jnp.float32) - ref_out.astype(jnp.float32))))
            if not err < 5e-2:  # bf16-scale tolerance; also catches NaN
                raise RuntimeError(f"probe numerics off: max err {err}")
            break
        except Exception as e:  # noqa: BLE001 - first-run kernel failure
            # retry ONLY transient pool errors, after letting them clear
            # (observed to clear in minutes; mirrors bench's init retry).
            # Deterministic failures — Mosaic miscompiles, bad numerics —
            # go straight to the fallback: a doomed re-compile would burn
            # many minutes of the one serialized TPU claim.
            if attempt == 1 and "UNAVAILABLE" in str(e):
                print(f"pallas probe hit a transient pool error "
                      f"({str(e)[:120]}); retrying in 60s", flush=True)
                time.sleep(60)
                continue
            print(f"pallas flash forward FAILED on this backend: "
                  f"{str(e)[:200]}\nsweeping with the XLA reference "
                  f"attention instead", flush=True)
            attn_base, attn_name = ops.mha_reference, "reference"
            break

    # normalize to 8-tuples (seq defaults to the flagship 2048)
    configs = [(*c, cfg.max_seq) if len(c) == 7 else tuple(c)
               for c in CONFIGS]
    subset = os.environ.get("TFOS_SWEEP")
    if subset:
        want = set(subset.split(","))
        configs = [c for c in configs if c[0] in want]
    if tiny:  # plumbing check (CPU): tiny batch, blocks fitting
        # max_seq, always including one remat, one pallas-bwd, one
        # blockwise-CE, and one long-seq config
        picked = (configs[:2] + [c for c in configs[2:] if c[4]][:1]
                  + [c for c in configs[2:] if c[5] == "pallas"][:1]
                  + [c for c in configs[2:] if c[6] == "block"][:1]
                  + [c for c in configs[2:] if c[7] != cfg.max_seq][:1])
        configs = [(n, 1, min(bq, 128), min(bkv, 128), r, bw, ce,
                    cfg.max_seq * (2 if s != cfg.max_seq else 1))
                   for n, _, bq, bkv, r, bw, ce, s in picked]

    import dataclasses

    rng = np.random.default_rng(0)
    results = []
    by_name = {}
    seen_ref = set()  # reference attn ignores blocks: dedupe configs
    for name, batch, bq, bkv, remat, bwd, ce, seq in configs:
        ccfg = (cfg if seq == cfg.max_seq
                else dataclasses.replace(cfg, max_seq=seq))
        cflops_tok = (flops_tok if seq == cfg.max_seq
                      else M.transformer_flops_per_token(ccfg))
        if attn_name == "reference":
            if bwd == "pallas":
                print(f"{name:18s} SKIPPED (pallas unavailable)",
                      flush=True)
                continue
            key = (batch, remat, ce, seq)
            if key in seen_ref:  # blocks don't matter without pallas —
                # don't spend multi-minute compiles on duplicates
                print(f"{name:18s} SKIPPED (duplicate under reference "
                      f"attn)", flush=True)
                continue
            seen_ref.add(key)
        try:
            tokens = jnp.asarray(
                rng.integers(0, ccfg.vocab_size, (batch, ccfg.max_seq)),
                jnp.int32)
            if attn_name == "flash":
                attn = functools.partial(
                    attn_base, causal=True, block_q=bq,
                    block_kv=bkv, bwd_impl=bwd)
            else:
                attn = functools.partial(attn_base, causal=True)

            @jax.jit
            def run(params, opt_state, tokens):
                def body(carry, _):
                    p, o = carry
                    loss, grads = jax.value_and_grad(transformer.loss_fn)(
                        p, tokens, ccfg, attn_fn=attn, remat=remat,
                        ce_impl=("blockwise" if ce == "block" else "dense"),
                        ce_block=min(2048, ccfg.vocab_size))
                    updates, o = opt.update(grads, o)
                    return (optax.apply_updates(p, updates), o), loss
                (_, _), losses = lax.scan(
                    body, (params, opt_state), None, length=args.steps)
                return losses[-1]

            t0 = time.perf_counter()
            float(run(params, opt_state, tokens))
            compile_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            float(run(params, opt_state, tokens))
            dt = time.perf_counter() - t0
            tps = batch * ccfg.max_seq * args.steps / dt
            mfu = tps * cflops_tok / peak
            print(f"{name:22s} tok/s={tps:9.0f}  mfu={mfu:.4f}  "
                  f"(compile {compile_s:.0f}s)", flush=True)
            results.append((mfu, name))
            by_name[name] = {"batch": batch, "block_q": bq,
                             "block_kv": bkv, "remat": remat, "bwd": bwd,
                             "ce": ce, "attn": attn_name, "seq": seq}
        except Exception as e:  # noqa: BLE001 - keep sweeping
            print(f"{name:18s} FAILED: {str(e)[:160]}", flush=True)
    for mfu, name in sorted(results, reverse=True):
        print(f"  {mfu:.4f}  {name}")
    if args.promote and results:
        import json

        tiny_guard = tiny and \
            os.environ.get("TFOS_SWEEP_TINY_PROMOTE_OK") != "1"
        if smoke or tiny_guard or jax.devices()[0].platform == "cpu":
            # TINY shrinks shapes too (see sweep_resnet.py): only the
            # dry-run tests may promote tiny results, via the explicit
            # TFOS_SWEEP_TINY_PROMOTE_OK acknowledgement
            print("promote skipped: smoke/CPU/tiny runs must not pin the "
                  "TPU bench to toy shapes", flush=True)
            return
        best_mfu, best = max(results)
        path = config_path()
        cfg_all = {}
        if os.path.exists(path):  # keep the resnet section
            try:
                with open(path) as f:
                    cfg_all = json.load(f)
            except (OSError, ValueError):
                cfg_all = {}
        prior = cfg_all.get("transformer", {})
        if isinstance(prior, dict) and prior.get("mfu", 0) > best_mfu:
            # a subset re-sweep must not demote a better earlier winner
            print(f"promote kept prior {prior.get('winner')} "
                  f"(mfu {prior['mfu']:.4f} > {best_mfu:.4f})", flush=True)
            return
        cfg_all["transformer"] = dict(
            by_name[best], winner=best, mfu=round(best_mfu, 4))
        with open(path, "w") as f:
            json.dump(cfg_all, f, indent=1)
        print(f"promoted {best} (mfu {best_mfu:.4f}) -> {path}", flush=True)


if __name__ == "__main__":
    main()
