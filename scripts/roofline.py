"""Measure this chip's achievable roofline: big-matmul TFLOP/s (MXU
ceiling) and big-elementwise GB/s (HBM ceiling).

Grounds MFU analysis in measured hardware numbers instead of datasheet
peaks: ResNet-50's step is HBM-bound (PERF.md round 4), so its MFU
ceiling is set by measured bandwidth, not the 197 TFLOP/s MXU figure.
Pairs with scripts/resnet_traffic.py (analytic model traffic floor).

Timing discipline: every probe CHAINS — each call's output is the next
call's input — and ends in a 1-element value fetch, so the clock cannot
stop before every call has executed, whatever the runtime does with
repeated identical dispatches.

Usage: python scripts/roofline.py [--out ROOFLINE.json]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


# per-generation sanity ceilings, ~2x datasheet (HBM GB/s, bf16 TFLOP/s):
# a legitimate measurement can beat datasheet a little (clocks, cache
# effects), a dispatch artifact beats it by orders of magnitude.  The
# matched limits are stamped into the report so consumers
# (scripts/resnet_traffic.py) share them instead of duplicating.
_PHYSICS = [
    ("v5 lite", 1600, 400),   # v5e: 819 GB/s, 197 TFLOP/s
    ("v5p", 5500, 950),       # v5p: 2765 GB/s, 459 TFLOP/s
    ("v4", 2400, 550),        # v4: 1228 GB/s, 275 TFLOP/s
    ("v6", 3300, 1900),       # v6e: 1640 GB/s, 918 TFLOP/s
]
_DEFAULT_PHYSICS = (1600, 400)  # unknown TPU: assume v5e-class


def physics_limits(device_kind):
    kind = (device_kind or "").lower()
    for sub, gbs, tflops in _PHYSICS:
        if sub in kind:
            return gbs, tflops
    return _DEFAULT_PHYSICS


def _fetch(x):
    """True completion barrier: a 1-element read that depends on x."""
    import jax.numpy as jnp

    return float(np.asarray(jnp.ravel(x)[0]))


def _timed_chain(fn, x, *rest, iters=8):
    """Time ``iters`` chained calls x = fn(x, *rest); returns s/call."""
    x = fn(x, *rest)
    _fetch(x)  # compile + warmup + verified completion
    t0 = time.perf_counter()
    for _ in range(iters):
        x = fn(x, *rest)
    _fetch(x)
    return (time.perf_counter() - t0) / iters


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None, help="also write JSON here")
    ap.add_argument("--iters", type=int, default=8)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax import lax

    dev = jax.devices()[0]
    print(f"device: {dev} ({getattr(dev, 'device_kind', '?')})", flush=True)
    small = dev.platform == "cpu"
    max_gbs, max_tflops = physics_limits(getattr(dev, "device_kind", ""))
    report = {"device": str(dev), "platform": dev.platform,
              "sanity_max_gbs": max_gbs, "sanity_max_tflops": max_tflops}
    suspect = []

    # -- MXU ceiling: bf16 matmul chain, K large enough to amortize -----
    m = 2048 if small else 8192
    k = n = m
    steps = 4
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (m, k), jnp.bfloat16)
    b = jax.random.normal(key, (k, n), jnp.bfloat16)

    @jax.jit
    def mm(x, b):
        # 1/128 epilogue scale keeps the chained values bounded (fuses
        # into the matmul, no extra HBM traffic)
        def body(x, _):
            y = jnp.dot(x, b, preferred_element_type=jnp.bfloat16)
            return y * jnp.bfloat16(1.0 / 128.0), None

        y, _ = lax.scan(body, x, None, length=steps)
        return y

    dt = _timed_chain(mm, a, b, iters=args.iters)
    tflops = 2.0 * m * k * n * steps / dt / 1e12
    report["matmul_bf16_tflops"] = round(tflops, 1)
    print(f"bf16 matmul ({m}x{k}x{n} x{steps}): {tflops:.1f} TFLOP/s",
          flush=True)
    if not small and tflops > max_tflops:
        suspect.append("matmul_bf16_tflops")

    # -- HBM ceiling: elementwise scale-add (read + write) --------------
    nelem = (1 << 24) if small else (1 << 29)  # 1 GiB bf16 on TPU
    x = jax.random.normal(key, (nelem,), jnp.bfloat16)

    @jax.jit
    def ew(x):
        def body(y, _):
            return y * jnp.bfloat16(1.0001) + jnp.bfloat16(1e-6), None

        y, _ = lax.scan(body, x, None, length=steps)
        return y

    dt = _timed_chain(ew, x, iters=args.iters)
    gbs_ew = 2 * 2 * nelem * steps / dt / 1e9  # read + write, 2B/elem
    report["elementwise_gbs"] = round(gbs_ew, 1)
    print(f"elementwise r+w: {gbs_ew:.1f} GB/s", flush=True)
    if not small and gbs_ew > max_gbs:
        suspect.append("elementwise_gbs")

    # -- BN-shaped op: the ResNet hot pattern at its real shape ---------
    # (covers the reduction ceiling too: stats are a 2-sum reduce pass)
    bshape = (64, 56, 56, 256) if not small else (8, 16, 16, 32)
    xb = jax.random.normal(key, bshape, jnp.bfloat16)

    @jax.jit
    def bnlike(x):
        xf = x.astype(jnp.float32)
        ax = (0, 1, 2)
        nred = x.size // x.shape[-1]
        mean = jnp.sum(xf, axis=ax) / nred
        var = jnp.maximum(jnp.sum(xf * xf, axis=ax) / nred - mean * mean, 0)
        mul = lax.rsqrt(var + 1e-5).astype(x.dtype)
        add = (-mean * lax.rsqrt(var + 1e-5)).astype(x.dtype)
        return x * mul + add

    dt = _timed_chain(bnlike, xb, iters=args.iters)
    nb = int(np.prod(bshape))
    gbs_bn = 2 * (2 * nb + nb) / dt / 1e9  # stats read + norm read + write
    report["bn_fwd_gbs"] = round(gbs_bn, 1)
    print(f"bn-shaped fwd (stats+normalize, {bshape}): {gbs_bn:.1f} GB/s "
          f"effective", flush=True)
    if not small and gbs_bn > max_gbs:
        suspect.append("bn_fwd_gbs")

    if suspect:
        report["suspect"] = suspect
        print(f"WARNING: {suspect} exceed datasheet physics - timing "
              f"path compromised, numbers unusable", flush=True)

    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
        print(f"wrote {args.out}", flush=True)


if __name__ == "__main__":
    main()
