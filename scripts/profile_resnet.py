"""Profile the ResNet-50 train step on the attached TPU and print the
top ops by self-time, grouped by fusion kind.

Usage: python scripts/profile_resnet.py [--steps N] [--batch N]
Writes the xplane trace under /tmp/tfos_profile and parses it with the
tensorflow xplane protobuf (no TensorBoard needed).
"""

import argparse
import glob
import os
import sys
import time
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def parse_xplane(logdir):
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise SystemExit(f"no xplane.pb under {logdir}")
    xspace = xplane_pb2.XSpace()
    with open(sorted(paths)[-1], "rb") as f:
        xspace.ParseFromString(f.read())
    return xspace


def summarize(xspace, top=40):
    # find the TPU device plane (op-level events live there)
    for plane in xspace.planes:
        if "TPU" in plane.name or "/device:" in plane.name:
            ev_names = plane.event_metadata
            totals = defaultdict(float)
            counts = defaultdict(int)
            for line in plane.lines:
                if "XLA Ops" not in line.name and "Ops" != line.name.strip():
                    continue
                for ev in line.events:
                    name = ev_names[ev.metadata_id].name
                    totals[name] += ev.duration_ps / 1e9  # ms
                    counts[name] += 1
            if totals:
                yield plane.name, totals, counts


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--image", type=int, default=224)
    ap.add_argument("--stem", choices=("s2d", "7x7"), default="s2d")
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--bn", choices=("fused", "plain"), default="fused",
                    help="BatchNorm backward: custom-VJP fused vs autodiff")
    ap.add_argument("--out", default=None,
                    help="also write the breakdown as markdown (e.g. PERF.md)")
    args = ap.parse_args()

    from tensorflowonspark_tpu.utils import compile_cache

    compile_cache.export_env()  # before jax reads it at import
    import jax
    import jax.numpy as jnp
    import optax
    from jax import lax

    from tensorflowonspark_tpu.models import resnet

    # one jitted init program: eager init is hundreds of tiny dispatches,
    # each compiled on its own
    print("init...", flush=True)
    opt = optax.sgd(0.1, momentum=0.9)

    @jax.jit
    def init_all(key):
        params, state = resnet.init(key, depth=50, num_classes=1000)
        return params, state, opt.init(params)

    params, state, opt_state = init_all(jax.random.PRNGKey(0))
    # apply() silently falls back to 7x7 on odd image sizes — report the
    # stem that actually runs, not the one requested
    effective_stem = ("s2d" if args.stem == "s2d" and args.image % 2 == 0
                      else "7x7")
    step_fn = resnet.make_train_step(opt, depth=50,
                                     stem_s2d=(args.stem == "s2d"),
                                     remat=args.remat,
                                     bn_fused=(args.bn == "fused"))

    rng = np.random.default_rng(0)
    images = jnp.asarray(rng.random((args.batch, args.image, args.image, 3),
                                    dtype=np.float32), dtype=jnp.bfloat16)
    labels = jnp.asarray(rng.integers(0, 1000, args.batch), dtype=jnp.int32)

    @jax.jit
    def run_steps(params, state, opt_state, images, labels):
        def body(carry, _):
            p, s, o = carry
            p, s, o, loss, _ = step_fn(p, s, o, images, labels)
            return (p, s, o), loss
        (_, _, _), losses = lax.scan(body, (params, state, opt_state),
                                     None, length=args.steps)
        return losses[-1]

    print("compiling...", flush=True)
    float(run_steps(params, state, opt_state, images, labels))
    t0 = time.perf_counter()
    float(run_steps(params, state, opt_state, images, labels))
    dt = time.perf_counter() - t0
    ms_per_step = 1000 * dt / args.steps
    print(f"step={ms_per_step:.1f}ms  img/s={args.batch / (dt / args.steps):.0f}",
          flush=True)

    import shutil

    logdir = "/tmp/tfos_profile"
    shutil.rmtree(logdir, ignore_errors=True)
    jax.profiler.start_trace(logdir)
    float(run_steps(params, state, opt_state, images, labels))
    jax.profiler.stop_trace()

    xspace = parse_xplane(logdir)
    report = [f"# ResNet-50 step-time breakdown",
              f"",
              f"batch={args.batch} image={args.image} stem={effective_stem} "
              f"remat={args.remat} bn={args.bn} steps={args.steps}; "
              f"measured {ms_per_step:.1f} ms/step "
              f"({args.batch / (ms_per_step / 1000):.0f} img/s).",
              ""]
    for plane_name, totals, counts in summarize(xspace):
        total = sum(totals.values())
        print(f"\n== {plane_name}  total {total:.1f}ms over {args.steps} steps ==")
        report += [f"## {plane_name} — {total:.1f} ms device time "
                   f"over {args.steps} steps", ""]
        # group by fusion-kind prefix
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
            print(f"  {ms:8.2f}ms x{counts[name]:<4d} {100 * ms / total:5.1f}%  {name[:110]}")
            report.append(f"| `{name[:90]}` | {ms:.2f} | {counts[name]} "
                          f"| {100 * ms / total:.1f} |")
        report.append("")
    if args.out:
        from tensorflowonspark_tpu.recordio import fs as _fs

        with _fs.open_file(args.out, "w") as f:
            f.write("\n".join(report) + "\n")
        print(f"\nwrote {args.out}")


if __name__ == "__main__":
    main()
