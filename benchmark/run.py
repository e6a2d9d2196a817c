"""One run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This parent never imports jax.  It reads the manifest, finds the cell's
configuration, traffic mix and runner BY NAME (benchmark/lib/manifest.py),
runs the runner in a child process group of its own, kills whatever that
group leaves behind, applies the cell's metric readers to the facts the
runner wrote, and prints one JSON object as the last line of stdout.
Every other number is printed on earlier lines.

Without an accelerator, or with fewer chips than the cell asks for, the
run fails: non-zero exit, no result line.  ``--rehearse`` (tiny shapes
from the configuration's ``rehearse`` block, CPU, interpreted kernels)
exists for the sandbox and the tests and can never print
``"platform": "tpu"``.
"""

import time

T_START = time.time()  # process start, as near as Python lets us see it

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import lastline, manifest as M  # noqa: E402

# a first run in a checkout compiles and may take 1200 s; later ones 360 s.
# The child gets a little less than the larger, so that a hang ends here,
# with its process group killed, and not in the driver's own limit.
CHILD_TIMEOUT_S = 1150
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def say(msg):
    print(f"[bench] {msg}", flush=True)


def child_env(rehearse, chips_per_process, chips):
    env = dict(os.environ)
    env.pop("BENCH_RUN", None)  # the driver's own; nothing here reads it
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    # one fixed directory inside the checkout, unless the machine names one
    env.setdefault(CACHE_ENV, os.path.join(ROOT, ".jax_cache"))
    # cache every program, the small ones too: a run after the first
    # then compiles nothing, and set-up is the same work every time
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    env.setdefault("TPU_LOG_DIR", "disabled")
    if rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                            f"{chips_per_process}")
        # lets the rehearsal claim chips the way the chip run does
        env["TFOS_TPU_CHIPS_PER_HOST"] = str(chips)
        env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    return env


def run_child(ctx_path, env):
    """The runner in its own session; the whole group dies with it."""
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--child", ctx_path],
        env=env, cwd=ROOT, start_new_session=True)
    try:
        rc = proc.wait(timeout=CHILD_TIMEOUT_S)
    except subprocess.TimeoutExpired:
        rc = f"timeout after {CHILD_TIMEOUT_S}s"
    finally:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()
    return rc


def child_main(ctx_path):
    with open(ctx_path) as f:
        ctx = json.load(f)
    runner = M.load_module(os.path.join(
        HERE, "runners", ctx["mix"]["runner"] + ".py"))
    facts = runner.run(ctx)
    tmp = ctx["facts_path"] + ".tmp"
    with open(tmp, "w") as f:
        json.dump(facts, f)
    os.replace(tmp, ctx["facts_path"])


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--keep-work", action="store_true",
                    help="leave .bench_work/<cell>/ (facts, node files, the "
                         "raw trace) in place after a run that succeeded")
    ap.add_argument("--child", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.child:
        return child_main(args.child)
    if not args.workload:
        ap.error("--workload is required")

    man = M.load(ROOT)
    bad = M.problems(man, ROOT)
    if bad:
        raise SystemExit("BENCHMARK.json breaks the contract:\n  "
                         + "\n  ".join(bad))
    cell = M.cell(man, args.workload, ROOT)
    seconds = float(args.seconds if args.seconds is not None
                    else man["run_seconds"])
    work = os.path.join(ROOT, ".bench_work", args.workload)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    ctx = dict(cell, seed=int(args.seed), seconds=seconds,
               trace=bool(args.trace), rehearse=bool(args.rehearse),
               t_start=T_START, work=work, root=ROOT,
               facts_path=os.path.join(work, "facts.json"))
    ctx_path = os.path.join(work, "ctx.json")
    with open(ctx_path, "w") as f:
        json.dump(ctx, f)
    env = child_env(args.rehearse,
                    int(cell["mix"].get("chips_per_process", 1)),
                    cell["workload"]["chips"])
    say(f"cell {args.workload}: config {cell['workload']['config']}, "
        f"traffic {cell['workload']['traffic']}, runner "
        f"{cell['mix']['runner']}, {cell['workload']['chips']} chip(s), "
        f"seed {args.seed}, window {seconds}s, trace {args.trace}, "
        f"compile cache {env[CACHE_ENV]}"
        + (" [REHEARSAL on the CPU: no number below is a device number]"
           if args.rehearse else ""))
    rc = run_child(ctx_path, env)
    if "jax" in sys.modules:
        raise SystemExit("the parent imported jax")
    if rc != 0 or not os.path.exists(ctx["facts_path"]):
        say(f"runner failed (exit {rc}); no result")
        return 1
    with open(ctx["facts_path"]) as f:
        facts = json.load(f)
    dev = facts["device"]
    if args.rehearse:
        if dev["platform"] == "tpu":
            raise SystemExit("a rehearsal reported platform tpu")
    elif dev["platform"] == "cpu" or dev["count"] < cell["workload"]["chips"]:
        say(f"no accelerator, or too few chips for this cell: {dev}")
        return 1
    group = "per_layer" if args.trace else "end_to_end"
    other = "end_to_end" if args.trace else "per_layer"
    metrics = M.read_metrics(man, args.workload, group, facts, ROOT)
    # the other group's readers too, on an earlier line: everything a run
    # can say, whichever group the last line carries
    say(f"{other} readers (not on the last line of this run): " + json.dumps(
        {k: v["value"] for k, v in M.read_metrics(
            man, args.workload, other, facts, ROOT).items()}))
    line = lastline.compose(facts, metrics, args.trace)
    # a CPU rehearsal has no device plane to be busy on
    bad = lastline.problems(line, trace=bool(args.trace)
                            and not args.rehearse)
    if bad:
        say("the result breaks the contract: " + "; ".join(bad))
        return 1
    if args.rehearse:
        line["rehearsal"] = True
    if not args.keep_work:
        shutil.rmtree(work, ignore_errors=True)
    print(lastline.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
