"""Put on the PYTHONPATH of the serving replica by the ``serve_decode``
runner, because only the process that owns the chip can trace it or read
its memory, that process is the program's own, and nothing in
``serving/replicas.py`` answers a profile directive (PERF.md, Open
questions: the ``tracing`` issue should add one and retire this file).

Inert unless ``BENCH_HOOK_DIR`` is set.  Then ONE daemon thread polls that
directory twice a second and acts only in a process that has a jax
backend up (the replica; never the driver or a bare executor):

    mem.go    -> writes mem.json: ``memory_stats()`` of the first device
    trace.go  -> ``{"seconds", "dir"}``: a profiler capture of that many
                 seconds into ``dir``; then trace.done
                 (only the ``--trace 1`` run writes this file)
"""

import os


def _start(ctl):
    import json
    import sys
    import threading
    import time

    def owns_chip():
        # never IMPORT anything of jax from this thread: the main thread
        # may be in the middle of importing it
        bridge = sys.modules.get("jax._src.xla_bridge")
        up = getattr(bridge, "backends_are_initialized", None)
        return bool(up and up())

    def write(name, obj):
        tmp = os.path.join(ctl, name + ".tmp")
        with open(tmp, "w") as f:
            json.dump(obj, f)
        os.replace(tmp, os.path.join(ctl, name))

    def loop():
        while True:
            time.sleep(0.5)
            try:
                if not os.path.isdir(ctl) or not os.listdir(ctl) \
                        or not owns_chip():
                    continue
                jax = sys.modules["jax"]

                go = os.path.join(ctl, "mem.go")
                if os.path.exists(go):
                    os.remove(go)
                    dev = jax.local_devices()[0]
                    write("mem.json", {"pid": os.getpid(),
                                       "stats": dev.memory_stats() or {}})
                go = os.path.join(ctl, "trace.go")
                if os.path.exists(go):
                    with open(go) as f:
                        req = json.load(f)
                    os.remove(go)
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0  # it slows every thread
                    opts.host_tracer_level = 2
                    jax.profiler.start_trace(req["dir"],
                                             profiler_options=opts)
                    time.sleep(float(req["seconds"]))
                    jax.profiler.stop_trace()
                    write("trace.done", {"pid": os.getpid()})
            except Exception as e:  # noqa: BLE001 - never hurt the replica
                try:
                    write("hook.error", {"error": repr(e)})
                except OSError:
                    pass

    threading.Thread(target=loop, name="bench-hook", daemon=True).start()


if os.environ.get("BENCH_HOOK_DIR"):
    _start(os.environ["BENCH_HOOK_DIR"])
