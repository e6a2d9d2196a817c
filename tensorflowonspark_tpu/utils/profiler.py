"""Dashboard + device profiler (parity: the TensorBoard subprocess spawn
of reference TFSparkNode.py:282-319, plus the XLA/TPU profiler capture
the reference lacked — SURVEY.md §5 "Tracing: new build adds native
XLA/TPU profiler capture").

``launch_tensorboard`` mirrors the reference's behavior: port from
``TENSORBOARD_PORT`` or ephemeral, binary found next to the python
executable / on PATH / via PYTHONPATH module fallback, child killed at
node shutdown.  ``trace``/``start_trace``/``stop_trace`` wrap
``jax.profiler`` so each worker can drop a device trace (HLO timelines,
MXU utilization) into the same log_dir TensorBoard serves.
"""

from __future__ import annotations

import contextlib
import logging
import os
import socket
import subprocess
import sys
import time

from tensorflowonspark_tpu.utils import telemetry

logger = logging.getLogger(__name__)


def _find_tensorboard():
    """Locate a tensorboard executable (TFSparkNode.py:299-311 order:
    python bin dir, then PATH)."""
    candidates = [
        os.path.join(os.path.dirname(sys.executable), "tensorboard"),
    ]
    from tensorflowonspark_tpu.utils.hostinfo import find_in_path

    on_path = find_in_path(os.environ.get("PATH", ""), "tensorboard")
    if on_path:
        candidates.append(on_path)
    for c in candidates:
        if c and os.path.isfile(c) and os.access(c, os.X_OK):
            return [c]
    try:  # module fallback (no console script installed)
        import tensorboard  # noqa: F401

        return [sys.executable, "-m", "tensorboard.main"]
    except ImportError:
        return None


def launch_tensorboard(log_dir, port=None):
    """Spawn TensorBoard on ``log_dir``; returns (process, port) or
    (None, None) when no tensorboard is installed (logged, not fatal)."""
    cmd = _find_tensorboard()
    if not cmd:
        logger.warning("tensorboard not found; dashboard disabled")
        return None, None
    if port is None:
        if os.environ.get("TENSORBOARD_PORT"):
            port = int(os.environ["TENSORBOARD_PORT"])
        else:
            with socket.socket() as s:  # ephemeral pick
                s.bind(("", 0))
                port = s.getsockname()[1]
    os.makedirs(log_dir, exist_ok=True)
    tb_log = os.path.join(log_dir, "tensorboard.log")
    with open(tb_log, "ab") as sink:
        proc = subprocess.Popen(
            cmd + ["--logdir", log_dir, "--port", str(port), "--bind_all"],
            stdout=sink,
            stderr=sink,
        )
    # liveness check: an ephemeral port can be stolen between release and
    # the child's bind, and a bad install dies instantly — don't advertise
    # a dashboard that isn't running
    time.sleep(1.0)
    if proc.poll() is not None:
        logger.warning(
            "tensorboard exited immediately (rc=%s); see %s",
            proc.returncode, tb_log,
        )
        return None, None
    logger.info("TensorBoard pid=%d port=%d logdir=%s", proc.pid, port, log_dir)
    return proc, port


def stop_tensorboard(proc):
    if proc is not None and proc.poll() is None:
        proc.kill()
        proc.wait(timeout=10)


# Capture degrades to a no-op on images where jax.profiler can't start a
# trace (no jax, no profiler plugin, CPU-only builds without the capture
# backend).  A missing profiler must never take down the run — or the
# obs control plane asking a worker for an on-demand capture — so every
# entry point warns once and reports success as a boolean.
_degraded_warned = False


def _warn_unavailable(err):
    global _degraded_warned
    if not _degraded_warned:
        logger.warning(
            "jax profiler capture unavailable (%s); trace is a no-op", err)
        _degraded_warned = True
    else:
        logger.debug("jax profiler capture unavailable: %s", err)


def start_trace(log_dir):
    """Begin an XLA device trace (viewable in TensorBoard's profile tab).

    THE one place the program starts a capture.  The Python tracer is
    off: it hooks every call of every thread and slowed the feed's
    consumer thirteenfold (PERF.md, PR23); the program's own spans reach
    the capture as TraceMe events (``telemetry.span``), which the host
    tracer records.  Every capture opens with one ``tfos/clock``
    annotation carrying the wall clock, because the capture's own
    timestamps count from its start (PERF.md, PR25): that is what puts a
    feeder's spool and the capture on one timeline
    (``scripts/trace_merge.py --xplane``).

    Returns True when a capture actually started; False when capture is
    unavailable in this build (warned once, never raises)."""
    try:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        with telemetry.span(telemetry.CLOCK, time_ns=time.time_ns()):
            pass
        return True
    except Exception as e:  # noqa: BLE001 - capture is best-effort
        _warn_unavailable(e)
        return False


def stop_trace():
    """End the running trace; returns True on success (never raises)."""
    try:
        import jax

        jax.profiler.stop_trace()
        return True
    except Exception as e:  # noqa: BLE001 - capture is best-effort
        _warn_unavailable(e)
        return False


@contextlib.contextmanager
def trace(log_dir, enabled=True):
    """``with profiler.trace(log_dir): step(...)`` around hot steps.

    Degrades to a plain passthrough when capture is unavailable (the
    body always runs; only the trace is skipped)."""
    if not enabled:
        yield
        return
    started = start_trace(log_dir)
    try:
        yield
    finally:
        if started:
            stop_trace()
