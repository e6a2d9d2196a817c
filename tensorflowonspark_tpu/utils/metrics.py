"""Step-time / throughput / MFU / infeed-stall counters.

The reference has no metrics at all (SURVEY.md §5 "Observability = log
lines"); the ≥50% MFU north star needs them.  One lightweight
``TrainMetrics`` aggregator per worker: time steps with ``step()``,
account feed-wait with ``infeed_wait()`` (DataFeed calls this
internally when handed a metrics object), read a structured summary with
``report()``.

MFU convention: model FLOPs per step / (step time x peak FLOPs), peak
resolved from the device kind (PEAK_FLOPS below, the one table).  FLOPs estimators for the
zoo's families are provided (6ND for transformers, 2 x MACs for convs is
the caller's number).
"""

from __future__ import annotations

import functools
import logging
import time

from tensorflowonspark_tpu.utils import faults, metrics_registry, telemetry

logger = logging.getLogger(__name__)

# THE table of bf16 peak FLOP/s per chip, by device-kind substring
# (vendor documentation: v5e 197, v4 275, v5p 459, v6e 918 TFLOP/s).
# bench.py and the scripts read it through peak_flops(); no second table,
# no override.
PEAK_FLOPS = {
    "v5 lite": 197e12,
    "v5e": 197e12,
    "v4": 275e12,
    "v5p": 459e12,
    "v6": 918e12,
}


def peak_flops(device=None):
    """Peak of ``device`` (default: the first jax device) from the table.
    A CPU has no entry and gives None — MFU is then not reported.  Any
    other device the table does not know is an error, never a default:
    a utilization against the wrong peak reads as a measurement."""
    if device is None:
        import jax

        device = jax.devices()[0]
    kind = device.device_kind.lower()
    for k, v in PEAK_FLOPS.items():
        if k in kind:
            return v
    if device.platform == "cpu":
        return None
    raise ValueError(
        f"no peak FLOP/s known for device kind {device.device_kind!r} "
        f"(platform {device.platform!r}); add it to "
        "utils.metrics.PEAK_FLOPS with its source")


def transformer_flops_per_token(cfg, causal=False):
    """~6N FLOPs/token (fwd+bwd) + attention term, from the config.

    Default is the PaLM appendix-B convention: the attention matmuls are
    counted dense (12·L·d·S per token) even for causal models — the
    convention most published MFU numbers use.  ``causal=True`` halves
    the attention term to count only the algorithmically required work,
    the honest denominator for kernels that skip the non-causal half
    (e.g. the pallas flash path with causal block skipping)."""
    n_params = (
        cfg.vocab_size * cfg.dim * 2
        + cfg.n_layers * (cfg.dim * cfg.dim * 4 + cfg.dim * cfg.dim * cfg.mlp_ratio * 2)
    )
    attn = 12 * cfg.n_layers * cfg.dim * cfg.max_seq  # 2*2*3 * L * d * S
    if causal:
        attn //= 2
    return 6 * n_params + attn


def segmentation_flops_per_image(image_size=256, num_classes=21, width=1.0):
    """Forward-pass FLOPs per image for models/segmentation.py, counted
    shape-exactly from the traced program (utils.flops walks the jaxpr;
    2 FLOPs/MAC, transposed-conv zero positions excluded).  Multiply by
    3 for the train step like resnet.flops_per_image's callers.  Tracing
    is abstract (eval_shape) — no device compute, safe pre-backend."""
    return _seg_flops_cached(int(image_size), int(num_classes), float(width))


@functools.lru_cache(maxsize=8)
def _seg_flops_cached(image_size, num_classes, width):
    import jax

    from tensorflowonspark_tpu.models import segmentation
    from tensorflowonspark_tpu.utils import flops as F

    ps, ss = jax.eval_shape(
        lambda k: segmentation.init(k, num_classes=num_classes, width=width),
        jax.random.PRNGKey(0))
    img = jax.ShapeDtypeStruct((1, image_size, image_size, 3), "float32")
    return F.count_flops(
        lambda p, s, x: segmentation.apply(p, s, x, train=True)[0],
        ps, ss, img)["flops"]


@functools.lru_cache(maxsize=1)
def mnist_inference_flops_per_row():
    """Forward-pass FLOPs per row for the MNIST export model that
    BASELINE config #5 (batch inference) serves — the jittable core
    ``mnist.apply``, counted like segmentation_flops_per_image."""
    import jax

    from tensorflowonspark_tpu.models import mnist
    from tensorflowonspark_tpu.utils import flops as F

    params = jax.eval_shape(mnist.init_params, jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((1, 28, 28, 1), "float32")
    return F.count_flops(mnist.apply, params, x)["flops"]


class TrainMetrics:
    """Windowed counters; cheap enough for the hot loop.

    Also the feed point of the training-health watchtower
    (``obs/health.py``): by default a :class:`~.health.HealthMonitor`
    rides along (``TFOS_HEALTH=0`` disables it; pass ``health=False`` to
    opt one instance out, or your own monitor to wire a ``checkpoint_fn``
    for the ``TFOS_HEALTH_ACTION`` reactions) and every ``step()`` hands
    it the step duration, the infeed-stall fraction, and — when the
    caller supplies them — the loss and the device-computed grad-norm
    probe (``utils.train.health_probe``)."""

    def __init__(self, flops_per_item=None, device=None, window=50,
                 health=None):
        self.flops_per_item = flops_per_item
        self.window = window
        self._peak = peak_flops(device) if flops_per_item else None
        if health is None:
            from tensorflowonspark_tpu.obs import health as _health

            self.health = _health.monitor_from_env()
        else:
            self.health = health or None  # health=False opts out
        self.reset()

    def reset(self):
        self.steps = 0
        self.items = 0
        self.step_time = 0.0
        self.infeed_time = 0.0
        self.ring_wait_time = 0.0
        self._last = None

    # -- recording ----------------------------------------------------------

    def infeed_wait(self, seconds, ring_wait=0.0):
        """``seconds`` the feed's consumer spent fetching a chunk, of
        which ``ring_wait`` with the transport EMPTY (the producer's
        side of the ring); the rest was reading the chunk out of it."""
        self.infeed_time += seconds
        self.ring_wait_time += ring_wait

    def step(self, items=0, loss=None, grad_norm=None, grad_finite=None):
        """Call once per completed train step with the item count.

        The first call only arms the timer; its items are NOT counted, so
        rates divide N timed steps' items by N timed steps' time.

        ``loss`` (optional) feeds the health monitor's NaN gate and
        loss-spike detector — pass the step's scalar loss (the float()
        here is the same value fetch the timing convention already
        requires, PERF.md r4).  ``grad_norm``/``grad_finite`` forward
        the ``utils.train.health_probe`` outputs.  A configured
        ``TFOS_HEALTH_ACTION=halt`` propagates :class:`HealthHalt` out
        of this call on a numeric anomaly."""
        # injection point: ``train.step`` — check() serves delay/exc
        # (seeded stragglers), poison() the deterministic NaN e2e.  Both
        # sit before the clock read so an injected delay lands in this
        # step's measured duration like a real slowdown would.
        faults.check("train.step")
        if loss is not None:
            loss = faults.poison("train.step", loss)
        now = time.perf_counter()
        dur = None
        if self._last is not None:
            dur = now - self._last
            self.step_time += dur
            self.items += items
            if telemetry.enabled():
                # same measured duration as the counter above, so the
                # trace-merge percentiles and report() agree exactly
                attrs = {"items": items}
                if self.flops_per_item:
                    attrs["flops_per_item"] = self.flops_per_item
                if self._peak:
                    attrs["peak_flops"] = self._peak
                telemetry.record_span("train/step", dur, **attrs)
            if metrics_registry.enabled():
                # live plane: the same windowed numbers report() derives,
                # published mid-run by obs/publish.py
                metrics_registry.inc("tfos_train_steps_total")
                metrics_registry.observe("tfos_train_step_ms", dur * 1000.0)
                if self.step_time:
                    metrics_registry.set_gauge(
                        "tfos_train_items_per_sec",
                        self.items / self.step_time)
                    metrics_registry.set_gauge(
                        "tfos_train_infeed_stall_frac",
                        min(self.infeed_time / self.step_time, 1.0))
                    if self.flops_per_item and self._peak:
                        metrics_registry.set_gauge(
                            "tfos_train_mfu",
                            self.items * self.flops_per_item
                            / self.step_time / self._peak)
        self._last = now
        self.steps += 1
        if self.health is not None:
            self.health.observe_step(
                loss=None if loss is None else float(loss),
                step_time_s=dur,
                infeed_frac=(min(self.infeed_time / self.step_time, 1.0)
                             if self.step_time else None),
                grad_norm=(None if grad_norm is None else float(grad_norm)),
                grad_finite=(None if grad_finite is None
                             else bool(grad_finite)),
                step=self.steps)

    # -- reading ------------------------------------------------------------

    def report(self):
        """Summary dict over the window since reset(); rates need >=2
        step() calls (the first call only arms the timer)."""
        out = {
            "steps": self.steps,
            "items": self.items,
            "step_time_avg_s": self.step_time / max(self.steps - 1, 1),
            "infeed_wait_s": self.infeed_time,
            "ring_wait_s": self.ring_wait_time,
            "infeed_stall_frac": (
                self.infeed_time / self.step_time if self.step_time else 0.0
            ),
        }
        if self.step_time:
            out["items_per_sec"] = self.items / self.step_time
            if self.flops_per_item and self._peak:
                out["mfu"] = (
                    self.items * self.flops_per_item
                    / self.step_time / self._peak
                )
        return out

    def maybe_log(self, prefix=""):
        if self.steps and self.steps % self.window == 0:
            logger.info("%smetrics: %s", prefix, self.report())
