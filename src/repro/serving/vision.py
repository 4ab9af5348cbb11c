"""Vision serving: a thin façade over the serving runtime.

``VisionEngine`` is a façade over the runtime subsystem:

    ``serving.executors.ExecutorCache``   shape-bucketed compiled
                                          executables, plans shared
                                          across buckets, LRU eviction
    ``serving.scheduler``                 continuous micro-batching with
                                          deadline-aware flush
    ``serving.telemetry``                 per-bucket counters

The constructor lowers and plans the primary microbatch shape once,
outside the request loop, exposed as ``.program`` / ``.plan``.
``logits`` / ``classify`` chunk a batch the way the scheduler forms
batches (``scheduler.form_batches``: full largest buckets, the ragged
tail to the smallest cached bucket that fits it), and chunks dispatch
without host synchronization between them.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.efficientvit import EfficientViTConfig
from repro.serving.executors import ExecutorCache
from repro.serving.scheduler import (
    MicroBatchScheduler, Request, form_batches)
from repro.serving.telemetry import Telemetry

__all__ = ["VisionServeConfig", "VisionEngine"]


def _default_buckets(microbatch: int) -> tuple:
    """Powers of two up to and including the microbatch: 8 -> (1,2,4,8)."""
    out = {microbatch}
    b = 1
    while b < microbatch:
        out.add(b)
        b *= 2
    return tuple(sorted(out))


@dataclasses.dataclass(frozen=True)
class VisionServeConfig:
    microbatch: int = 8       # largest batch bucket
    autotune: bool = True
    precision: str = "auto"   # "auto" | "fp" | "int8" (FIX8 serving mode:
    #                           pass a quantize_efficientvit tree and the
    #                           plan routes the int8 megakernels)
    buckets: tuple | None = None   # None -> powers of 2 up to microbatch
    capacity: int | None = None    # executor-cache LRU capacity (None =
    #                                unbounded)
    devices: tuple | None = None   # device mesh for batch-axis sharding
    #                                + per-device fault domains; None =
    #                                classic single-device serving
    result_cache: int | None = None  # image-hash response cache capacity
    #                                  in front of admission (None = off)
    watchdog_ms: float | None = None  # in-flight hang bound for the
    #                                   scheduler's watchdog (None = off)
    artifact: object | None = None  # offline-searched ScheduleArtifact
    #                                 (or a path to one): buckets and
    #                                 per-site decisions come from the
    #                                 search, cold start runs zero
    #                                 autotune sweeps (repro.search)


class VisionEngine:
    def __init__(self, params, cfg: EfficientViTConfig,
                 serve_cfg: VisionServeConfig = VisionServeConfig(), *,
                 faults=None, tracer=None):
        self.params = params
        self.cfg = cfg
        self.serve_cfg = serve_cfg
        self.faults = faults  # serving.faults.FaultPlan (chaos testing)
        # one obs.trace.Tracer threaded through the whole runtime: the
        # executor cache, every scheduler this engine vends, and the
        # fault plan (if it doesn't already carry one).  None = tracing
        # off, zero overhead.
        self.tracer = tracer
        if faults is not None and tracer is not None \
                and getattr(faults, "tracer", None) is None:
            faults.tracer = tracer
        artifact = serve_cfg.artifact
        if isinstance(artifact, str):
            from repro.search.artifact import ScheduleArtifact
            artifact = ScheduleArtifact.load(artifact)
        self.artifact = artifact
        if artifact is not None:
            # the searched bucket set replaces the hand-configured one;
            # the microbatch (the primary compiled shape and chunking
            # unit) becomes its largest bucket
            mb = max(artifact.buckets)
            buckets = artifact.buckets
        else:
            mb = serve_cfg.microbatch
            buckets = serve_cfg.buckets
            if buckets is None:
                buckets = _default_buckets(mb)
            # the microbatch is always a bucket: it is the primary
            # compiled shape, and chunking must never hand an n-row
            # batch to an executor compiled for fewer rows
            buckets = tuple(sorted(set(buckets) | {mb}))
        self.microbatch = mb
        self.telemetry = Telemetry()
        self.cache = ExecutorCache(
            params, cfg, buckets=buckets, precision=serve_cfg.precision,
            autotune=serve_cfg.autotune, capacity=serve_cfg.capacity,
            telemetry=self.telemetry, faults=faults,
            devices=serve_cfg.devices, artifact=artifact, tracer=tracer)
        # primary executor built eagerly: plan construction (autotune
        # sweeps included) happens here, outside the request loop, and
        # .program / .plan keep their pre-runtime meaning
        primary = self.cache.get(mb, cfg.image_size)
        self.program = primary.program
        self.plan = primary.plan
        self._scheduler: MicroBatchScheduler | None = None

    @classmethod
    def quantized(cls, params, cfg: EfficientViTConfig,
                  serve_cfg: VisionServeConfig = VisionServeConfig()):
        """FIX8 serving mode: quantize an fp32 param tree post-training
        and serve it through the int8 fused path."""
        from repro.core.quantization import quantize_efficientvit
        return cls(quantize_efficientvit(params, cfg), cfg,
                   dataclasses.replace(serve_cfg, precision="int8"))

    # -- batch API -------------------------------------------------------
    def logits(self, images) -> jax.Array:
        """images: (n, H, W, 3), any n -> (n, num_classes).

        Chunks dispatch asynchronously (no host sync between them); the
        ragged tail routes to the smallest cached bucket >= its size,
        so a 9-image call with microbatch 8 runs an 8-bucket and a
        1-bucket instead of padding 8+8.
        """
        images = jnp.asarray(images)
        n = int(images.shape[0])
        res = int(images.shape[1])
        outs = []
        i = 0
        for bucket in form_batches(n, self.cache.buckets, due=True):
            take = min(bucket, n - i)
            chunk = images[i:i + take]
            if bucket > take:
                chunk = jnp.concatenate(
                    [chunk, jnp.zeros((bucket - take,) + chunk.shape[1:],
                                      chunk.dtype)])
            ex = self.cache.get(bucket, res)
            outs.append(ex(self.params, chunk)[:take])
            self.telemetry.record_dispatch(
                (bucket, res, self.cache.precision), take, bucket)
            i += take
        return jnp.concatenate(outs)

    def classify(self, images) -> np.ndarray:
        """images: (n, H, W, 3) -> (n,) int top-1 labels."""
        return np.asarray(jnp.argmax(self.logits(images), axis=-1))

    # -- request API (the serving runtime) ------------------------------
    def scheduler(self, *, clock=None, **kw) -> MicroBatchScheduler:
        """A continuous micro-batching scheduler bound to this engine's
        executor cache, params and telemetry.  Extra keywords
        (``max_queue_depth``, ``max_retries``, ``backoff_ms``, ...) pass
        through to ``MicroBatchScheduler``; the engine's fault plan is
        installed unless overridden."""
        kw.setdefault("faults", self.faults)
        kw.setdefault("result_cache", self.serve_cfg.result_cache)
        kw.setdefault("watchdog_ms", self.serve_cfg.watchdog_ms)
        kw.setdefault("tracer", self.tracer)
        return MicroBatchScheduler(self.cache, self.params,
                                   telemetry=self.telemetry, clock=clock,
                                   **kw)

    def export_trace(self, path: str) -> dict:
        """Write the engine's request timeline as Chrome trace JSON
        (``chrome://tracing`` / Perfetto).  Requires a tracer."""
        if self.tracer is None:
            raise ValueError("VisionEngine built without tracer=; "
                             "nothing to export")
        return self.tracer.export(path)

    def metrics(self):
        """A ``repro.obs.MetricsRegistry`` over this engine's telemetry
        (Prometheus text / JSON export)."""
        from repro.obs import MetricsRegistry
        return MetricsRegistry(telemetry=self.telemetry)

    def serve(self, requests: list[Request]) -> np.ndarray:
        """Serve a list of ``scheduler.Request``s (mixed resolutions and
        deadlines welcome); returns logits stacked in request order."""
        if self._scheduler is None:
            self._scheduler = self.scheduler()
        return self._scheduler.serve(requests)

    def warmup(self, resolutions=None) -> "VisionEngine":
        """Pre-compile the bucket working set for the given resolutions
        (default: the config's image size)."""
        self.cache.warmup(resolutions if resolutions is not None
                          else (self.cfg.image_size,))
        return self
