"""The system under test, driven through its own entry points.

Set-up builds a ``VisionEngine`` over weights the benchmark made, warms
the cell's buckets, and starts the scheduler's async host loop.  The
window then only calls ``MicroBatchScheduler.submit`` and ``wait`` and
reads ``Request.logits``: batching, dispatch, materialization and
scatter are the program's.  Each request's latency runs from its due
time (when the traffic sends it) to the moment the harness sees its
logits on the host.
"""
from __future__ import annotations

import collections
import dataclasses
import time

import numpy as np

from benchlib.spec import SpecError
from benchlib.traffic import Mix, Schedule

clock = time.perf_counter
GRACE_S = 60.0          # how long past the window's close an answer may come


@dataclasses.dataclass(slots=True)
class Sent:
    """One request as the harness saw it."""
    rid: int
    image: int              # pool index
    due: float              # host clock
    done: float | None = None
    status: str = "pending"
    logits: np.ndarray | None = None


class FirstReading:
    """A clock that remembers its first reading: the ``Tracer``'s spans
    are relative to it, so it puts them back on the host clock."""

    def __init__(self):
        self.first = None

    def __call__(self) -> float:
        t = clock()
        if self.first is None:
            self.first = t
        return t


def make_inputs(ref, cfg: dict, pool: int, seed: int):
    """The weights (fp32, on the device, one jitted call) and the image
    pool (on the host, as clients send them) of a seed."""
    import jax
    key = jax.random.fold_in(jax.random.key(seed % 2 ** 32),
                             (seed // 2 ** 32) % 2 ** 32)
    params, images = jax.jit(lambda k: (
        ref.init_params(jax.random.fold_in(k, 0), cfg),
        ref.images(jax.random.fold_in(k, 1), pool, cfg["image_size"])))(key)
    return jax.block_until_ready(params), jax.device_get(images)


def frozen(value):
    """Lists as tuples, at every depth: the program caches its lowering
    on the hashed config."""
    if isinstance(value, (list, tuple)):
        return tuple(frozen(v) for v in value)
    return value


def model_config(cfg: dict):
    """The program's ``EfficientViTConfig`` of a configuration: its
    ``name`` and ``image_size`` and every key of its ``model``.  A key
    the program has no field for refuses the configuration."""
    from repro.core.efficientvit import EfficientViTConfig
    model = cfg.get("model")
    if not isinstance(model, dict):
        raise SpecError(f"configuration {cfg['name']}: no \"model\" object "
                        f"with the program's architecture")
    fields = {f.name for f in dataclasses.fields(EfficientViTConfig)}
    for key in model:
        if key in ("name", "image_size"):
            raise SpecError(f"configuration {cfg['name']}: \"model\" "
                            f"repeats {key!r}, a top-level key")
        if key not in fields:
            raise SpecError(f"configuration {cfg['name']}: model key "
                            f"{key!r} is not a field of this checkout's "
                            f"EfficientViTConfig, so its program cannot "
                            f"serve this configuration")
    return EfficientViTConfig(name=cfg["name"], image_size=cfg["image_size"],
                              **{k: frozen(v) for k, v in model.items()})


def served_tree(params, cfg: dict):
    """The weights in the form the configuration serves them."""
    import jax
    if cfg["precision"] == "int8":
        from repro.core.quantization import quantize_efficientvit
        return jax.block_until_ready(jax.jit(quantize_efficientvit)(params))
    if cfg["precision"] != "fp32":
        raise ValueError(f"precision {cfg['precision']!r}")
    return params


def build_engine(tree, cfg: dict, mix: Mix, tracer=None):
    from repro.serving.vision import VisionEngine, VisionServeConfig
    return VisionEngine(tree, model_config(cfg), VisionServeConfig(
        microbatch=max(mix.buckets), buckets=mix.buckets,
        precision="int8" if cfg["precision"] == "int8" else "fp",
        autotune=False), tracer=tracer)


class Client:
    """Sends a schedule through a started scheduler and stamps what
    comes back."""

    def __init__(self, sched, pool: np.ndarray, plan: Schedule):
        from repro.serving.scheduler import Request
        self._Request = Request
        self.sched, self.pool, self.plan = sched, pool, plan
        self.sent: list[Sent] = []
        self.lateness: list[float] = []     # send time - due time
        self._inflight: "collections.OrderedDict[int, tuple]" = \
            collections.OrderedDict()

    def _send(self, due: float) -> None:
        i = len(self.sent)
        img = int(self.plan.images[i % len(self.plan.images)])
        s = Sent(rid=i, image=img, due=due)
        req = self._Request(rid=i, image=self.pool[img],
                            deadline_ms=self.plan.mix.deadline_ms)
        self.sent.append(s)
        self._inflight[i] = (s, req)
        self.lateness.append(clock() - due)
        self.sched.submit(req)

    def collect(self) -> None:
        """Stamp the requests that have become terminal.  The scheduler
        answers one resolution's requests in order, so only the front
        of the in-flight queue is looked at."""
        t = clock()
        while self._inflight:
            s, r = next(iter(self._inflight.values()))
            if r.status == "pending":
                return
            s.status = r.status
            if r.status == "completed":
                s.done, s.logits = t, r.logits
            self._inflight.popitem(last=False)

    def _wait_oldest(self, until: float) -> None:
        left = until - clock()
        if left <= 0:
            return
        if not self._inflight:
            time.sleep(left)
            return
        _, req = next(iter(self._inflight.values()))
        self.sched.wait([req], timeout_s=left)
        self.collect()

    def closed(self, t0: float, t1: float, outstanding: int) -> None:
        """Keep ``outstanding`` in flight from now until ``t1``."""
        for _ in range(outstanding):
            self._send(clock())
        while clock() < t1:
            self._wait_oldest(t1)
            while len(self._inflight) < outstanding and clock() < t1:
                self._send(clock())

    def open(self, t0: float) -> None:
        """Send each request at ``t0 + due_s``."""
        for d in self.plan.due_s:
            due = t0 + float(d)
            while clock() < due:
                self._wait_oldest(due)
            self._send(due)
            self.collect()

    def finish(self, deadline: float) -> None:
        """After the window: wait until ``deadline`` for the answers the
        host loop still owes (an open loop's tail is flushed by its
        deadline), then stop the loop, which serves what is left, such
        as a closed loop's partial bucket that only a drain flushes."""
        if self.plan.mix.loop == "open":
            while self._inflight and clock() < deadline:
                self._wait_oldest(deadline)
        self.sched.stop(drain=True)
        self.collect()


def percentile(values, q: float) -> float:
    """Nearest-rank percentile; ``inf`` (a missing answer) sorts last."""
    v = sorted(values)
    if not v:
        return float("nan")
    k = max(0, min(len(v) - 1, int(np.ceil(q / 100.0 * len(v))) - 1))
    return float(v[k])
