"""Continuous micro-batching scheduler over the executor cache.

Requests (one image each, possibly mixed resolutions and deadlines)
flow through an admission queue per resolution.  Batch formation groups
same-resolution requests into the *largest ready bucket* — never
padding a 5-deep queue to a fixed microbatch of 8 — and a ragged tail
is flushed to the smallest bucket that fits it, either when its
deadline comes due or at drain.  This is the continuous-batching
discipline of the LM engine (``serving.engine``) translated to vision:
there slots free per token, here buckets form per dispatch.

Dispatches are asynchronous: ``step()`` hands padded batches to the
compiled executors and returns without any host/device sync.
``finalize()`` materializes every outstanding output, in dispatch
order, scatters logits back onto their requests and stamps completion
latency into telemetry.  The async host loop (below) instead completes
one batch at a time and dispatches again before it waits on the next,
so the batches still queued on the device cover the host's hand-off.

Wall-clock is injectable (``clock=``): the serving benchmark replays
recorded traces on a manual clock, so queue-wait and deadline behavior
are deterministic and testable.

## Fault tolerance

The scheduler guarantees every submitted request terminates in exactly
ONE of three states (``Request.status``), with ``Request.error`` typed
(``repro.common.errors``) for the two failure outcomes:

    "completed"  logits delivered;
    "shed"       never served: admission bound hit (CapacityExceeded)
                 or the hard per-request deadline expired while queued
                 (DeadlineExceeded) — an expired request is swept out
                 *before* batch formation, so it never occupies a slot;
    "failed"     served ``max_retries`` times and every attempt raised.

Failed dispatches (executor build errors, fused-launch faults, negative
-cache hits) retry with exponential backoff; from the second failure on
the executor cache's degradation ladder moves (the blamed site demoted,
then the reference interpreter), and a ``NumericsError`` — finalize
detects NaN/Inf in delivered logits — pins the bucket's plan to fp
immediately.  All of it is surfaced through ``Telemetry``: ``shed`` /
``retries`` / ``failed`` / ``degraded`` / ``pinned_fp`` counters plus
per-bucket error counts.

Two failure classes bypass the ladder (``serving.sharding``): a
``DeviceLostError`` shrinks the executor cache's device mesh instead —
replanning on the survivors IS the recovery, so the surviving devices
keep their fused plans — and once the mesh is exhausted every affected
request fails immediately with ``MeshExhausted`` rather than burning
its retry budget against an empty mesh.

## The async host loop

``start()`` runs the scheduler on a background thread behind the
(bounded) admission queue: ``submit()`` returns immediately, ``wait()``
blocks until a request set is terminal, ``stop()`` drains and joins.
Every public entry point locks the same RLock, so the
foreground/background interleaving cannot corrupt queue state.  Each
turn of the loop ``step()``s, then waits for the oldest batch in flight
with that lock released (``block_until_ready``), so ``submit()`` lands
while the device computes.  It then retakes the lock, completes the
batches at the front of the in-flight list whose outputs are ready (at
least the oldest, in dispatch order), wakes ``wait()``ers and steps
again: a refill is launched while the batches behind the one just read
still run, so the device does not drain between turns.  The in-flight
list is read again after the wait, since the watchdog or a foreground
``finalize()`` may have taken the batch meanwhile.

A *watchdog* (``watchdog_ms``) sweeps dispatched-but-unmaterialized
batches: one that has been in flight longer than the bound is declared
hung — a typed ``DeadlineExceeded`` routed through the same failure
path, so the ladder moves and the requests retry on a rebuilt executor
instead of blocking the loop forever.

``result_cache`` puts an image-hash response cache in front of
admission: a repeated image completes at ``submit()`` without touching
a queue or a batch slot.  Only healthy results enter it — finalize
stores a result only when its executor is undegraded and its logits
are finite, so a degraded plan or a corrupted epilogue can never pin a
wrong answer into the cache.
"""
from __future__ import annotations

import collections
import dataclasses
import hashlib
import threading
import time
from typing import List, Optional

import jax.numpy as jnp
import numpy as np

from repro.common.errors import (
    CapacityExceeded, DeadlineExceeded, DeviceLostError, ExecutorError,
    MeshExhausted, NumericsError, ReproError)
from repro.serving.executors import ExecutorCache
from repro.serving.telemetry import Telemetry

__all__ = ["Request", "form_batches", "ManualClock", "MicroBatchScheduler",
           "ResultCache"]


@dataclasses.dataclass
class Request:
    """One classification request: an (H, W, 3) image + optional deadline
    (milliseconds after arrival) by which it should be dispatched even if
    its bucket has not filled.

    ``deadline_ms`` is the *soft* target — it triggers a tail flush so
    the request dispatches by then.  ``timeout_ms`` is the *hard* SLA:
    once it expires the result is worthless, so the scheduler sheds the
    request (``status="shed"``, ``error=DeadlineExceeded``) instead of
    spending a batch slot on it.
    """
    rid: int
    image: object
    deadline_ms: Optional[float] = None
    timeout_ms: Optional[float] = None   # hard deadline; None = never shed
    arrival: float = 0.0                 # stamped by submit()
    logits: Optional[np.ndarray] = None  # filled by finalize()
    status: str = "pending"              # pending | completed | shed | failed
    error: Optional[ReproError] = None   # typed cause for shed/failed
    retries: int = 0                     # failed dispatch attempts so far
    # tracing handles (obs.trace spans; None when no tracer is threaded):
    # ``span`` is the request's root span (submit -> terminal), ``qspan``
    # the currently-open queue-residency child (one per queue/backoff stay)
    span: Optional[object] = dataclasses.field(default=None, repr=False)
    qspan: Optional[object] = dataclasses.field(default=None, repr=False)

    @property
    def resolution(self) -> int:
        return int(np.shape(self.image)[0])


class ManualClock:
    """Deterministic clock for trace replay and deadline tests."""

    def __init__(self, now: float = 0.0):
        self.now = float(now)

    def __call__(self) -> float:
        return self.now

    def advance(self, dt: float) -> float:
        self.now += float(dt)
        return self.now

    def advance_to(self, t: float) -> float:
        self.now = max(self.now, float(t))
        return self.now


class ResultCache:
    """Image-hash -> logits LRU in front of admission.

    Keys are content hashes (blake2b over the fp32 image bytes plus the
    shape), so a byte-identical resubmission — retried uploads, probe
    traffic, duplicate frames — completes without occupying a batch
    slot.  ``put`` refuses non-finite logits: results that slipped past
    a degraded executor or a corrupted epilogue must never be replayed
    to a later request.
    """

    def __init__(self, capacity: int = 256):
        assert capacity >= 1, capacity
        self.capacity = int(capacity)
        self._lru: "collections.OrderedDict[tuple, np.ndarray]" = \
            collections.OrderedDict()
        self.hits = 0
        self.misses = 0

    @staticmethod
    def key(image) -> tuple:
        a = np.ascontiguousarray(np.asarray(image, np.float32))
        return (hashlib.blake2b(a.tobytes(), digest_size=16).hexdigest(),
                a.shape)

    def get(self, image) -> Optional[np.ndarray]:
        k = self.key(image)
        hit = self._lru.get(k)
        if hit is None:
            self.misses += 1
            return None
        self._lru.move_to_end(k)
        self.hits += 1
        return hit

    def put(self, image, logits) -> bool:
        arr = np.asarray(logits)
        if not np.all(np.isfinite(arr)):
            return False     # integrity guard: never cache corruption
        self._lru[self.key(image)] = arr
        self._lru.move_to_end(self.key(image))
        while len(self._lru) > self.capacity:
            self._lru.popitem(last=False)
        return True

    def __len__(self) -> int:
        return len(self._lru)


def form_batches(qlen: int, buckets, due: bool) -> List[int]:
    """Batch formation: the bucket sizes to dispatch for ``qlen`` queued
    requests over the ascending ``buckets``.  Full largest buckets go
    at once; the ragged tail goes to the smallest bucket >= tail only
    when ``due`` (deadline or drain)."""
    sizes = []
    big = buckets[-1]
    while qlen >= big:
        sizes.append(big)
        qlen -= big
    if due and qlen:
        sizes.append(next(b for b in buckets if b >= qlen))
    return sizes


@dataclasses.dataclass(eq=False)
class _InFlight:
    """One dispatched batch not yet read back.  ``rspan`` is its open
    ``readback`` span once the host loop waits on it with the lock
    released, so whoever completes it ends that span."""
    out: object
    reqs: List[Request]
    key: tuple
    ex: object
    t_disp: float
    devspan: Optional[object] = None
    rspan: Optional[object] = None


def _ready(out) -> bool:
    """Whether reading ``out`` would return without waiting on the
    device: a device array that says so, or a host array."""
    is_ready = getattr(out, "is_ready", None)
    if is_ready is not None:
        return bool(is_ready())
    return getattr(out, "block_until_ready", None) is None


class MicroBatchScheduler:
    """Admission queues + batch formation + async dispatch over an
    ``ExecutorCache``.

    Typical loop (the benchmark's trace replay)::

        sched = MicroBatchScheduler(cache, params)
        for req in arriving:   sched.submit(req); sched.step()
        sched.step(drain=True)
        sched.finalize()       # req.logits populated

    or one-shot: ``sched.serve(requests) -> (n, num_classes)``.

    Fault-tolerance knobs (all inert by default):
    ``max_queue_depth`` bounds total admission (beyond it, submits shed
    with ``CapacityExceeded``); ``max_retries`` / ``backoff_ms`` /
    ``backoff_base`` shape the retry-with-exponential-backoff policy
    for failed dispatches; ``faults`` is a ``serving.faults.FaultPlan``
    consulted at admission (the "queue.overload" point).
    """

    def __init__(self, cache: ExecutorCache, params, *,
                 telemetry: Telemetry | None = None,
                 clock=None, max_queue_depth: int | None = None,
                 max_retries: int = 4, backoff_ms: float = 10.0,
                 backoff_base: float = 2.0, faults=None,
                 watchdog_ms: float | None = None,
                 result_cache: int | None = None, tracer=None):
        self.cache = cache
        self.params = params
        # obs.trace.Tracer (or None).  Span recording is host-clock only
        # — begin/end cost two clock reads and a deque append; nothing
        # on the dispatch path synchronizes with the device.
        self.tracer = tracer
        self.telemetry = (telemetry if telemetry is not None
                          else cache.telemetry)
        self.clock = clock if clock is not None else time.monotonic
        self.max_queue_depth = max_queue_depth
        self.max_retries = int(max_retries)
        self.backoff_ms = float(backoff_ms)
        self.backoff_base = float(backoff_base)
        self.faults = faults
        self.watchdog_ms = watchdog_ms
        self.results = ResultCache(result_cache) \
            if result_cache is not None else None
        self._queues: dict[int, collections.deque] = {}
        self._pending: List[_InFlight] = []    # in dispatch order
        self._retry: list = []       # (not_before, resolution, requests)
        self._lock = threading.RLock()
        self._work = threading.Condition(self._lock)
        self._thread: Optional[threading.Thread] = None
        self._stopping = False

    # -- tracing helpers (no-ops without a tracer) -----------------------
    def _t_end(self, span, **attrs) -> None:
        if self.tracer is not None and span is not None:
            self.tracer.end(span, **attrs)

    def _t_event(self, req: Request, name: str, **attrs) -> None:
        if self.tracer is not None:
            self.tracer.event(req.span, name, **attrs)

    def _t_close(self, req: Request, status: str) -> None:
        """Close a request's open spans at a terminal transition."""
        if self.tracer is None:
            return
        self._t_end(req.qspan)
        req.qspan = None
        self._t_end(req.span, status=status)

    # -- terminal states (the no-lost / no-duplicated invariant) ---------
    def _shed(self, req: Request, err: ReproError) -> None:
        assert req.status == "pending", (req.rid, req.status)
        req.status, req.error = "shed", err
        self.telemetry.count("shed")
        self.telemetry.count(
            "shed_deadline" if isinstance(err, DeadlineExceeded)
            else "shed_capacity")
        self._t_event(req, "shed", error=type(err).__name__)
        self._t_close(req, "shed")

    def _fail(self, req: Request, err: ReproError) -> None:
        assert req.status == "pending", (req.rid, req.status)
        req.status, req.error = "failed", err
        self.telemetry.count("failed")
        self._t_event(req, "failed", error=type(err).__name__)
        self._t_close(req, "failed")

    # -- admission -------------------------------------------------------
    def submit(self, req: Request) -> bool:
        """Admit one request; returns False when it was shed instead
        (bounded queue / overload fault), with ``req.error`` typed.
        A result-cache hit completes the request here — in front of
        admission, before the queue bound is even consulted."""
        # "admit": how long this client waited for the scheduler lock
        # (held by step, by the loop completing a batch, or by a
        # caller's finalize blocked on the device)
        aspan = None
        if self.tracer is not None:
            aspan = self.tracer.begin("admit", track="client", rid=req.rid)
        with self._lock:
            if aspan is not None:
                self.tracer.end(aspan)
            req.arrival = self.clock()
            self.telemetry.count("submitted")
            if self.tracer is not None:
                req.span = self.tracer.begin(
                    "request", rid=req.rid, resolution=req.resolution)
            if self.results is not None:
                hit = self.results.get(req.image)
                if hit is not None:
                    req.logits = np.array(hit)
                    req.status = "completed"
                    self.telemetry.count("result_cache_hit")
                    self.telemetry.count("completed")
                    self._t_event(req, "result_cache_hit")
                    self._t_close(req, "completed")
                    return True
                self.telemetry.count("result_cache_miss")
            if self.faults is not None:
                try:
                    self.faults.fire("queue.overload",
                                     resolution=req.resolution)
                except CapacityExceeded as e:
                    self._shed(req, e)
                    return False
            if self.max_queue_depth is not None \
                    and self.queue_depth() >= self.max_queue_depth:
                self._shed(req, CapacityExceeded(
                    f"admission queue full ({self.max_queue_depth}); "
                    f"request {req.rid} shed"))
                return False
            if self.tracer is not None:
                req.qspan = self.tracer.begin("queue", parent=req.span)
            self._queues.setdefault(req.resolution,
                                    collections.deque()).append(req)
            self._work.notify_all()
            return True

    def queue_depth(self, resolution: int | None = None) -> int:
        with self._lock:
            if resolution is not None:
                return len(self._queues.get(resolution, ()))
            return sum(len(q) for q in self._queues.values())

    def outstanding(self) -> int:
        """Requests not yet terminal: queued + awaiting retry + in
        flight on the device."""
        with self._lock:
            return (self.queue_depth()
                    + sum(len(reqs) for _, _, reqs in self._retry)
                    + sum(len(b.reqs) for b in self._pending))

    # -- batch formation + dispatch -------------------------------------
    def _due(self, q) -> bool:
        now = self.clock()
        return any(r.deadline_ms is not None
                   and now >= r.arrival + r.deadline_ms / 1e3 for r in q)

    def _expired(self, req: Request, now: float) -> bool:
        return req.timeout_ms is not None \
            and now > req.arrival + req.timeout_ms / 1e3

    def _sweep_expired(self) -> int:
        """Shed every queued/retry-parked request whose hard deadline
        passed — BEFORE batch formation, so none occupies a slot."""
        now = self.clock()
        shed = 0
        for res, q in self._queues.items():
            keep = collections.deque()
            for r in q:
                if self._expired(r, now):
                    self._shed(r, DeadlineExceeded(
                        f"request {r.rid} expired after "
                        f"{r.timeout_ms:g} ms in queue"))
                    shed += 1
                else:
                    keep.append(r)
            self._queues[res] = keep
        retry = []
        for not_before, res, reqs in self._retry:
            live = []
            for r in reqs:
                if self._expired(r, now):
                    self._shed(r, DeadlineExceeded(
                        f"request {r.rid} expired after "
                        f"{r.timeout_ms:g} ms (while backing off)"))
                    shed += 1
                else:
                    live.append(r)
            if live:
                retry.append((not_before, res, live))
        self._retry = retry
        return shed

    def _requeue_ripe_retries(self, drain: bool) -> None:
        """Move retry groups whose backoff elapsed back to the FRONT of
        their admission queue (they are the oldest requests)."""
        now = self.clock()
        parked = []
        for not_before, res, reqs in self._retry:
            if drain or now >= not_before:
                q = self._queues.setdefault(res, collections.deque())
                for r in reversed(reqs):
                    q.appendleft(r)
            else:
                parked.append((not_before, res, reqs))
        self._retry = parked

    def step(self, *, drain: bool = False) -> int:
        """Form and dispatch every ready batch; returns the number of
        requests dispatched.  ``drain=True`` treats all queues as due
        (and retries immediately, ignoring remaining backoff)."""
        with self._lock:
            self._check_watchdog()
            self._sweep_expired()
            self._requeue_ripe_retries(drain)
            dispatched = 0
            for res, q in list(self._queues.items()):
                due = drain or self._due(q)
                for size in form_batches(len(q), self.cache.buckets, due):
                    take = min(size, len(q))
                    if take == 0:
                        break
                    reqs = [q.popleft() for _ in range(take)]
                    if self.tracer is not None:
                        with self.tracer.span(
                                "form", resolution=res, bucket=size,
                                rids=[r.rid for r in reqs]):
                            for r in reqs:
                                self._t_end(r.qspan)
                                r.qspan = None
                    self._dispatch(res, reqs, size)
                    dispatched += take
            return dispatched

    def _dispatch(self, resolution: int, reqs: List[Request],
                  bucket: int) -> None:
        now = self.clock()
        key = (bucket, resolution, self.cache.precision)
        rids = [r.rid for r in reqs]
        dspan = None
        if self.tracer is not None:
            dspan = self.tracer.begin(
                "dispatch", rids=rids, bucket=bucket,
                resolution=resolution, precision=self.cache.precision)
        try:
            ex = self.cache.get(bucket, resolution)
        except ReproError as e:
            self._t_end(dspan, error=type(e).__name__)
            self._on_failure(resolution, reqs, key, e)
            return
        imgs = np.stack([np.asarray(r.image, np.float32) for r in reqs])
        if bucket > len(reqs):
            pad = np.zeros((bucket - len(reqs),) + imgs.shape[1:],
                           imgs.dtype)
            imgs = np.concatenate([imgs, pad])
        lspan = None
        if self.tracer is not None:
            hspan = self.tracer.begin("h2d", parent=dspan, bucket=bucket,
                                      bytes=imgs.nbytes)
        x = jnp.asarray(imgs)                    # the host->device copy
        # batches already in flight: 0 means the device has nothing
        # queued behind which this launch could wait
        inflight = len(self._pending)
        if self.tracer is not None:
            self.tracer.end(hspan)
            lspan = self.tracer.begin("launch", parent=dspan,
                                      inflight=inflight)
        try:
            out = ex(self.params, x)             # async, no host sync
        except ReproError as e:
            self._t_end(lspan, error=type(e).__name__)
            self._t_end(dspan, error=type(e).__name__)
            self._on_failure(resolution, reqs, key, e, ex=ex)
            return
        if self.tracer is not None:
            self.tracer.end(lspan)
        if not inflight:
            self.telemetry.count("launch_into_empty")
        self.telemetry.record_dispatch(
            key, len(reqs), bucket,
            queue_depth=len(self._queues.get(resolution, ())),
            wait_ms=[(now - r.arrival) * 1e3 for r in reqs])
        if getattr(ex, "shard", None) is not None:
            self.telemetry.record_device_dispatch(
                ex.device_ids, len(reqs), bucket)
        # the "device" span is the host-observed in-flight window:
        # dispatch -> materialization.  No device sync happens here.
        devspan = None
        if self.tracer is not None:
            devspan = self.tracer.begin(
                "device", rids=rids, bucket=bucket, resolution=resolution,
                devices=list(getattr(ex, "device_ids", ()) or ()))
        self._pending.append(_InFlight(out, reqs, key, ex, now, devspan))
        self._t_end(dspan)

    # -- failure handling: retry/backoff + the degradation ladder --------
    def _on_failure(self, resolution: int, reqs: List[Request], key,
                    err: ReproError, ex=None) -> None:
        """One dispatch (or finalize) attempt failed for a whole group.

        Attempt 1 of a *transient* error retries the same executor after
        backoff; from attempt 2 on (or immediately for persistent
        errors) the cache's degradation ladder moves — the blamed site
        demoted, then the reference interpreter — and a numerics error
        pins the bucket to fp at once.  Requests whose retry budget is
        spent terminate as "failed"; the rest park in the retry buffer
        with exponential backoff.

        Two sharding-specific branches: a ``DeviceLostError`` shrinks
        the mesh instead of moving the ladder (the shrunken rebuild IS
        the recovery — the survivors keep their fused plans), and an
        exhausted mesh fails the group immediately, typed
        ``MeshExhausted``, so nothing retries into a serving stack with
        no devices left.
        """
        self.telemetry.count("dispatch_failures")
        self.telemetry.record_error(key)
        attempt = max(r.retries for r in reqs) + 1
        for r in reqs:
            r.retries = attempt
        bucket = key[0]
        blamed = getattr(err, "site", None)
        if isinstance(err, DeviceLostError):
            dev = err.device
            if dev is None and ex is not None:
                dev = self.cache.health.attribute(err, ex.shard) \
                    if getattr(self.cache, "health", None) is not None \
                    else None
            if getattr(self.cache, "on_device_lost", None) is not None \
                    and self.cache.on_device_lost(dev):
                self.telemetry.count("device_failover", len(reqs))
                for r in reqs:
                    self._t_event(r, "failover", device=dev,
                                  error=type(err).__name__)
        elif isinstance(err, NumericsError):
            # fake caches in tests may return None; attrs degrade softly
            state = self.cache.pin_fp(bucket, resolution)
            for r in reqs:
                self._t_event(r, "pin_fp", site=blamed,
                              level=getattr(state, "level", None),
                              error=type(err).__name__)
        elif not isinstance(err, MeshExhausted) \
                and (not err.transient or attempt >= 2):
            state = self.cache.degrade(bucket, resolution, site=blamed)
            for r in reqs:
                self._t_event(r, "degrade", site=blamed,
                              level=getattr(state, "level", None),
                              demoted=sorted(getattr(state, "demoted",
                                                     ()) or ()),
                              error=type(err).__name__)
        if isinstance(err, MeshExhausted) \
                or getattr(self.cache, "mesh_exhausted", False):
            if not isinstance(err, MeshExhausted):
                err = MeshExhausted(
                    f"mesh exhausted while serving {key}: {err}", key=key)
            for r in reqs:
                self._fail(r, err)
            return
        if attempt > self.max_retries:
            for r in reqs:
                self._fail(r, err)
            return
        self.telemetry.count("retries", len(reqs))
        not_before = self.clock() + self.backoff_ms / 1e3 \
            * self.backoff_base ** (attempt - 1)
        if self.tracer is not None:
            for r in reqs:
                self._t_event(r, "retry", attempt=attempt,
                              error=type(err).__name__, site=blamed)
                # backoff is queue time: a fresh residency span
                self._t_end(r.qspan)
                r.qspan = self.tracer.begin("queue", parent=r.span,
                                            retry=attempt)
        self._retry.append((not_before, resolution, list(reqs)))

    # -- completion ------------------------------------------------------
    def finalize(self) -> int:
        """Block on outstanding dispatches (in dispatch order), scatter
        logits onto requests, stamp completion latency.  Returns the
        number of requests completed.

        This is where async failures surface: a compile/launch error
        raised at materialization, or non-finite logits (the int8
        epilogue blow-up signature), routes the batch through the same
        retry/degradation path as a dispatch failure — call ``step()``
        again afterwards to re-dispatch (``outstanding()`` tells you
        whether anything went back).
        """
        with self._lock:
            self._check_watchdog()
            pending, self._pending = self._pending, []
            return self._completed(sum(self._complete(b) for b in pending))

    def _completed(self, done: int) -> int:
        self.telemetry.count("completed", done)
        if done:
            self._work.notify_all()
        return done

    def _complete(self, b: _InFlight) -> int:
        """Read one batch back (blocking), check it and scatter its logits
        onto its requests; returns the number completed, 0 when the batch
        went to ``_on_failure`` instead.  The caller holds the lock and
        has taken ``b`` off ``_pending``."""
        reqs, key, ex = b.reqs, b.key, b.ex
        # "readback": the host blocked on this batch's answer; the host
        # loop opened it already if it waited on the device first
        rspan = b.rspan
        if self.tracer is not None and rspan is None:
            rspan = self.tracer.begin("readback", parent=b.devspan,
                                      bucket=key[0])
        try:
            arr = np.asarray(b.out)                # sync on this chunk
        except ReproError as e:
            self._t_end(rspan, error=type(e).__name__)
            self._t_end(b.devspan, error=type(e).__name__)
            self._on_failure(key[1], reqs, key, e, ex=ex)
            return 0
        except Exception as e:                     # untyped XLA crash
            self._t_end(rspan, error=type(e).__name__)
            self._t_end(b.devspan, error=type(e).__name__)
            self._on_failure(key[1], reqs, key, ExecutorError(
                f"materializing executor {key} output failed: {e}"), ex=ex)
            return 0
        self._t_end(rspan)
        self._t_end(b.devspan)
        fspan = None
        if self.tracer is not None:
            fspan = self.tracer.begin(
                "finalize", rids=[r.rid for r in reqs],
                bucket=key[0], resolution=key[1])
        if not np.all(np.isfinite(arr[:len(reqs)])):
            self._t_end(fspan, error="NumericsError")
            self._on_failure(key[1], reqs, key, NumericsError(
                f"non-finite logits delivered by executor {key} "
                f"(int8 epilogue blow-up signature)", key=key), ex=ex)
            return 0
        t = self.clock()
        healthy = (getattr(ex, "degraded", None) is None
                   or not ex.degraded.degraded)
        for i, r in enumerate(reqs):
            assert r.status == "pending", (r.rid, r.status)
            r.logits = arr[i]
            r.status = "completed"
            # only undegraded, finite results may be replayed
            if self.results is not None and healthy \
                    and self.results.put(r.image, arr[i]):
                self.telemetry.count("result_cache_store")
            self._t_close(r, "completed")
        self.telemetry.record_latency(
            key, [(t - r.arrival) * 1e3 for r in reqs])
        self._t_end(fspan)
        return len(reqs)

    # -- the watchdog ----------------------------------------------------
    def _check_watchdog(self) -> int:
        """Convert hung in-flight batches into typed failures.

        A dispatched batch whose output has not materialized within
        ``watchdog_ms`` is declared hung: its device output is dropped
        and the group routes through ``_on_failure`` as a
        ``DeadlineExceeded`` — persistent, so the degradation ladder
        moves immediately and the retry lands on a rebuilt executor
        instead of the wedged one.  Returns the number of batches
        declared hung.
        """
        if self.watchdog_ms is None or not self._pending:
            return 0
        now = self.clock()
        keep, hung = [], []
        for b in self._pending:
            (hung if now - b.t_disp > self.watchdog_ms / 1e3
             else keep).append(b)
        self._pending = keep
        for b in hung:
            key = b.key
            self.telemetry.count("watchdog_fired")
            self._t_end(b.rspan, error="watchdog")
            self._t_end(b.devspan, error="watchdog")
            for r in b.reqs:
                self._t_event(r, "watchdog_fired", bucket=key[0])
            self._on_failure(key[1], b.reqs, key, DeadlineExceeded(
                f"batch {key} in flight for {(now - b.t_disp) * 1e3:.0f} "
                f"ms (watchdog bound {self.watchdog_ms:g} ms) — declared "
                f"hung", key=key), ex=b.ex)
        return len(hung)

    # -- the async host loop ---------------------------------------------
    def start(self, poll_s: float = 0.002) -> "MicroBatchScheduler":
        """Run the host loop on a background thread.

        ``submit()`` then behaves as the async front door: it enqueues
        (or sheds) and returns; the loop forms batches as they become
        ready and completes them one at a time, oldest first, waiting on
        the device with the lock released.  ``poll_s`` bounds how long
        the loop sleeps when nothing is in flight — deadline flushes,
        backoff expiry and the watchdog are all polled at least this
        often.
        """
        with self._lock:
            if self._thread is not None:
                return self
            self._stopping = False
            self._thread = threading.Thread(
                target=self._loop, args=(float(poll_s),),
                name="microbatch-scheduler", daemon=True)
            self._thread.start()
        return self

    @property
    def running(self) -> bool:
        return self._thread is not None

    def _loop(self, poll_s: float) -> None:
        while True:
            with self._lock:
                if self._stopping:
                    return
                self.step()
                if not self._pending:
                    self._work.wait(timeout=poll_s)
                    continue
                oldest = self._pending[0]
                if self.tracer is not None:
                    oldest.rspan = self.tracer.begin(
                        "readback", parent=oldest.devspan,
                        bucket=oldest.key[0])
            # wait on the device with the lock free, so submit() lands
            wait = getattr(oldest.out, "block_until_ready", None)
            if wait is not None:
                try:
                    wait()
                except Exception:
                    pass        # the read under the lock raises it again
            with self._lock:
                # the watchdog or a caller's finalize() may have taken it
                done = 0
                while self._pending and (self._pending[0] is oldest
                                         or _ready(self._pending[0].out)):
                    done += self._complete(self._pending.pop(0))
                self._completed(done)

    def stop(self, *, drain: bool = True) -> None:
        """Join the host loop; ``drain=True`` first serves everything
        still outstanding (retries included) on the caller's thread."""
        with self._lock:
            if self._thread is None:
                return
            self._stopping = True
            self._work.notify_all()
            thread, self._thread = self._thread, None
        thread.join()
        if drain:
            while self.outstanding():
                self.step(drain=True)
                self.finalize()

    def wait(self, requests: List[Request],
             timeout_s: float | None = None) -> bool:
        """Block until every request in ``requests`` is terminal
        (completed / shed / failed).  Returns False on timeout.  Only
        meaningful with the host loop running — nothing else makes
        progress while the caller blocks."""
        deadline = (time.monotonic() + timeout_s
                    if timeout_s is not None else None)
        with self._lock:
            while any(r.status == "pending" for r in requests):
                left = None if deadline is None \
                    else deadline - time.monotonic()
                if left is not None and left <= 0:
                    return False
                self._work.wait(timeout=0.05 if left is None
                                else min(0.05, left))
            return True

    # -- one-shot --------------------------------------------------------
    def serve(self, requests: List[Request]) -> np.ndarray:
        """Submit, drain, finalize (looping until every request is
        terminal — retries included); logits stacked in request order.
        Raises the typed error of the first non-completed request if
        any was shed or failed."""
        for r in requests:
            self.submit(r)
        while self.outstanding():
            self.step(drain=True)
            self.finalize()
        bad = next((r for r in requests if r.status != "completed"), None)
        if bad is not None:
            raise bad.error
        return np.stack([r.logits for r in requests])
