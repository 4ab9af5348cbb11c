"""Observability layer (ISSUE 9): tracer spans through the serving
runtime (async host loop + watchdog on a manual clock), ring bounding,
Chrome/Perfetto export round-trip, drift-report math on a scripted
timer, Prometheus text escaping, and the benchmark ledger schema.

The tracer tests run against the fault-tolerance suite's fake-cache
idiom: host-only scripted executors, so hundreds of span assertions
stay fast and deterministic."""
import json
import math
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from repro.common.errors import ExecutorError
from repro.obs import (
    BENCH_SCHEMA, TRACE_SCHEMA, MetricsRegistry, Tracer, bench_result,
    escape_label, load_result, request_chains, validate_chrome_trace,
    validate_result, write_result)
from repro.serving.scheduler import (
    ManualClock, MicroBatchScheduler, Request, form_batches)
from repro.serving.telemetry import Telemetry


# -- fakes (the test_fault_tolerance idiom) --------------------------------

class FakeExecutor:
    def __init__(self, cache, bucket):
        self.cache, self.bucket = cache, bucket

    def __call__(self, params, x):
        if self.cache.call_faults:
            raise self.cache.call_faults.pop(0)
        return np.full((int(x.shape[0]), 4), float(self.bucket),
                       np.float32)


class FakeCache:
    def __init__(self, *, buckets=(1, 2, 4), call_faults=()):
        self.buckets = tuple(buckets)
        self.precision = "auto"
        self.telemetry = Telemetry()
        self.call_faults = list(call_faults)
        self.degrades = []

    def get(self, batch, resolution):
        return FakeExecutor(self, batch)

    def degrade(self, batch, resolution, *, site=None):
        self.degrades.append((batch, resolution, site))

    def pin_fp(self, batch, resolution):
        pass


def _reqs(n, res=32, **kw):
    return [Request(rid=i, image=np.zeros((res, res, 3), np.float32), **kw)
            for i in range(n)]


# -- tracer core -----------------------------------------------------------

def test_span_nesting_and_manual_clock():
    clock = ManualClock()
    tr = Tracer(clock=clock)
    root = tr.begin("request", rid=7)
    clock.advance(0.010)
    with tr.span("queue", parent=root):
        clock.advance(0.005)
    tr.event(root, "retry", attempt=1)
    clock.advance(0.001)
    tr.end(root, status="completed")
    q, = tr.spans("queue")
    r, = tr.spans("request")
    assert q.parent_id == r.span_id and q.track == r.track
    assert q.start == pytest.approx(0.010)
    assert q.duration == pytest.approx(0.005)
    assert r.duration == pytest.approx(0.016)
    assert r.attrs["rid"] == 7 and r.attrs["status"] == "completed"
    assert r.event_names() == ("retry",)
    # end is idempotent: the ring holds the span exactly once
    tr.end(r)
    assert len(tr.spans("request")) == 1
    # event on a None span is a guarded no-op (optional handles)
    tr.event(None, "ignored")


def test_ring_bounds_finished_spans():
    tr = Tracer(capacity=8)
    for i in range(20):
        tr.end(tr.begin(f"s{i}"))
    assert len(tr) == 8
    assert tr.dropped == 12
    assert [s.name for s in tr.spans()] == [f"s{i}" for i in range(12, 20)]
    # open spans are not subject to the ring
    tr.begin("open")
    assert [s.name for s in tr.open_spans()] == ["open"]


def test_chrome_export_round_trips_through_json(tmp_path):
    clock = ManualClock()
    tr = Tracer(clock=clock)
    root = tr.begin("request", rid=1, resolution=32)
    q = tr.begin("queue", parent=root)
    clock.advance(0.004)
    tr.end(q)
    tr.event(root, "retry", attempt=1)
    tr.end(root, status="completed")
    b = tr.begin("dispatch", rids=[1], bucket=1, resolution=32)
    tr.end(b)
    for name in ("device", "finalize"):
        tr.end(tr.begin(name, rids=[1], bucket=1, resolution=32))
    path = tmp_path / "trace.json"
    tr.export(str(path))
    doc = json.loads(path.read_text())          # the Perfetto load path
    assert doc["schema"] == TRACE_SCHEMA
    assert validate_chrome_trace(doc) == 5
    spans = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
    assert spans["queue"]["dur"] == pytest.approx(4000.0)  # µs
    assert spans["queue"]["args"]["parent_id"] \
        == spans["request"]["args"]["span_id"]
    chains = request_chains(doc)
    assert set(chains) == {1}
    c = chains[1]
    assert {"queue"} <= c["children"]
    assert {"dispatch", "device", "finalize"} <= c["member_of"]
    assert c["events"] == ("retry",)


def test_validate_chrome_trace_rejects_malformed():
    with pytest.raises(ValueError, match="schema"):
        validate_chrome_trace({"traceEvents": []})
    with pytest.raises(ValueError, match="traceEvents"):
        validate_chrome_trace({"schema": TRACE_SCHEMA})
    bad = {"schema": TRACE_SCHEMA, "traceEvents": [
        {"ph": "X", "pid": 1, "tid": 0, "name": "x", "ts": 0.0,
         "dur": -1.0, "args": {"span_id": 1}}]}
    with pytest.raises(ValueError, match="bad dur"):
        validate_chrome_trace(bad)
    with pytest.raises(ValueError, match="unknown ph"):
        validate_chrome_trace({"schema": TRACE_SCHEMA, "traceEvents": [
            {"ph": "B", "pid": 1, "tid": 0, "name": "x"}]})


def test_trace_module_never_imports_jax():
    """The hot-path constraint: obs.trace must stay importable (and
    import-side-effect-free) without jax — span recording on the
    dispatch path may not touch the device stack."""
    code = ("import sys; import repro.obs.trace; "
            "assert 'jax' not in sys.modules, 'obs.trace pulled in jax'; "
            "import repro.obs; "
            "assert 'jax' not in sys.modules, 'repro.obs pulled in jax'")
    subprocess.run([sys.executable, "-c", code], check=True)


# -- tracer x scheduler: the instrumented runtime --------------------------

def test_scheduler_emits_complete_request_chains():
    clock = ManualClock()
    tracer = Tracer(clock=clock)
    cache = FakeCache()
    sched = MicroBatchScheduler(cache, None, clock=clock, tracer=tracer)
    reqs = _reqs(4)
    for r in reqs:
        sched.submit(r)
    sched.step(drain=True)
    sched.finalize()
    assert all(r.status == "completed" for r in reqs)
    assert not tracer.open_spans()
    chains = request_chains(tracer.to_chrome())
    assert set(chains) == {0, 1, 2, 3}
    for c in chains.values():
        assert {"queue"} <= c["children"]
        assert {"dispatch", "device", "finalize"} <= c["member_of"]


def test_retry_opens_fresh_queue_residency_span():
    clock = ManualClock()
    tracer = Tracer(clock=clock)
    cache = FakeCache(call_faults=[ExecutorError("flaky launch")])
    sched = MicroBatchScheduler(cache, None, clock=clock, tracer=tracer,
                                backoff_ms=10.0)
    reqs = _reqs(2, deadline_ms=5.0)
    for r in reqs:
        sched.submit(r)
    clock.advance(0.01)
    sched.step()                       # dispatch fails -> retry parked
    clock.advance(0.02)
    sched.step()
    sched.finalize()
    assert all(r.status == "completed" and r.retries == 1 for r in reqs)
    # one queue residency per stay: original + post-backoff requeue
    for root in tracer.spans("request"):
        qspans = [s for s in tracer.spans("queue")
                  if s.parent_id == root.span_id]
        assert len(qspans) == 2, [s.attrs for s in qspans]
        assert qspans[1].attrs.get("retry") == 1
        assert "retry" in root.event_names()
        assert root.attrs["status"] == "completed"


def test_watchdog_fires_as_trace_events_on_manual_clock():
    clock = ManualClock()
    tracer = Tracer(clock=clock)
    cache = FakeCache()
    sched = MicroBatchScheduler(cache, None, clock=clock, tracer=tracer,
                                watchdog_ms=50.0, backoff_ms=0.0)
    reqs = _reqs(2)
    for r in reqs:
        sched.submit(r)
    sched.step(drain=True)             # in flight, NOT finalized
    clock.advance(0.2)                 # blow the 50 ms watchdog bound
    sched.step(drain=True)             # sweep declares the batch hung
    assert cache.telemetry.counters.get("watchdog_fired") == 1
    dev = [s for s in tracer.spans("device")
           if s.attrs.get("error") == "watchdog"]
    assert len(dev) == 1 and dev[0].finished
    sched.finalize()
    while sched.outstanding():
        sched.step(drain=True)
        sched.finalize()
        clock.advance(0.1)
    assert all(r.status == "completed" for r in reqs)
    for root in tracer.spans("request"):
        assert "watchdog_fired" in root.event_names()
        assert root.attrs["status"] == "completed"
    assert not tracer.open_spans()


def test_async_host_loop_traces_without_span_leaks():
    """start()/stop(): spans record correctly from the background
    thread — every request chain completes, nothing stays open."""
    clock = ManualClock()
    tracer = Tracer(clock=clock)
    cache = FakeCache()
    sched = MicroBatchScheduler(cache, None, clock=clock, tracer=tracer,
                                watchdog_ms=500.0)
    sched.start(poll_s=0.001)
    try:
        reqs = _reqs(8, deadline_ms=5.0)
        for r in reqs:
            sched.submit(r)
        clock.advance(0.05)            # make stragglers due for the loop
        deadline = time.monotonic() + 10.0
        while any(r.status == "pending" for r in reqs):
            assert time.monotonic() < deadline, \
                [(r.rid, r.status) for r in reqs]
            time.sleep(0.002)
    finally:
        sched.stop()
    assert all(r.status == "completed" for r in reqs)
    assert not tracer.open_spans(), \
        [s.name for s in tracer.open_spans()]
    chains = request_chains(tracer.to_chrome())
    assert len(chains) == 8
    for c in chains.values():
        assert {"queue"} <= c["children"]
        assert {"dispatch", "device", "finalize"} <= c["member_of"]


def _within(child, parent):
    return (child.parent_id == parent.span_id
            and parent.start <= child.start
            and child.end_ts <= parent.end_ts)


def test_async_loop_batches_carry_one_copy_launch_and_readback():
    """On the background loop, on the host's own clock: each dispatched
    batch has exactly one ``h2d`` and one ``launch`` inside its
    ``dispatch`` span, one ``readback`` inside its ``device`` span."""
    clock = ManualClock()
    tracer = Tracer()
    sched = MicroBatchScheduler(FakeCache(), None, clock=clock,
                                tracer=tracer)
    sched.start(poll_s=0.001)
    try:
        reqs = _reqs(11, deadline_ms=5.0)
        for r in reqs:
            sched.submit(r)
        clock.advance(0.05)            # flush the ragged tail
        assert sched.wait(reqs, timeout_s=10.0)
    finally:
        sched.stop()
    assert all(r.status == "completed" for r in reqs)
    dispatches, devices = tracer.spans("dispatch"), tracer.spans("device")
    assert len(dispatches) == len(devices) >= 3
    for name, parents in (("h2d", dispatches), ("launch", dispatches),
                          ("readback", devices)):
        kids = tracer.spans(name)
        assert len(kids) == len(parents), name
        for p in parents:
            mine = [k for k in kids if _within(k, p)]
            assert len(mine) == 1, (name, p.attrs)
    for h, d in zip(tracer.spans("h2d"), dispatches):
        assert h.attrs["bucket"] == d.attrs["bucket"]
        assert h.attrs["bytes"] == d.attrs["bucket"] * 32 * 32 * 3 * 4
    assert not tracer.open_spans()


def test_admit_measures_the_scheduler_lock_held_elsewhere():
    """``admit`` opens before ``submit`` takes the lock and ends once it
    holds it: a lock held by another thread for 40 ms reads 40 ms.  The
    request's own span still opens after the lock, as before."""
    clock = ManualClock()
    tracer = Tracer(clock=clock)
    sched = MicroBatchScheduler(FakeCache(), None, clock=clock,
                                tracer=tracer)
    req, = _reqs(1)
    client = threading.Thread(target=sched.submit, args=(req,))
    with sched._lock:
        client.start()
        deadline = time.monotonic() + 10.0
        while not any(s.name == "admit" for s in tracer.open_spans()):
            assert time.monotonic() < deadline
            time.sleep(0.001)
        clock.advance(0.040)
    client.join(timeout=10.0)
    assert not client.is_alive()
    admit, = tracer.spans("admit")
    assert admit.duration == pytest.approx(0.040)
    assert admit.parent_id is None and admit.track == "client"
    assert admit.attrs == {"rid": req.rid}
    request, = [s for s in tracer.open_spans() if s.name == "request"]
    assert request.start == pytest.approx(0.040)
    assert req.arrival == pytest.approx(0.040)


def test_readback_ends_with_the_error_of_a_failed_materialization():
    class Boom:
        def __array__(self, *a, **k):
            raise ExecutorError("materialization fault")

    class BoomCache(FakeCache):
        def get(self, batch, resolution):
            return lambda params, x: Boom()

    tracer = Tracer(clock=ManualClock())
    sched = MicroBatchScheduler(BoomCache(), None, clock=ManualClock(),
                                tracer=tracer, max_retries=0)
    reqs = _reqs(4)
    for r in reqs:
        sched.submit(r)
    sched.step(drain=True)
    sched.finalize()
    assert all(r.status == "failed" for r in reqs)
    rb, = tracer.spans("readback")
    dev, = tracer.spans("device")
    assert rb.attrs["error"] == dev.attrs["error"] == "ExecutorError"
    assert rb.parent_id == dev.span_id
    assert not tracer.open_spans()


def test_request_chains_ignore_the_new_child_and_client_spans():
    """``admit`` (a root without ``rids``) and the batch spans' children
    (``h2d``, ``launch``, ``readback``) leave the per-request chains as
    they were: exactly the queue child and the four batch spans."""
    clock = ManualClock()
    tracer = Tracer(clock=clock)
    sched = MicroBatchScheduler(FakeCache(), None, clock=clock,
                                tracer=tracer)
    reqs = _reqs(6)
    for r in reqs:
        sched.submit(r)
    sched.step(drain=True)
    sched.finalize()
    doc = tracer.to_chrome()
    names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert {"admit", "h2d", "launch", "readback"} <= names
    chains = request_chains(doc)
    assert set(chains) == {r.rid for r in reqs}
    for c in chains.values():
        assert c["children"] == {"queue"}
        assert c["member_of"] == {"form", "dispatch", "device",
                                  "finalize"}
        assert c["events"] == ()


class CountingClock(ManualClock):
    def __init__(self):
        super().__init__()
        self.reads = 0

    def __call__(self) -> float:
        self.reads += 1
        return super().__call__()


@pytest.mark.parametrize("n", [4, 11])
def test_clock_reads_without_a_tracer_are_pinned(n):
    """Without a tracer the scheduler reads its clock once per request
    (arrival), twice per step (expiry sweep, retry requeue) and twice
    per batch (dispatch, finalize), and nothing more.  With a tracer on
    the same clock, every extra read is a span boundary."""
    reads = {}
    for traced in (False, True):
        clock = CountingClock()
        tracer = Tracer(clock=clock) if traced else None
        sched = MicroBatchScheduler(FakeCache(), None, clock=clock,
                                    tracer=tracer)
        for r in _reqs(n):
            sched.submit(r)
        sched.step(drain=True)
        sched.finalize()
        reads[traced] = clock.reads
    batches = len(form_batches(n, (1, 2, 4), due=True))
    assert reads[False] == n + 2 + 2 * batches
    assert reads[True] == reads[False] + 2 * len(tracer.spans())


# -- the async loop waits on the device with the lock released -------------

class HeldOutput:
    """A device output that is not ready until ``gate`` is set; records
    whether the thread that waits on it holds the scheduler's lock."""

    def __init__(self, value, gate, sched, waits):
        self.value, self.gate, self.sched, self.waits = \
            value, gate, sched, waits

    def is_ready(self):
        return self.gate.is_set()

    def block_until_ready(self):
        self.waits.append(self.sched._lock._is_owned())
        assert self.gate.wait(timeout=30.0)
        return self

    def __array__(self, dtype=None, copy=None):
        assert self.gate.wait(timeout=30.0)
        return self.value


class HeldCache(FakeCache):
    """Bucket 4 only; batch k (from 0) answers k on every logit, held
    back by one gate."""

    def __init__(self, gate):
        super().__init__(buckets=(4,))
        self.gate, self.sched, self.waits, self.calls = gate, None, [], 0

    def get(self, batch, resolution):
        def run(params, x):
            out = np.full((int(x.shape[0]), 4), float(self.calls),
                          np.float32)
            self.calls += 1
            return HeldOutput(out, self.gate, self.sched, self.waits)
        return run


def _until(cond, timeout_s=10.0):
    deadline = time.monotonic() + timeout_s
    while not cond():
        assert time.monotonic() < deadline
        time.sleep(0.001)


def test_async_loop_waits_on_the_device_with_the_lock_released():
    """While batch 1 is held on the device, a submit from another thread
    lands at once, and a second full bucket launches behind it
    (``inflight`` 1) before batch 1's readback ends.  Once released,
    every request completes exactly once, in dispatch order."""
    gate = threading.Event()
    cache = HeldCache(gate)
    tracer = Tracer()
    sched = MicroBatchScheduler(cache, None, clock=ManualClock(),
                                tracer=tracer)
    cache.sched = sched
    first, second = _reqs(8)[:4], _reqs(8)[4:]
    sched.start(poll_s=0.001)
    try:
        for r in first:
            sched.submit(r)
        _until(lambda: cache.waits)      # the loop waits on batch 1
        client = threading.Thread(
            target=lambda: [sched.submit(r) for r in second])
        client.start()
        client.join(timeout=1.0)
        assert not client.is_alive(), "submit blocked behind the device"
        assert sched.queue_depth() == 4
        assert sched.step() == 4         # the lock is free to step too
        assert [r.status for r in first + second] == ["pending"] * 8
        launches = tracer.spans("launch")
        assert [s.attrs["inflight"] for s in launches] == [0, 1]
        open_rb = [s for s in tracer.open_spans() if s.name == "readback"]
        assert len(open_rb) == 1
        gate.set()
        assert sched.wait(first + second, timeout_s=10.0)
    finally:
        gate.set()
        sched.stop()
    assert cache.waits and not any(cache.waits)
    rb1 = open_rb[0]
    dev1 = next(s for s in tracer.spans("device")
                if s.span_id == rb1.parent_id)
    assert dev1.attrs["rids"] == [0, 1, 2, 3]
    assert launches[1].end_ts < rb1.end_ts
    for r in first + second:
        assert r.status == "completed"
        np.testing.assert_array_equal(r.logits, [r.rid // 4] * 4)
    assert cache.telemetry.counters["completed"] == 8
    assert cache.telemetry.counters["launch_into_empty"] == 1
    assert [s.attrs["rids"] for s in tracer.spans("finalize")] == \
        [[0, 1, 2, 3], [4, 5, 6, 7]]
    assert [s.attrs["status"] for s in tracer.spans("request")] == \
        ["completed"] * 8
    assert len(tracer.spans("readback")) == 2
    assert not tracer.open_spans(), [s.name for s in tracer.open_spans()]


class FaultyOutput:
    """A device output whose computation failed: waiting and reading
    both raise."""

    def __init__(self, sched, waits):
        self.sched, self.waits = sched, waits

    def block_until_ready(self):
        self.waits.append(self.sched._lock._is_owned())
        raise ExecutorError("materialization fault")

    def __array__(self, dtype=None, copy=None):
        raise ExecutorError("materialization fault")


@pytest.mark.parametrize("traced", [False, True])
def test_async_loop_routes_a_materialization_error_through_retry(traced):
    """The first batch fails when the loop waits on it (lock released)
    and again when it is read (lock held): the failure path retries it
    and it completes.  ``launch_into_empty`` counts the first launch and
    the retry's, not the launch made behind the first batch."""
    cache = FakeCache(buckets=(4,))
    waits = []
    sched = MicroBatchScheduler(cache, None, clock=ManualClock(),
                                backoff_ms=0.0,
                                tracer=Tracer() if traced else None)
    launches = []

    def get(batch, resolution):
        def run(params, x):
            launches.append(len(launches))
            if len(launches) == 1:
                return FaultyOutput(sched, waits)
            return np.full((int(x.shape[0]), 4), 1.0, np.float32)
        return run

    cache.get = get
    reqs = _reqs(8)
    for r in reqs:
        sched.submit(r)                  # both buckets launch in one step
    sched.start(poll_s=0.001)
    try:
        assert sched.wait(reqs, timeout_s=10.0)
    finally:
        sched.stop()
    assert waits == [False]
    assert [r.status for r in reqs] == ["completed"] * 8
    assert [r.retries for r in reqs] == [1] * 4 + [0] * 4
    tel = cache.telemetry.counters
    assert tel["dispatch_failures"] == 1 and tel["retries"] == 4
    assert len(launches) == 3
    assert tel["launch_into_empty"] == 2
    if traced:
        assert [s.attrs["inflight"] for s in
                sched.tracer.spans("launch")] == [0, 1, 0]
        rb = sched.tracer.spans("readback")
        assert rb[0].attrs["error"] == "ExecutorError"
        assert not sched.tracer.open_spans()


class TimedOutput:
    """A device output that becomes ready ``delay_s`` after its launch."""

    def __init__(self, value, delay_s):
        self.value, self.ready_at = value, time.monotonic() + delay_s

    def is_ready(self):
        return time.monotonic() >= self.ready_at

    def block_until_ready(self):
        time.sleep(max(0.0, self.ready_at - time.monotonic()))
        return self

    def __array__(self, dtype=None, copy=None):
        self.block_until_ready()
        return self.value


def test_async_loop_stress_with_concurrent_submits_and_finalize():
    """More submitter threads than cores, a foreground ``finalize()``
    racing the loop for the batch it waits on, and a short switch
    interval: every request completes exactly once with its own answer,
    and every batch has exactly one closed readback."""
    n_threads = (os.cpu_count() or 1) + 2
    per_thread = 12
    cache = FakeCache(buckets=(1, 2, 4))

    def get(batch, resolution):
        def run(params, x):
            x = np.asarray(x)
            return TimedOutput(x[:, 0, 0, :1].copy(),
                               0.0003 * (len(tracer.spans("launch")) % 5))
        return run

    cache.get = get
    tracer = Tracer()
    sched = MicroBatchScheduler(cache, None, clock=ManualClock(),
                                tracer=tracer)
    groups = [[Request(rid=g * per_thread + i, image=np.full(
        (8, 8, 3), g * per_thread + i, np.float32))
        for i in range(per_thread)] for g in range(n_threads)]
    flat = [r for g in groups for r in g]
    switch, hook = sys.getswitchinterval(), threading.excepthook
    died = []
    threading.excepthook = lambda args: died.append(args.exc_value)
    sys.setswitchinterval(1e-5)
    stop = threading.Event()

    def finalizer():
        while not stop.is_set():
            sched.finalize()
            time.sleep(0.002)

    try:
        sched.start(poll_s=0.001)
        threads = [threading.Thread(
            target=lambda g=g: [sched.submit(r) for r in g])
            for g in groups] + [threading.Thread(target=finalizer)]
        for t in threads:
            t.start()
        for t in threads[:-1]:
            t.join(timeout=60.0)
            assert not t.is_alive()
        # the ragged tail never comes due on a manual clock: stop drains it
        stop.set()
        threads[-1].join(timeout=60.0)
        assert not threads[-1].is_alive()
        sched.stop(drain=True)
    finally:
        stop.set()
        sys.setswitchinterval(switch)
        threading.excepthook = hook
        sched.stop()
    assert not died, died               # the loop thread never raised
    for r in flat:
        assert r.status == "completed", (r.rid, r.status)
        np.testing.assert_array_equal(r.logits, [r.rid])
    assert cache.telemetry.counters["completed"] == len(flat)
    devices = tracer.spans("device")
    readbacks = tracer.spans("readback")
    assert sum(len(d.attrs["rids"]) for d in devices) == len(flat)
    assert sorted(rb.parent_id for rb in readbacks) == \
        sorted(d.span_id for d in devices)
    assert not tracer.open_spans(), [s.name for s in tracer.open_spans()]


# -- drift report math on a scripted timer ---------------------------------

def test_drift_report_math_scripted_timer():
    jax = pytest.importorskip("jax")
    from repro.core.efficientvit import B1_SMOKE
    from repro.core.program import lower
    from repro.obs.profile import SiteProfiler, drift_report

    program = lower(B1_SMOKE, batch=1, image_size=32)
    ticks = iter(x * 1e-3 for x in range(10_000))
    prof = SiteProfiler(clock=lambda: next(ticks), sync=lambda out: out)
    for _ in range(2):                     # two scripted repeats
        for site in program.sites:
            prof.begin(site)
            prof.end(site, out=None)
    assert prof.repeats == 2
    # each begin->end spans exactly one 1 ms tick
    rep = drift_report(program, prof, plan=None, precision="fp")
    assert rep.precision == "fp" and rep.repeats == 2
    assert len(rep.rows) == len(program.sites)
    assert rep.finite()
    for r in rep.rows:
        assert r["measured_ms"] == pytest.approx(1.0)
        assert r["predicted_cycles"] > 0
        assert r["drift"] == pytest.approx(
            r["measured_ms"] / r["predicted_ms"])
    # the zero-MAC gap site is charged its memory-bound boundary floor
    gap = rep.row("head.gap")
    assert gap["predicted_ms"] > 0
    assert rep.drift == pytest.approx(
        rep.measured_ms / rep.predicted_ms)
    doc = rep.to_dict()
    json.dumps(doc)                        # ledger-ready
    assert doc["rows"][0]["site"] == program.sites[0].name
    # partial profiles refuse to reconcile
    with pytest.raises(KeyError):
        drift_report(program, SiteProfiler(), plan=None)


# -- metrics registry ------------------------------------------------------

def test_prometheus_escaping_and_text_format():
    assert escape_label('a\\b"c\nd') == 'a\\\\b\\"c\\nd'
    reg = MetricsRegistry(namespace="repro")
    reg.counter("req", "requests").inc(3, route='vis"ion\n', mesh="a\\b")
    text = reg.prometheus_text()
    assert '# TYPE repro_req counter' in text
    assert 'route="vis\\"ion\\n"' in text
    assert 'mesh="a\\\\b"' in text
    assert text.endswith("\n")


def test_histogram_cumulative_buckets_text():
    reg = MetricsRegistry()
    h = reg.histogram("build_s", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    text = reg.prometheus_text()
    assert 'repro_build_s_bucket{le="0.1"} 1' in text
    assert 'repro_build_s_bucket{le="1"} 2' in text
    assert 'repro_build_s_bucket{le="+Inf"} 3' in text
    assert 'repro_build_s_sum 5.55' in text
    assert 'repro_build_s_count 3' in text


def test_registry_renders_telemetry_with_p99():
    tel = Telemetry()
    tel.record_dispatch((4, 32, "auto"), 3, 4, queue_depth=2,
                        wait_ms=[1.0, 2.0, 3.0])
    tel.record_latency((4, 32, "auto"), [10.0, 20.0])
    tel.count("completed", 3)
    reg = MetricsRegistry(telemetry=tel)
    text = reg.prometheus_text()
    assert "repro_completed_total 3" in text
    assert ('repro_bucket_samples_total{bucket="4",precision="auto",'
            'resolution="32"} 3') in text
    assert 'quantile="0.99"' in text
    doc = reg.to_json()
    json.dumps(doc)
    names = {f["name"] for f in doc["families"]}
    assert {"repro_bucket_occupancy", "repro_bucket_wait_ms",
            "repro_bucket_latency_ms"} <= names


def test_telemetry_table_renders_dash_for_empty_series():
    tel = Telemetry()
    tel.record_dispatch((4, 32, "auto"), 4, 4)   # no waits, no latencies
    table = tel.table()
    assert "p50/p95/p99" in table
    row = next(line for line in table.splitlines() if "4x32xauto" in line)
    assert "-/-/-" in row
    assert "nan" not in table.lower()


# -- benchmark ledger ------------------------------------------------------

def test_ledger_round_trip(tmp_path):
    doc = bench_result(
        "kernel_bench",
        config={"backend": "cpu"},
        metrics={"max_err": np.float32(1e-3), "shape": (2, 3),
                 "bad": float("nan")},
        gates={"err": True})
    assert doc["schema"] == BENCH_SCHEMA
    assert doc["metrics"]["max_err"] == pytest.approx(1e-3)
    assert doc["metrics"]["shape"] == [2, 3]       # tuples -> lists
    assert doc["metrics"]["bad"] is None           # NaN -> null
    path = tmp_path / "BENCH_X.json"
    write_result(str(path), doc)
    assert load_result(str(path)) == doc
    assert json.loads(path.read_text())["name"] == "kernel_bench"


def test_ledger_rejects_malformed():
    with pytest.raises(ValueError, match="unknown benchmark"):
        bench_result("nonsense_bench")
    good = bench_result("e2e_latency")
    bad = dict(good, schema=99)
    with pytest.raises(ValueError, match="schema"):
        validate_result(bad)
    bad = dict(good, gates={"g": "yes"})
    with pytest.raises(ValueError, match="not a bool"):
        validate_result(bad)
    bad = dict(good)
    del bad["metrics"]
    with pytest.raises(ValueError, match="metrics"):
        validate_result(bad)


def test_ledger_fixture_is_valid():
    """The committed serving_bench smoke fixture stays loadable and
    self-judging (every gate green)."""
    import os
    path = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "ledger", "BENCH_SMOKE.json")
    doc = load_result(path)
    assert doc["name"] == "serving_bench"
    assert doc["gates"] and all(doc["gates"].values()), doc["gates"]
    assert doc["metrics"]["trace"]["fp"]["chains"] \
        == doc["config"]["n_requests"]


def test_plan_span_and_counters_say_which_sites_ran_which_path():
    """A tiny L-series executor's ``plan`` span counts fused and
    reference-path sites by kind and names each demotion with its
    reason; ``Telemetry`` counts the same, by kind and reason.  A
    ``demote=`` ladder step (``degrade(site=...)``) shows in both."""
    import jax

    from repro.core.efficientvit import L_SMOKE, init_efficientvit
    from repro.serving.executors import ExecutorCache

    params = init_efficientvit(jax.random.PRNGKey(0), L_SMOKE)
    tracer = Tracer()
    cache = ExecutorCache(params, L_SMOKE, buckets=(2,), autotune=False,
                          tracer=tracer)
    cache.get(2, 64)
    cache.degrade(2, 64, site="S1.fmb0")
    cache.get(2, 64)
    healthy, demoted = tracer.spans("plan")
    assert healthy.attrs == {"reused_donor": False, "fused_resblock": 1,
                             "fused_fmbconv": 4, "fused_mbconv": 4,
                             "fused_msa": 1, "demoted": ""}
    assert demoted.attrs["fused_fmbconv"] == 3
    assert demoted.attrs["ref_fmbconv"] == 1
    assert demoted.attrs["demoted"] == "S1.fmb0:fault"
    c = cache.telemetry.counters
    assert c["plan_sites_fused.resblock"] == 1 + 1
    assert c["plan_sites_fused.fmbconv"] == 4 + 3
    assert c["plan_sites_fused.mbconv"] == 4 + 4
    assert c["plan_sites_fused.msa"] == 2
    assert c["plan_sites_ref.fmbconv.fault"] == 1
    assert not any(k.startswith("plan_sites_ref.mbconv") for k in c)
