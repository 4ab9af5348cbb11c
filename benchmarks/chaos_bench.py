"""Chaos replay: every fault class injected against the serving runtime.

Each scenario arms a ``serving.faults.FaultPlan`` at one injection
point, replays a small request trace on a manual clock through the real
runtime (``ExecutorCache`` + ``MicroBatchScheduler`` on ``B1_SMOKE``),
and asserts the designed response — not merely "no crash":

    control             no faults armed: zero shed / retries / degrade,
                        fp logits match the unbatched reference
    compile.transient   one executor build crash; the failure is
                        negative-cached (probed within TTL), the retry
                        after TTL rebuilds healthy — no degradation
    autotune            one sweep crash; PlanError blames the site, the
                        ladder demotes exactly that site (reason
                        "fault") and traffic completes on the level-1
                        plan
    kernel.launch       a persistently failing fused launch; the ladder
                        demotes the blamed site, then bottoms out on
                        the reference interpreter — whose output is
                        bit-identical to ``execute(plan=None)``
    epilogue.numerics   silent NaN corruption of int8 output; finalize
                        detects it, pins the bucket to fp, and the
                        pinned plan's logits are bit-identical to the
                        reference interpreter on the same batch
    queue.overload      admission bound + injected overload: excess
                        requests shed with ``CapacityExceeded``, the
                        admitted ones complete
    deadline            hard ``timeout_ms`` expiry in queue: expired
                        requests shed with ``DeadlineExceeded`` before
                        occupying a batch slot, live ones complete
    device.dropout      (>= 2 devices) one mesh device dies mid-trace:
                        the mesh shrinks and replans around it, the
                        trace completes on the survivors, the ladder
                        does not move; total loss of every device fails
                        the trace typed ``MeshExhausted`` with no hang

Global invariants, checked over every scenario:
  * every submitted request terminates in exactly ONE of
    {completed, shed, failed}; none lost, none duplicated;
  * shed requests carry a typed error (DeadlineExceeded /
    CapacityExceeded), completed ones carry finite logits;
  * every fault class fired at least once and every budget is spent
    (``FaultPlan.exhausted``) — the chaos schedule provably ran.

Every scenario runs with an ``obs.trace.Tracer`` threaded through the
runtime, and the ladder scenarios additionally assert their designed
response is *visible in the trace*: fault demotion, the walk down to
the reference interpreter and the fp pin each appear as span events on
the affected requests' spans with the blamed site attributed, next to
the ``fault.injected`` marks that caused them.

    PYTHONPATH=src python -m benchmarks.chaos_bench [--smoke]
        [--json OUT]            machine-readable result ledger
                                (repro.obs.ledger, BENCH_SCHEMA)
"""
from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.errors import (
    CapacityExceeded, DeadlineExceeded, ExecutorError, ReproError)
from repro.core.efficientvit import B1_SMOKE, init_efficientvit
from repro.core.program import execute, lower
from repro.core.quantization import quantize_efficientvit
from repro.obs import Tracer, bench_result, flag_value, write_result
from repro.serving.executors import ExecutorCache
from repro.serving.faults import FaultPlan, FaultSpec
from repro.serving.scheduler import ManualClock, MicroBatchScheduler, Request
from repro.serving.telemetry import Telemetry

BUCKETS = (1, 2, 4)
RES = 32


def make_requests(n, res=RES, seed=0, **kw):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, image=rng.standard_normal(
        (res, res, 3)).astype(np.float32), **kw) for i in range(n)]


def runtime(params, *, precision="auto", faults=None, clock=None,
            neg_ttl_s=1.0, devices=None, **sched_kw):
    """(telemetry, cache, scheduler, clock) sharing one manual clock.

    Every scenario runs traced: a ``Tracer`` on the same virtual clock
    threads through the cache, the scheduler and the fault plan, so the
    ladder scenarios can assert their response shows up as span events
    (retrieve it as ``sched.tracer``)."""
    clock = clock if clock is not None else ManualClock()
    tel = Telemetry()
    tracer = Tracer(clock=clock)
    if faults is not None and faults.tracer is None:
        faults.tracer = tracer
    cache = ExecutorCache(params, B1_SMOKE, buckets=BUCKETS,
                          precision=precision, autotune=False,
                          telemetry=tel, faults=faults,
                          neg_ttl_s=neg_ttl_s, clock=clock,
                          devices=devices, tracer=tracer)
    sched = MicroBatchScheduler(cache, params, telemetry=tel, clock=clock,
                                faults=faults, tracer=tracer, **sched_kw)
    return tel, cache, sched, clock


def span_events(sched, name):
    """Attrs of every ``name`` event across the trace's request spans
    (finished or open), submit order."""
    spans = sched.tracer.spans("request") + [
        s for s in sched.tracer.open_spans() if s.name == "request"]
    return [attrs for s in spans for _ts, n, attrs in s.events
            if n == name]


def drain(sched, clock, max_rounds=64, tick_s=0.05):
    """Step/finalize until every request is terminal; the clock ticks
    between rounds so backoff windows and negative-cache TTLs expire."""
    for _ in range(max_rounds):
        if not sched.outstanding():
            return
        sched.step(drain=True)
        sched.finalize()
        clock.advance(tick_s)
    raise AssertionError(
        f"scheduler failed to drain: {sched.outstanding()} outstanding")


def probe_vs_reference(cache, params, bucket, res, seed=99):
    """Bitwise gate: the (possibly degraded) executor's output vs the
    jitted reference interpreter (plan=None) on the SAME batch."""
    ex = cache.get(bucket, res)
    x = jnp.asarray(np.random.default_rng(seed).standard_normal(
        (bucket, res, res, 3)).astype(np.float32))
    got = np.asarray(ex(params, x))
    program = lower(B1_SMOKE, batch=bucket, image_size=res)
    ref = np.asarray(jax.jit(
        lambda p, v: execute(program, p, v, plan=None))(params, x))
    return got, ref


def check_partition(name, reqs):
    """The no-lost / no-duplicated / exactly-one-terminal-state gate."""
    states = {"completed": 0, "shed": 0, "failed": 0}
    assert len({r.rid for r in reqs}) == len(reqs), f"{name}: rid collision"
    for r in reqs:
        assert r.status in states, \
            f"{name}: request {r.rid} non-terminal ({r.status})"
        states[r.status] += 1
        if r.status == "completed":
            assert r.logits is not None and np.all(np.isfinite(r.logits)), \
                f"{name}: request {r.rid} completed without finite logits"
            assert r.error is None or r.retries, (name, r.rid)
        else:
            assert isinstance(r.error, ReproError), \
                f"{name}: {r.status} request {r.rid} lacks a typed error"
    assert sum(states.values()) == len(reqs)
    return states


# -- scenarios -------------------------------------------------------------

def scenario_control(params, n):
    faults = FaultPlan()          # idle plan: must alter nothing
    tel, cache, sched, clock = runtime(params, faults=faults)
    reqs = make_requests(n, deadline_ms=10.0)
    for r in reqs:
        sched.submit(r)
        clock.advance(0.002)
        sched.step()
    drain(sched, clock)
    for c in ("shed", "failed", "retries", "degraded", "pinned_fp",
              "dispatch_failures"):
        assert tel.counters.get(c, 0) == 0, (c, tel.counters)
    # fp parity vs the unbatched eager reference
    for r in reqs:
        prog = lower(B1_SMOKE, batch=1, image_size=RES)
        ref = np.asarray(execute(prog, params, r.image[None]))[0]
        err = float(np.max(np.abs(r.logits - ref)))
        assert err < 1e-3, (r.rid, err)
    return dict(name="control", point="(none)", faults=faults, tel=tel,
                reqs=reqs, note="no-fault replay unchanged; fp parity ok")


def scenario_compile_transient(params, n):
    faults = FaultPlan(FaultSpec("executor.compile", times=1,
                                 note="transient serve-time compile crash"))
    tel, cache, sched, clock = runtime(params, faults=faults,
                                       neg_ttl_s=0.5)
    reqs = make_requests(n)
    for r in reqs:
        sched.submit(r)
    sched.step(drain=True)        # first dispatch: build fails, parks retry
    assert tel.counters.get("executor_build_failed") == 1
    # probe the negative cache within TTL: typed error, no rebuild
    try:
        cache.get(BUCKETS[-1], RES)
        raise AssertionError("negative cache failed to answer")
    except ExecutorError:
        pass
    assert tel.counters.get("negative_cache_hit") == 1
    assert tel.counters.get("executor_build_failed") == 1   # no 2nd build
    clock.advance(0.6)            # past TTL + past backoff
    sched.step()
    sched.finalize()
    drain(sched, clock)
    states = check_partition("compile_transient", reqs)
    assert states["completed"] == n, states
    assert tel.counters.get("retries", 0) >= 1
    assert cache.degradation(BUCKETS[-1], RES) is None, \
        "transient failure must not move the ladder"
    return dict(name="compile_transient", point="executor.compile",
                faults=faults, tel=tel, reqs=reqs,
                note="neg-cached, retried after TTL, no degradation")


def scenario_autotune(params, n):
    faults = FaultPlan(FaultSpec("autotune", times=1,
                                 note="crashed block-size sweep"))
    tel, cache, sched, clock = runtime(params, faults=faults)
    reqs = make_requests(n)
    with faults:                  # hook the autotuner
        for r in reqs:
            sched.submit(r)
        drain(sched, clock)
    states = check_partition("autotune", reqs)
    assert states["completed"] == n, states
    state = cache.degradation(BUCKETS[-1], RES)
    assert state is not None and state.level == 1 and state.demoted, state
    site = next(iter(state.demoted))
    ex = cache.get(BUCKETS[-1], RES)
    d = ex.plan.decisions[site]
    assert not d.fused and d.reason == "fault", (site, d)
    # the transition is in the trace: the failed group's request spans
    # carry a "degrade" event blaming exactly the demoted site, next to
    # the injector's "fault.injected" mark
    ev = span_events(sched, "degrade")
    assert ev and all(e["site"] == site and e["level"] == 1
                      for e in ev), ev
    assert sched.tracer.spans("fault.injected"), "injection left no mark"
    return dict(name="autotune_fault", point="autotune", faults=faults,
                tel=tel, reqs=reqs,
                note=f"PlanError blamed {site}; demoted (reason=fault), "
                     f"rest of the plan stays fused")


def scenario_launch(params, n):
    # discover a real fused site to blame, on a clean runtime
    probe = ExecutorCache(params, B1_SMOKE, buckets=BUCKETS,
                          autotune=False, telemetry=Telemetry())
    site = probe.get(BUCKETS[-1], RES).fused_sites[0]
    # 3 failures walk the full ladder: retry same -> demote site ->
    # reference interpreter (level 2, no fused sites left to fault)
    faults = FaultPlan(FaultSpec("kernel.launch", times=3, site=site,
                                 note="persistent fused-launch failure"))
    tel, cache, sched, clock = runtime(params, faults=faults)
    reqs = make_requests(n)
    for r in reqs:
        sched.submit(r)
    drain(sched, clock)
    states = check_partition("kernel_launch", reqs)
    assert states["completed"] == n, states
    state = cache.degradation(BUCKETS[-1], RES)
    assert state is not None and state.level == 2, state
    ex = cache.get(BUCKETS[-1], RES)
    assert ex.plan is None and not ex.fused_sites
    got, ref = probe_vs_reference(cache, params, BUCKETS[-1], RES)
    assert np.array_equal(got, ref), \
        "level-2 executor must be the reference interpreter, bit-exact"
    # the full ladder walk is in the trace: a traced retry for the
    # transient first attempt, then "degrade" events at level 1 (the
    # blamed site demoted) and level 2 (reference interpreter)
    assert span_events(sched, "retry"), \
        "attempt 1 must park a traced retry"
    ev = span_events(sched, "degrade")
    assert sorted({e["level"] for e in ev}) == [1, 2], ev
    assert any(e["level"] == 1 and e["site"] == site for e in ev), ev
    return dict(name="launch_fault", point="kernel.launch", faults=faults,
                tel=tel, reqs=reqs,
                note=f"ladder: fused -> {site} demoted -> reference "
                     f"interpreter (bit-exact vs plan=None)")


def scenario_numerics(qparams, n):
    faults = FaultPlan(FaultSpec("epilogue.numerics", times=1,
                                 note="silent int8 epilogue blow-up"))
    tel, cache, sched, clock = runtime(qparams, precision="int8",
                                       faults=faults)
    reqs = make_requests(n)
    for r in reqs:
        sched.submit(r)
    drain(sched, clock)
    states = check_partition("numerics", reqs)
    assert states["completed"] == n, states
    state = cache.degradation(BUCKETS[-1], RES)
    assert state is not None and state.pinned_fp, state
    assert tel.counters.get("pinned_fp") == 1
    got, ref = probe_vs_reference(cache, qparams, BUCKETS[-1], RES)
    assert np.array_equal(got, ref), \
        "fp-pinned executor must match the reference interpreter bit-exact"
    # the pin is in the trace: finalize's NaN guard stamps "pin_fp" on
    # the corrupted batch's request spans (site attributed — None here:
    # a silent epilogue blow-up blames no single site)
    ev = span_events(sched, "pin_fp")
    assert ev and all(e["error"] == "NumericsError" and "site" in e
                      for e in ev), ev
    return dict(name="numerics_int8", point="epilogue.numerics",
                faults=faults, tel=tel, reqs=reqs,
                note="NaN caught at finalize; bucket pinned to fp "
                     "(bit-exact vs reference); served batch finite")


def scenario_overload(params, n):
    faults = FaultPlan(FaultSpec("queue.overload", times=1,
                                 note="load spike beyond the bound"))
    depth = max(2, n // 2)
    tel, cache, sched, clock = runtime(params, faults=faults,
                                       max_queue_depth=depth)
    reqs = make_requests(n)
    admitted = sum(sched.submit(r) for r in reqs)
    drain(sched, clock)
    states = check_partition("overload", reqs)
    assert states["shed"] == n - admitted and states["shed"] >= 2, states
    assert states["completed"] == admitted, states
    shed = [r for r in reqs if r.status == "shed"]
    assert all(isinstance(r.error, CapacityExceeded) for r in shed)
    assert tel.counters.get("shed_capacity") == len(shed)
    return dict(name="overload_shed", point="queue.overload", faults=faults,
                tel=tel, reqs=reqs,
                note=f"bound {depth}: {len(shed)} shed typed "
                     f"CapacityExceeded, {admitted} served")


def scenario_deadline(params, n):
    faults = FaultPlan()
    tel, cache, sched, clock = runtime(params, faults=faults)
    # the early half of the trace carries a 5 ms hard SLA and sits
    # queued past it (too few to fill a bucket, no soft deadline to
    # flush them); the late half arrives with headroom and must be
    # served
    tight = make_requests(min(n // 2, BUCKETS[-1] - 1), timeout_ms=5.0)
    loose = make_requests(n - len(tight), seed=7, timeout_ms=10_000.0)
    for r in loose:
        r.rid += 1000
    for r in tight:
        sched.submit(r)
        sched.step()              # not due, bucket not full: queued
    clock.advance(0.05)           # blow the 5 ms SLA while queued
    sched.step()                  # sweep happens BEFORE batch formation
    for r in loose:
        sched.submit(r)
    drain(sched, clock)
    states = check_partition("deadline", tight + loose)
    assert all(r.status == "shed" and isinstance(r.error, DeadlineExceeded)
               for r in tight), [(r.rid, r.status) for r in tight]
    assert all(r.status == "completed" for r in loose)
    assert tel.counters.get("shed_deadline") == len(tight)
    return dict(name="deadline_shed", point="(timeout_ms)", faults=faults,
                tel=tel, reqs=tight + loose,
                note=f"{len(tight)} expired in queue, shed typed "
                     f"DeadlineExceeded without occupying a slot")


def scenario_device_dropout(params, n):
    """One device dies mid-trace: the mesh shrinks around it, the trace
    completes on the survivors, and post-failover occupancy recovers —
    the degradation ladder does NOT move (replanning on the smaller
    mesh IS the recovery)."""
    devices = tuple(jax.devices())
    victim = devices[-1].id
    faults = FaultPlan(FaultSpec("device.dropout", times=1, device=victim,
                                 note="device died mid-trace"))
    tel, cache, sched, clock = runtime(params, faults=faults,
                                       devices=devices, backoff_ms=0.0)
    reqs = make_requests(n)
    for r in reqs:
        sched.submit(r)
    drain(sched, clock)
    states = check_partition("device_dropout", reqs)
    assert states["completed"] == n, states
    assert cache.health.dead_ids() == (victim,), cache.health.dead_ids()
    assert cache.degradation(BUCKETS[-1], RES) is None, \
        "device loss must not move the degradation ladder"
    assert tel.counters.get("device_lost") == 1
    assert tel.counters.get("mesh_shrunk") == 1
    assert tel.devices[victim].lost
    # occupancy recovers: a post-failover wave serves entirely on the
    # survivors, full slots, no further faults
    before = {d.id: tel.devices[d.id].samples for d in devices
              if d.id in tel.devices and d.id != victim}
    more = make_requests(n, seed=5)
    for r in more:
        r.rid += 2000
        sched.submit(r)
    drain(sched, clock)
    check_partition("device_dropout/recovery", more)
    assert all(r.status == "completed" for r in more)
    gained = [did for did, s in before.items()
              if tel.devices[did].samples > s]
    assert gained, "survivors served no post-failover traffic"
    # fp parity vs the unbatched eager reference survives the failover
    prog = lower(B1_SMOKE, batch=1, image_size=RES)
    for r in more[:2]:
        ref = np.asarray(execute(prog, params, r.image[None]))[0]
        err = float(np.max(np.abs(r.logits - ref)))
        assert err < 1e-3, (r.rid, err)
    return dict(name="device_dropout", point="device.dropout",
                faults=faults, tel=tel, reqs=reqs + more,
                note=f"dev{victim} lost; mesh "
                     f"{len(devices)}->{cache.health.n_alive}; trace + "
                     f"recovery wave completed on survivors, ladder idle")


def scenario_mesh_loss(params, n):
    """Every device dies: requests terminate failed with a typed
    ``MeshExhausted`` — a clean shed-everything, provably no hang."""
    from repro.common.errors import MeshExhausted
    devices = tuple(jax.devices())
    faults = FaultPlan(*[FaultSpec("device.dropout", times=1, device=d.id,
                                   note="total mesh loss")
                         for d in devices])
    tel, cache, sched, clock = runtime(params, faults=faults,
                                       devices=devices, backoff_ms=0.0)
    reqs = make_requests(n)
    for r in reqs:
        sched.submit(r)
    drain(sched, clock)           # must terminate — drain itself is the
    #                               no-hang gate (bounded rounds)
    states = check_partition("mesh_loss", reqs)
    assert states["failed"] == n, states
    assert all(isinstance(r.error, MeshExhausted) for r in reqs)
    assert cache.mesh_exhausted and cache.health.n_alive == 0
    # a straggler after total loss fails fast on the typed error too
    late = make_requests(1, seed=9)[0]
    late.rid = 9999
    sched.submit(late)
    drain(sched, clock)
    assert late.status == "failed" and isinstance(late.error, MeshExhausted)
    return dict(name="mesh_loss", point="device.dropout", faults=faults,
                tel=tel, reqs=reqs + [late],
                note=f"all {len(devices)} devices lost; {n}+1 requests "
                     f"failed typed MeshExhausted, scheduler drained clean")


# -- driver ----------------------------------------------------------------

def run(smoke: bool = False, json_out: str | None = None):
    n = 4 if smoke else 8
    params = init_efficientvit(jax.random.PRNGKey(0), B1_SMOKE)
    qparams = quantize_efficientvit(params)

    multi_device = len(jax.devices()) >= 2
    print(f"# chaos bench — {B1_SMOKE.name} @ {RES}px, buckets {BUCKETS}, "
          f"{n} requests/scenario, manual clock, "
          f"{len(jax.devices())} device(s)")
    results = [
        scenario_control(params, n),
        scenario_compile_transient(params, n),
        scenario_autotune(params, n),
        scenario_launch(params, n),
        scenario_numerics(qparams, n),
        scenario_overload(params, n + 2),
        scenario_deadline(params, n),
    ]
    if multi_device:
        results += [
            scenario_device_dropout(params, n),
            scenario_mesh_loss(params, n),
        ]
    else:
        print("(single device: device.dropout scenarios skipped — run "
              "under XLA_FLAGS=--xla_force_host_platform_device_count=N)")

    head = (f"{'scenario':<18} {'fault point':<18} {'inj':>3} "
            f"{'done':>4} {'shed':>4} {'fail':>4}  outcome")
    print("\n## fault matrix")
    print(head)
    print("-" * len(head))
    injected_points = set()
    matrix = {}
    for r in results:
        states = check_partition(r["name"], r["reqs"])
        fired = sum(r["faults"].fired.values())
        injected_points.update(r["faults"].fired)
        assert r["faults"].exhausted, \
            (r["name"], "unspent fault budget", r["faults"].specs)
        matrix[r["name"]] = dict(point=r["point"], injected=fired,
                                 note=r["note"], **states)
        print(f"{r['name']:<18} {r['point']:<18} {fired:>3} "
              f"{states['completed']:>4} {states['shed']:>4} "
              f"{states['failed']:>4}  {r['note']}")

    from repro.serving.faults import FAULT_POINTS
    required = set(FAULT_POINTS)
    if not multi_device:
        required -= {"device.dropout"}   # needs >= 2 devices to shrink
    missing = required - injected_points
    assert not missing, f"fault classes never injected: {missing}"
    total = sum(len(r["reqs"]) for r in results)
    print(f"\nall {total} requests across {len(results)} scenarios "
          f"terminated in exactly one of completed/shed/failed; "
          f"all {len(required)} required fault classes injected; "
          f"every fault budget spent")
    if json_out is not None:
        doc = bench_result(
            "chaos_bench",
            config=dict(smoke=smoke, cfg=B1_SMOKE.name, resolution=RES,
                        buckets=list(BUCKETS), n_per_scenario=n,
                        n_devices=len(jax.devices())),
            metrics=dict(scenarios=matrix, total_requests=total,
                         injected_points=sorted(injected_points)),
            gates=dict(
                partition_exact=True,          # asserted per scenario
                all_fault_classes_injected=not missing,
                budgets_spent=all(r["faults"].exhausted for r in results),
                ladder_events_traced=True))    # asserted in scenarios
        write_result(json_out, doc)
        print(f"ledger written to {json_out}")
    return results


def main():
    from repro.common.compile_cache import use_compile_cache
    use_compile_cache()
    argv = sys.argv[1:]
    run(smoke="--smoke" in argv, json_out=flag_value(argv, "--json"))


if __name__ == "__main__":
    main()
