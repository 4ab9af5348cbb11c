"""Mixed-trace serving benchmark: shape-bucketed continuous
micro-batching, fp32 and FIX8 int8.

A synthetic request trace (Poisson-ish arrivals, mixed resolutions) is
replayed once per precision through the serving runtime
(``serving.executors`` + ``serving.scheduler``): batch formation groups
same-resolution requests into the largest ready bucket and flushes due
tails to the smallest bucket that fits, so pad waste only ever appears
inside the smallest covering bucket.

Replay runs on a manual clock (deterministic queue/deadline behavior);
wall clock is measured around the dispatch+finalize work for a
throughput figure (CPU interpret mode: a consistency check, not a TPU
number — occupancy and pad waste are the backend-independent story).

Asserts (CI smoke gate, ``--smoke``):
  * fp logits agree with the unbatched reference forward (1e-3);
  * executor-cache key-set drift gate: the smoke replay compiles
    exactly ``EXPECTED_SMOKE_KEYS`` — a scheduler or bucket-policy
    change that alters the compiled working set must update the
    expectation here explicitly;
  * every completed request left a complete span chain.

    PYTHONPATH=src python -m benchmarks.serving_bench [--smoke]
        [--record-trace PATH]   export the replayed trace as JSON (the
                                offline schedule search's input)
        [--trace PATH]          replay a recorded trace instead of
                                synthesizing one
        [--trace-json PATH]     export the fp replay's request
                                timeline as Chrome trace JSON (Perfetto)
        [--json OUT]            machine-readable result ledger
                                (repro.obs.ledger, BENCH_SCHEMA)
"""
from __future__ import annotations

import sys
import time

import jax
import numpy as np

from repro.core.efficientvit import B1_SMOKE, init_efficientvit
from repro.core.program import execute, lower
from repro.core.quantization import quantize_efficientvit
from repro.obs import (
    Tracer, bench_result, flag_value, request_chains,
    validate_chrome_trace, write_result)
from repro.serving.executors import ExecutorCache
from repro.serving.scheduler import (
    ManualClock, MicroBatchScheduler, Request)
from repro.serving.telemetry import Telemetry

# Drift gate: the (batch bucket, resolution) executors the smoke replay
# actually dispatches to.  12 requests over {32, 64}px with
# buckets (1, 2, 4): full 4-buckets for the steady groups, a 1-bucket
# only for the drained tail.  If batch formation changes, this set
# moves — update it HERE, deliberately, alongside the scheduler change.
EXPECTED_SMOKE_KEYS = {(4, 32), (4, 64), (1, 64)}

SMOKE = dict(n_requests=12, resolutions=(32, 64), res_weights=(0.5, 0.5),
             buckets=(1, 2, 4), mean_gap_ms=2.0, deadline_ms=40.0)
FULL = dict(n_requests=32, resolutions=(32, 64, 96),
            res_weights=(0.3, 0.5, 0.2), buckets=(1, 2, 4, 8),
            mean_gap_ms=2.0, deadline_ms=40.0)


def make_trace(spec: dict, seed: int = 0):
    """[(arrival_s, resolution)] — exponential gaps, weighted sizes."""
    rng = np.random.default_rng(seed)
    t = 0.0
    trace = []
    for _ in range(spec["n_requests"]):
        t += rng.exponential(spec["mean_gap_ms"] / 1e3)
        res = int(rng.choice(spec["resolutions"], p=spec["res_weights"]))
        trace.append((t, res))
    return trace


def make_images(trace, seed: int = 1):
    rng = np.random.default_rng(seed)
    return [rng.standard_normal((res, res, 3)).astype(np.float32)
            for _, res in trace]


def replay(params, spec, trace, images, *, precision: str = "auto",
           devices=None, cfg=B1_SMOKE, autotune: bool = False,
           artifact=None, with_tracer: bool = False):
    """One replay at one precision; returns (telemetry, logits, wall_s,
    cache).  ``devices`` shards every dispatch's batch axis across that
    mesh (``serving.sharding``); ``artifact`` adopts an offline-searched
    ``repro.search.ScheduleArtifact`` (buckets + pinned plans, zero
    autotune sweeps).  ``with_tracer`` threads an ``obs.trace.Tracer``
    on the replay's virtual clock through the cache and scheduler
    (retrieve it as ``cache.tracer``)."""
    tel = Telemetry()
    clock = ManualClock()
    tracer = Tracer(clock=clock) if with_tracer else None
    cache = ExecutorCache(params, cfg, buckets=spec["buckets"],
                          precision=precision, autotune=autotune,
                          telemetry=tel, devices=devices,
                          artifact=artifact, tracer=tracer)
    sched = MicroBatchScheduler(cache, params, telemetry=tel, clock=clock,
                                tracer=tracer)
    reqs = [Request(rid=i, image=img, deadline_ms=spec["deadline_ms"])
            for i, img in enumerate(images)]
    # warm the compiled working set outside the timed window, like a
    # serving engine warming up before traffic — CPU-interpret compile
    # stalls would otherwise dominate the replay wall clock
    cache.warmup(spec["resolutions"])
    t0 = time.perf_counter()
    for (at, _), req in zip(trace, reqs):
        clock.advance_to(at)
        sched.submit(req)
        sched.step()
    clock.advance(spec["deadline_ms"] / 1e3)   # let stragglers come due
    sched.step()
    sched.step(drain=True)
    sched.finalize()
    wall = time.perf_counter() - t0
    assert all(r.logits is not None for r in reqs), "requests dropped"
    return tel, np.stack([r.logits for r in reqs]), wall, cache


def reference_logits(params, images):
    """Unbatched reference forward (plan=None), one request at a time."""
    outs = []
    for img in images:
        program = lower(B1_SMOKE, batch=1, image_size=img.shape[0])
        outs.append(np.asarray(
            execute(program, params, img[None]))[0])
    return np.stack(outs)


def _replay_line(name, tel, wall, n):
    return (f"  {name:<9} occupancy {tel.occupancy:>5.1%}  "
            f"padded {tel.total('padded'):>3}  "
            f"dispatches {tel.total('dispatches'):>3}  "
            f"compiles {tel.counters.get('executor_miss', 0):>2}  "
            f"plan-sites reused {tel.counters.get('plan_sites_reused', 0):>2}"
            f"  wall {wall * 1e3:7.0f} ms  ({n / wall:6.1f} img/s)")


def sharded_section(params, qparams, spec, trace, images, results):
    """Multi-device section (>= 2 devices, e.g. under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``): the same
    trace replayed with every dispatch batch-axis-sharded across the
    mesh, plus a 4x-compressed high-QPS replay for per-device occupancy.

    Parity gates: sharded fp logits match the single-device replay to
    1e-5, and sharded int8 logits are BIT-EXACT — per-batch-
    element activation scales make the batch split invisible to each
    request's numerics.
    """
    devices = tuple(jax.devices())
    if len(devices) < 2:
        print("\n(single device: sharded serving section skipped — run "
              "under XLA_FLAGS=--xla_force_host_platform_device_count=N)")
        return None
    n = len(images)
    print(f"\n## sharded x {len(devices)} devices (batch-axis shard_map)")
    for prec_name, tree, precision, gate in (
            ("fp", params, "auto", 1e-5), ("int8", qparams, "int8", 0.0)):
        tel, logits, wall, cache = replay(
            tree, spec, trace, images, precision=precision,
            devices=devices)
        single = results[prec_name]["logits"]
        err = float(np.max(np.abs(logits - single)))
        assert err <= gate, \
            (prec_name, "sharded vs single-device drift", err, gate)
        print(_replay_line(f"{prec_name}", tel, wall, n)
              + f"  vs single-device max|Δ| {err:.1e}"
              + (" (bit-exact)" if err == 0.0 else ""))
    # high-QPS replay: arrivals compressed 4x, so batch formation leans
    # on the big buckets and every mesh device sees traffic
    fast = [(at / 4.0, res) for at, res in trace]
    tel, _logits, wall, _cache = replay(
        params, spec, fast, images, devices=devices)
    assert tel.devices, "sharded replay recorded no per-device telemetry"
    used = sorted(tel.devices)
    print(f"  high-QPS (4x arrival rate): {len(used)} devices active")
    for did in used:
        d = tel.devices[did]
        print(f"    dev{did}: dispatches {d.dispatches:>3}  samples "
              f"{d.samples:>3}  padded {d.padded:>2}  occupancy "
              f"{d.occupancy:.0%}")
    return tel


def check_trace(tracer, reqs_done: int, trace_json: str | None = None):
    """Observability gate: the replay's trace must be schema-
    valid and contain a COMPLETE admit -> queue -> dispatch -> device ->
    finalize chain for every completed request.  Optionally exports the
    Chrome trace JSON to ``trace_json``."""
    doc = tracer.export(trace_json) if trace_json is not None \
        else tracer.to_chrome()
    n_complete = validate_chrome_trace(doc)
    chains = request_chains(doc)
    assert len(chains) == reqs_done, (len(chains), reqs_done)
    incomplete = [
        rid for rid, c in chains.items()
        if not ({"queue"} <= c["children"]
                and {"dispatch", "device", "finalize"} <= c["member_of"])]
    assert not incomplete, \
        f"requests without a complete span chain: {sorted(incomplete)}"
    assert not tracer.open_spans(), \
        [s.name for s in tracer.open_spans()]
    return doc, n_complete, chains


def run(smoke: bool = False, trace_path: str | None = None,
        record_path: str | None = None, trace_json: str | None = None,
        json_out: str | None = None):
    spec = SMOKE if smoke else FULL
    key = jax.random.PRNGKey(0)
    params = init_efficientvit(key, B1_SMOKE)
    qparams = quantize_efficientvit(params)
    if trace_path is not None:
        from repro.search.trace import load_trace
        trace = load_trace(trace_path)
        print(f"(replaying recorded trace {trace_path}: "
              f"{len(trace)} requests)")
    else:
        trace = make_trace(spec)
    if record_path is not None:
        from repro.search.trace import save_trace
        fp = save_trace(record_path, trace, spec=spec)
        print(f"(trace recorded to {record_path}, fingerprint {fp})")
    images = make_images(trace)
    n = len(images)

    print(f"# serving bench — {B1_SMOKE.name}, {n} requests over "
          f"{spec['resolutions']}px, buckets {spec['buckets']}, "
          f"deadline {spec['deadline_ms']:.0f} ms (virtual clock)")

    results = {}
    for prec_name, tree, precision in (("fp", params, "auto"),
                                       ("int8", qparams, "int8")):
        # the replays run WITH tracing enabled, so every drift gate
        # below (parity, EXPECTED_SMOKE_KEYS) holds on the traced
        # runtime, not a tracing-off twin
        tel, logits, wall, cache = replay(
            tree, spec, trace, images, precision=precision,
            with_tracer=True)
        results[prec_name] = dict(tel=tel, logits=logits, wall=wall,
                                  cache=cache)
        print(f"\n## {prec_name}")
        print(_replay_line(prec_name, tel, wall, n))
        print("\n  per-bucket telemetry:")
        for line in tel.table().splitlines():
            print("  " + line)

    # fp numerics: the replay matches the unbatched reference (int8
    # dequant reassociation leaves float-ulp noise across batch shapes
    # — per-bucket parity lives in tests/test_serving_runtime.py).
    fp = results["fp"]
    ref = reference_logits(params, images)
    err = float(np.max(np.abs(fp["logits"] - ref)))
    assert err < 1e-3, err
    print(f"\nfp parity: replay vs unbatched reference "
          f"max|Δ| < 1e-3 on all {n} requests")

    # executor-cache key-set drift gate (smoke trace only: the full
    # trace's key set depends on its larger random arrival pattern).
    # Gated on the keys batch formation actually dispatched to — the
    # warmed cache holds the full bucket x resolution product.
    if smoke:
        got = {(b, res) for b, res, _ in fp["tel"].buckets}
        assert got == EXPECTED_SMOKE_KEYS, \
            f"executor key-set drift: {sorted(got)} != " \
            f"{sorted(EXPECTED_SMOKE_KEYS)} — update EXPECTED_SMOKE_KEYS " \
            f"alongside the scheduler change"
        print(f"executor key-set gate: dispatched {sorted(got)} == expected")

    # trace completeness gate: every completed request in both traced
    # replays left a full admit -> queue -> dispatch -> device ->
    # finalize chain; the fp trace optionally exports
    trace_stats = {}
    for prec_name in ("fp", "int8"):
        tracer = results[prec_name]["cache"].tracer
        doc, n_complete, chains = check_trace(
            tracer, n, trace_json if prec_name == "fp" else None)
        trace_stats[prec_name] = dict(spans=n_complete, chains=len(chains))
    print(f"\ntrace gate: {trace_stats['fp']['chains']} fp / "
          f"{trace_stats['int8']['chains']} int8 request chains complete "
          f"({trace_stats['fp']['spans']} / {trace_stats['int8']['spans']} "
          f"spans)"
          + (f"; Chrome trace written to {trace_json}" if trace_json
             else ""))

    metrics = {
        prec: {"occupancy": d["tel"].occupancy,
               "padded": d["tel"].total("padded"),
               "dispatches": d["tel"].total("dispatches"),
               "wall_s": d["wall"]}
        for prec, d in results.items()}
    if json_out is not None:
        doc = bench_result(
            "serving_bench",
            config=dict(smoke=smoke, n_requests=n,
                        resolutions=list(spec["resolutions"]),
                        buckets=list(spec["buckets"]),
                        deadline_ms=spec["deadline_ms"],
                        n_devices=len(jax.devices())),
            metrics=dict(metrics,
                         trace=dict(trace_stats)),
            gates=dict(
                fp_parity=True,           # asserted above
                smoke_key_set=smoke,      # asserted above when smoke
                trace_chains_complete=True))
        write_result(json_out, doc)
        print(f"ledger written to {json_out}")
    return metrics


def main():
    from repro.common.compile_cache import use_compile_cache
    use_compile_cache()
    argv = sys.argv[1:]
    run(smoke="--smoke" in argv,
        trace_path=flag_value(argv, "--trace"),
        record_path=flag_value(argv, "--record-trace"),
        trace_json=flag_value(argv, "--trace-json"),
        json_out=flag_value(argv, "--json"))


if __name__ == "__main__":
    main()
