#!/usr/bin/env python3
"""Find a cell's knee: one set-up, then one window per offered load.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 100 200 400        # open loop: arrivals per second
    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --outstanding 8 16 32      # closed loop: requests in flight

Each window prints what it offered, what completed inside it, the
backlog left at its close and the latency tail.  The knee is the highest
rate whose completions keep pace with its arrivals with no backlog
growing.  Also printed: the device time of one forward per bucket
(back-to-back calls, read once at the end), from which a mix sets its
batching deadline.  ``--deadline-ms`` overrides the mix's (``auto``: the bucket-8
forward's device time, rounded up to 0.5 ms).  ``--refine K`` adds K
rates between the highest that kept pace and the lowest that did not.
``--write-traffic NAME --fraction F`` writes the mix, with the rate set
to F x the knee and the deadline used, as ``bench/traffic/NAME.json``.
A rate keeps pace when the backlog left at the window's close is at
most what arrives in 50 ms, plus 8, and its p99 latency is at most 3x
that of the lightest load tried (a queue that grows in bursts and
drains again leaves no backlog at the close, but shows in the tail).  ``--trace-out DIR`` keeps the
profiler trace of the first window there, for reading by hand.  A tool
for choosing a mix's numbers; the benchmark's runs do not call it.
"""
import time

T_START = time.perf_counter()

import argparse      # noqa: E402
import dataclasses   # noqa: E402
import json          # noqa: E402
import math          # noqa: E402
import sys           # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))

import run as bench_run  # noqa: E402
from benchlib import spec, system, traffic  # noqa: E402


def forward_ms(engine, tree, bucket: int, size: int, n: int = 50) -> float:
    import jax
    import jax.numpy as jnp
    ex = engine.cache.get(bucket, size)
    x = jnp.zeros((bucket, size, size, 3), jnp.float32)
    jax.block_until_ready(ex(tree, x))
    t = time.perf_counter()
    outs = [ex(tree, x) for _ in range(n)]
    jax.block_until_ready(outs)
    return (time.perf_counter() - t) * 1e3 / n


def knee_of(kept: dict):
    """(highest rate below the first that lost pace, that first rate)."""
    fail = min((r for r, k in kept.items() if not k), default=None)
    knee = max((r for r, k in kept.items()
                if k and (fail is None or r < fail)), default=None)
    return knee, fail


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", type=float, nargs="*", default=())
    ap.add_argument("--outstanding", type=int, nargs="*", default=())
    ap.add_argument("--deadline-ms", default=None)
    ap.add_argument("--refine", type=int, default=0)
    ap.add_argument("--write-traffic", default=None)
    ap.add_argument("--fraction", type=float, default=0.8)
    ap.add_argument("--trace-out", default=None)
    args = ap.parse_args(argv)

    cell = spec.load_cell(args.workload)
    cfg, mix = cell.config, traffic.Mix.parse(cell.traffic)
    sys.path.insert(0, str(bench_run.ROOT / "src"))
    jax = bench_run.configure_jax(cfg)
    try:
        bench_run.check_device(jax, cell.chips)
    except bench_run.Refused as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    ref = cell.reference
    params, pool = system.make_inputs(ref, cfg, mix.pool, args.seed)
    tree = system.served_tree(params, cfg)
    engine = system.build_engine(tree, cfg, mix)
    engine.warmup()
    size = cfg["image_size"]
    print(f"set-up {time.perf_counter() - T_START:.1f} s", flush=True)
    fwd = {}
    for b in mix.buckets:
        fwd[b] = forward_ms(engine, tree, b, size)
        print(f"bucket {b}: {fwd[b]!r} ms per forward, back to back",
              flush=True)
    if args.deadline_ms == "auto":
        mix = dataclasses.replace(
            mix, deadline_ms=math.ceil(fwd[max(fwd)] * 2) / 2)
    elif args.deadline_ms is not None:
        mix = dataclasses.replace(mix, deadline_ms=float(args.deadline_ms))
    print(f"deadline_ms {mix.deadline_ms}", flush=True)

    loads = ([("open", r) for r in args.rates]
             + [("closed", n) for n in args.outstanding])
    kept = {}
    floor_p99 = float("inf")     # p99 of the lightest load so far
    i = 0
    while i < len(loads):
        loop, load = loads[i]
        m = dataclasses.replace(
            mix, loop=loop, warmup_s=1.0,
            rate_per_s=float(load) if loop == "open" else mix.rate_per_s,
            outstanding=int(load) if loop == "closed" else mix.outstanding)
        plan = traffic.schedule(m, args.seed + i, args.seconds)
        sched = engine.scheduler().start()
        client = system.Client(sched, pool, plan)
        tracing = args.trace_out is not None and i == 0
        if tracing:
            jax.profiler.start_trace(args.trace_out,
                                     profiler_options=bench_run.PROFILE())
        t0 = time.perf_counter() + m.warmup_s
        t1 = t0 + args.seconds
        if loop == "closed":
            client.closed(t0, t1, m.outstanding)
        else:
            client.open(t0)
        backlog = sum(1 for s in client.sent
                      if s.due < t1 and (s.done is None or s.done >= t1))
        client.finish(t1 + system.GRACE_S)
        if tracing:
            jax.profiler.stop_trace()
        window = [s for s in client.sent if t0 <= s.due < t1]
        done = sum(1 for s in client.sent
                   if s.done is not None and t0 <= s.done < t1)
        lat = [(s.done - s.due) * 1e3 if s.done is not None
               else float("inf") for s in window]
        late = client.lateness
        per_bucket = {k[0]: v.dispatches for k, v in
                      sorted(engine.telemetry.buckets.items())}
        print(f"{loop} {load:g}: sent {len(window)} "
              f"({len(window) / args.seconds:.1f}/s), completed in window "
              f"{done} ({done / args.seconds:.1f}/s), backlog at close "
              f"{backlog}, latency p50 "
              f"{system.percentile(lat, 50):.3f} ms p99 "
              f"{system.percentile(lat, 99):.3f} ms max "
              f"{max(lat, default=0):.3f} ms, generator lateness p99 "
              f"{system.percentile(late, 99) * 1e3:.3f} ms, dispatches "
              f"per bucket so far {per_bucket}", flush=True)
        if loop == "open":
            p99 = system.percentile(lat, 99)
            floor_p99 = min(floor_p99, p99)
            kept[load] = (backlog <= 0.05 * load + 8
                          and p99 <= 3 * floor_p99)
            print(json.dumps({"rate": load, "sent": len(window),
                              "completed": done, "backlog": backlog,
                              "p50_ms": system.percentile(lat, 50),
                              "p99_ms": system.percentile(lat, 99),
                              "kept_pace": kept[load]}), flush=True)
        i += 1
        if i == len(loads) and args.refine and loop == "open":
            knee, fail = knee_of(kept)
            if knee and fail:
                lo, hi = knee, fail
                loads += [("open", round(lo + (hi - lo) * j
                                         / (args.refine + 1)))
                          for j in range(1, args.refine + 1)]
                args.refine = 0
    knee, _ = knee_of(kept)
    print(f"knee {knee} requests/s", flush=True)
    if args.write_traffic and knee:
        out = dict(cell.traffic, rate_per_s=round(args.fraction * knee),
                   deadline_ms=mix.deadline_ms)
        path = BENCH_DIR / "traffic" / f"{args.write_traffic}.json"
        path.write_text(json.dumps(out, indent=2) + "\n")
        print(f"wrote {path}: {json.dumps(out)}", flush=True)
    if args.trace_out:
        print(f"trace kept under {args.trace_out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
