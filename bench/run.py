#!/usr/bin/env python3
"""Run one cell of the benchmark on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell: set up (weights from the seed, the served
engine, every bucket the cell's traffic uses compiled and warmed, the
host loop started and run for the mix's warm-up), measure for
``--seconds``, check every answer against the configuration's plain
reference, and print one JSON line last on stdout.  ``--trace 0``
reports the cell's end-to-end metrics, ``--trace 1`` its per-layer
metrics, read from the run's spans and the profiler's device trace over
a window of at most ``TRACE_S`` seconds.
Progress and the set-up breakdown go to stderr; the numbers compared
for ``correct`` are its last lines.

Without a TPU, with fewer chips than the cell asks for, or with a chip
missing from ``bench/peaks.json`` it exits 2 and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse      # noqa: E402
import gc            # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import shutil        # noqa: E402
import sys           # noqa: E402
import tempfile      # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
sys.path.insert(0, str(BENCH_DIR))

from benchlib import spec  # noqa: E402

# A traced run measures a window of at most this many seconds: the
# device runs ~800 XLA ops per B1 forward, and reading a trace costs
# about 10 us per op, so a whole 20 s window would not be read in time.
TRACE_S = 3.0
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_MISS = "/jax/compilation_cache/cache_misses"


def PROFILE():
    """Device ops and user annotations only: the runtime's host events
    would make a traced window's file hundreds of MB."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


class GcPauses:
    """Collections of the cyclic garbage collector, stamped on the host
    clock: a pause stops every thread of the process, the served path's
    and the harness's."""

    def __init__(self):
        self.pauses, self._t = [], None
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        t = time.perf_counter()
        if phase == "start":
            self._t = t
        elif self._t is not None:
            self.pauses.append((self._t, t - self._t, info["generation"]))

    def summary(self, t0, t1) -> str:
        p = [d for t, d, _ in self.pauses if t0 <= t < t1]
        return (f"{len(p)} collections, {sum(p) * 1e3:.3f} ms in all, "
                f"longest {max(p, default=0) * 1e3:.3f} ms")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Refused(Exception):
    """This machine or checkout cannot run the cell: exit 2, no result."""


class CompileLog:
    """Executables produced (compiled or loaded from the persistent
    cache) and cache hits/misses, stamped on the host clock."""

    def __init__(self):
        import jax.monitoring as mon
        self.events = []
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event in (CACHE_HIT, CACHE_MISS):
            self.events.append((time.perf_counter(), event))

    def _duration(self, event, duration, **_):
        if event == BACKEND_COMPILE:
            self.events.append((time.perf_counter(), event))

    def count(self, event, t0=float("-inf"), t1=float("inf")) -> int:
        return sum(1 for t, e in self.events if e == event and t0 <= t < t1)


def configure_jax(cfg: dict):
    """Compile cache inside the checkout (or where the environment puts
    it), every executable cached, and the configuration's matmul
    precision for every thread."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_default_matmul_precision",
                      cfg["matmul_precision"])
    return jax


def check_device(jax, chips: int) -> dict:
    """The chip this run measures, and its peaks; refuses anything else."""
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise Refused(f"JAX found no devices: {e}")
    dev = devices[0]
    if dev.platform != "tpu":
        raise Refused(f"needs a TPU; JAX found {dev.platform} "
                      f"({dev.device_kind})")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX found "
                      f"{len(devices)}")
    peaks = spec.load_json(BENCH_DIR / "peaks.json")["devices"]
    if dev.device_kind not in peaks:
        raise Refused(f"device {dev.device_kind!r} is not in "
                      f"bench/peaks.json")
    return peaks[dev.device_kind]


def device_record(jax, chips: int) -> dict:
    devs = jax.devices()[:chips]
    peak = None
    for d in devs:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peak = max(peak or 0, int(stats["peak_bytes_in_use"]))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": peak}


class Setup:
    """Set-up items, each timed and printed as it ends."""

    def __init__(self):
        self._t = T_START

    def done(self, item: str, extra: str = "") -> None:
        t = time.perf_counter()
        log(f"setup {item}: {t - self._t:.3f} s{extra}")
        self._t = t


def run_cell(args, require_tpu: bool = True) -> dict:
    """One run of one cell; returns the result line's object."""
    from benchlib import correct as cmp
    from benchlib import system, traffic, xtrace

    if not (ROOT / "src" / "repro").is_dir():
        raise Refused(f"{ROOT / 'src' / 'repro'} not found: run from a "
                      f"checkout of the repository")
    cell = spec.load_cell(args.workload)
    cfg, mix = cell.config, traffic.Mix.parse(cell.traffic)
    plan = traffic.schedule(mix, args.seed, args.seconds)
    sys.path.insert(0, str(ROOT / "src"))
    jax = configure_jax(cfg)
    peaks = check_device(jax, cell.chips) if require_tpu else {}
    # a configuration this checkout's program cannot represent is
    # refused now, before minutes of weights and compiles
    system.model_config(cfg)
    compiles = CompileLog()
    ref = cell.reference
    import repro.serving.vision  # noqa: F401  (import time is set-up)
    setup = Setup()
    setup.done("imports and device check")

    params, pool = system.make_inputs(ref, cfg, mix.pool, args.seed)
    tree = system.served_tree(params, cfg)
    setup.done("params and image pool made on the device")

    tracer = None
    if args.trace:
        from repro.obs.trace import Tracer
        tclock = system.FirstReading()
        tracer = Tracer(clock=tclock, capacity=1 << 22)
    engine = system.build_engine(tree, cfg, mix, tracer=tracer)
    setup.done("engine build (lower, plan)")
    size = cfg["image_size"]
    for b in mix.buckets:
        t = time.perf_counter()
        engine.cache.warmup((size,), buckets=(b,))
        setup.done(f"compile bucket {b}",
                   f" (executables {compiles.count(BACKEND_COMPILE, t)}, "
                   f"cache hits {compiles.count(CACHE_HIT, t)}, misses "
                   f"{compiles.count(CACHE_MISS, t)})")

    # what set-up made lives as long as the process: the collector
    # need not walk it again in every full collection of the window
    gc.collect()
    gc.freeze()
    pauses = GcPauses()
    sched = engine.scheduler().start()
    client = system.Client(sched, pool, plan)
    trace_dir = None
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(trace_dir, profiler_options=PROFILE())
        align = time.perf_counter()
        with jax.profiler.TraceAnnotation(xtrace.ALIGN):
            pass
    t0 = time.perf_counter() + mix.warmup_s
    t1 = t0 + (min(args.seconds, TRACE_S) if args.trace else args.seconds)
    try:
        if mix.loop == "closed":
            client.closed(t0, t1, mix.outstanding)
        else:
            client.open(t0)
        setup_s = t0 - T_START
        client.finish(t1 + system.GRACE_S)
    finally:
        sched.stop(drain=False)
        if args.trace:
            jax.profiler.stop_trace()
    log(f"setup host-loop warm-up: {mix.warmup_s:.3f} s; setup_s "
        f"{setup_s:.3f} s")
    device = device_record(jax, cell.chips)

    window = [s for s in client.sent if t0 <= s.due < t1]
    done_in_window = sum(1 for s in client.sent
                         if s.done is not None and t0 <= s.done < t1)
    missing = [s for s in window if s.status != "completed"]
    late = sorted(client.lateness)
    lat = [(s.done - s.due) * 1e3 for s in window if s.done is not None]
    log(f"window: {len(window)} requests sent, {done_in_window} completed "
        f"inside it, {len(missing)} never answered; latency p99 "
        f"{system.percentile(lat, 99):.3f} ms, max "
        f"{max(lat, default=0):.3f} ms; generator lateness "
        f"p50 {system.percentile(late, 50) * 1e3:.3f} ms, p99 "
        f"{system.percentile(late, 99) * 1e3:.3f} ms, max "
        f"{late[-1] * 1e3 if late else 0:.3f} ms; garbage collector in "
        f"the window: {pauses.summary(t0, t1)}")

    run = RunRecord(cell=cell, t0=t0, t1=t1, sent=client.sent,
                    window=window, done_in_window=done_in_window,
                    compiles=compiles.count(BACKEND_COMPILE, t0, t1),
                    flops_per_image=2 * ref.macs_per_image(cfg),
                    peak=peaks.get(cfg["peak"]))
    if args.trace:
        run.spans = [(s.name, s.start + tclock.first, s.end_ts + tclock.first,
                      s.attrs, s.span_id, s.parent_id)
                     for s in tracer.spans()]
        path = xtrace.find_xplane(trace_dir)
        run.device = xtrace.reduce(xtrace.load(path, align), t0, t1)
        shutil.rmtree(trace_dir, ignore_errors=True)

    # the program's state goes before the reference runs
    del engine, sched, tree, client
    gc.collect()
    t = time.perf_counter()
    answers = [(s.image, s.logits) for s in run.sent
               if s.status == "completed"]
    refs = cmp.reference_logits(ref, cfg, params, pool,
                                [i for i, _ in answers])
    err = cmp.worst(answers, refs)
    log(f"reference over {len(refs)} pool images for {len(answers)} "
        f"answers: {time.perf_counter() - t:.3f} s")

    limit = float(cfg["correct"]["logit_err"])
    checks = {"logit_err": {"value": err, "limit": limit},
              "unanswered": {"value": len(missing), "limit": 0}}
    result = {"correct": bool(err <= limit and not missing),
              "attempted": len(window), "failed": len(missing)}
    if args.trace:
        result["metrics"] = per_layer(cell, run)
        if run.device is not None:
            device["busy_s"] = run.device.busy_s
            device["window_s"] = run.device.window_s
            result["breakdown"] = breakdown(run)
    else:
        result["metrics"] = end_to_end(cell, run, setup_s)
    result["device"] = device
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    return result


class RunRecord:
    """What the per-layer readers read (``bench/metrics/*.py``).

    ``sent``: every request (``system.Sent``); ``window``: those due in
    [t0, t1); ``spans``: the scheduler's spans as (name, start, end,
    attrs, span_id, parent_id) on the host clock (traced runs);
    ``device``: the ``xtrace.Reduction`` of the window (traced runs on a
    device); ``compiles``: executables produced inside the window;
    ``flops_per_image`` from the configuration's reference; ``peak``:
    the chip's peak rate for the configuration's arithmetic."""

    def __init__(self, **kw):
        self.spans = []
        self.device = None
        self.__dict__.update(kw)

    def spans_in_window(self, name: str):
        return [s for s in self.spans
                if s[0] == name and self.t0 <= s[1] < self.t1]

    def forwards(self) -> int:
        """Program executions in the window: the device trace's count
        where it has one, else the batches dispatched."""
        if self.device is not None and self.device.executions:
            return self.device.executions
        return len(self.spans_in_window("dispatch"))


def end_to_end(cell, run: RunRecord, setup_s: float) -> dict:
    from benchlib.system import percentile
    lat = [(s.done - s.due) * 1e3 if s.done is not None else float("inf")
           for s in run.window]
    values = {"images_per_s": run.done_in_window / (run.t1 - run.t0),
              "latency_p50_ms": percentile(lat, 50),
              "latency_p90_ms": percentile(lat, 90),
              "setup_s": setup_s}
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}


def per_layer(cell, run: RunRecord) -> dict:
    out = {}
    for m in cell.per_layer:
        v = spec.metric_reader(m["name"])(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def breakdown(run: RunRecord) -> dict:
    from benchlib import xtrace
    red = run.device
    ops = sorted(red.by_op.items(), key=lambda kv: -kv[1])[:10]
    tags = xtrace.tag_gaps(red.gaps, [(s[0], s[1], s[2])
                                      for s in run.spans])
    gaps = sorted(((f"{k} x{n}", v) for k, (v, n) in tags.items()),
                  key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, require_tpu: bool = True) -> int:
    args = parse(argv)
    try:
        result = run_cell(args, require_tpu=require_tpu)
    except (Refused, spec.SpecError) as e:
        log(f"bench: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
