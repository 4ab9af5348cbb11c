"""Offline schedule search bench: searched vs hand-default schedules.

Runs the full ``repro.search`` stack against a recorded traffic trace,
at BOTH serving precisions, and gates the claims the subsystem makes:

  1. objective gate — the searched schedule's trace-weighted cycle
     objective is <= the hand-default schedule's (the default IS in the
     search space, so this must hold; CI runs it on the committed
     fixture trace);
  2. zero-sweep gate — an artifact-warm ``ExecutorCache`` cold start
     performs ZERO autotune sweeps (``kernels.autotune.SWEEP_COUNT``
     does not move) while the default cold start, given a fresh tuner
     cache, sweeps for real;
  3. reproduction gate — every plan the artifact-warm cache builds is
     decision-for-decision identical to what the search froze into the
     artifact;
  4. wall-clock — the artifact-warm cold start replays the trace faster
     than the default cold start end to end (cache build + warmup +
     replay), at both precisions: the sweeps it skips are real work.

    PYTHONPATH=src python -m benchmarks.search_bench [--smoke]
        [--trace PATH]      trace to search against (default: the
                            committed fixture tests/data/trace_smoke.json)
        [--out DIR]         write the searched artifacts as JSON
        [--iters N]         annealing iterations (default 64)
        [--json OUT]        machine-readable result ledger
                            (repro.obs.ledger, BENCH_SCHEMA)
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
import time

import jax

from repro.core.efficientvit import B1_SMOKE, init_efficientvit
from repro.core.quantization import quantize_efficientvit
from repro.kernels import autotune as at
from repro.obs import bench_result, flag_value, write_result
from repro.search import ScheduleArtifact, search

FIXTURE = os.path.join(os.path.dirname(__file__), "..", "tests", "data",
                       "trace_smoke.json")
SPEC = dict(buckets=(1, 2, 4), deadline_ms=40.0, resolutions=(32, 64),
            microbatch=4)


def cold_start_replay(tree, spec, trace, images, *, precision,
                      artifact=None):
    """One cold-start measurement: fresh tuner cache, build + warm +
    replay all inside the wall-clock window.  Returns (wall_s, sweeps,
    cache)."""
    from benchmarks.serving_bench import replay
    with tempfile.TemporaryDirectory() as td:
        old = os.environ.get("REPRO_AUTOTUNE_CACHE")
        os.environ["REPRO_AUTOTUNE_CACHE"] = os.path.join(td, "at.json")
        at.clear_memory_cache()
        sweeps0 = at.SWEEP_COUNT
        t0 = time.perf_counter()
        try:
            _tel, logits, _wall, cache = replay(
                tree, spec, trace, images, precision=precision,
                autotune=True, artifact=artifact)
        finally:
            if old is None:
                os.environ.pop("REPRO_AUTOTUNE_CACHE", None)
            else:
                os.environ["REPRO_AUTOTUNE_CACHE"] = old
            at.clear_memory_cache()
        wall = time.perf_counter() - t0
    return wall, at.SWEEP_COUNT - sweeps0, cache, logits


def check_reproduction(cache, artifact) -> int:
    """Every plan the artifact-warm cache built must match the frozen
    decisions bit for bit; returns the number of plans checked."""
    checked = 0
    for key, ex in cache._lru.items():
        stored = artifact.decisions_for(key.batch, key.resolution)
        if stored is None or ex.plan is None:
            continue
        got = [d.to_dict() for d in ex.plan.decisions.values()]
        assert got == stored, (
            f"plan for {key} drifted from the searched artifact:\n"
            f"got {got}\nwant {stored}")
        checked += 1
    assert checked, "artifact-warm cache built no artifact-covered plans"
    return checked


def run(smoke: bool = False, trace_path: str | None = None,
        out_dir: str | None = None, iters: int = 64,
        json_out: str | None = None):
    from benchmarks.serving_bench import make_images, replay
    from repro.search import load_trace

    trace = load_trace(trace_path if trace_path is not None else FIXTURE)
    images = make_images(trace)
    spec = dict(SPEC)
    key = jax.random.PRNGKey(0)
    params = init_efficientvit(key, B1_SMOKE)
    qparams = quantize_efficientvit(params)

    print(f"# search bench — {B1_SMOKE.name}, {len(trace)} requests, "
          f"default buckets {spec['buckets']}, "
          f"deadline {spec['deadline_ms']:.0f} ms")
    results = {}
    for prec_name, tree, precision in (("fp", params, "auto"),
                                       ("int8", qparams, "int8")):
        print(f"\n## {prec_name}")
        t0 = time.perf_counter()
        art = search(B1_SMOKE, tree, trace, buckets=spec["buckets"],
                     precision=precision,
                     deadline_ms=spec["deadline_ms"], seed=0,
                     iters=iters, verbose=not smoke)
        t_search = time.perf_counter() - t0
        ratio = art.objective / art.default_objective
        print(f"  objective: default {art.default_objective:,.0f} -> "
              f"searched {art.objective:,.0f} cycles ({ratio:.3f}x), "
              f"buckets {list(spec['buckets'])} -> {list(art.buckets)}, "
              f"search took {t_search:.1f} s (host-only)")
        # gate 1: the default schedule is in the search space and the
        # best state is tracked, so searched <= default ALWAYS
        assert art.objective <= art.default_objective, \
            (prec_name, art.objective, art.default_objective)

        # round-trip through JSON, exactly as a cold-start pod would
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
            path = os.path.join(out_dir, f"schedule_{prec_name}.json")
        else:
            path = os.path.join(tempfile.gettempdir(),
                                f"repro_schedule_{prec_name}.json")
        art.save(path)
        art = ScheduleArtifact.load(path)
        print(f"  artifact: {path} "
              f"({os.path.getsize(path) / 1024:.1f} KiB, "
              f"{len(art.entries)} executor shapes)")

        wall_d, sweeps_d, _cache_d, logits_d = cold_start_replay(
            tree, spec, trace, images, precision=precision)
        aspec = dict(spec, buckets=art.buckets,
                     microbatch=max(art.buckets))
        wall_a, sweeps_a, cache_a, logits_a = cold_start_replay(
            tree, aspec, trace, images, precision=precision,
            artifact=art)
        print(f"  cold start: default {wall_d:.2f} s ({sweeps_d} autotune "
              f"sweeps) vs artifact-warm {wall_a:.2f} s ({sweeps_a} "
              f"sweeps) — {wall_d / wall_a:.2f}x")
        # gate 2: artifact-warm cold start never sweeps
        assert sweeps_a == 0, f"artifact-warm start swept {sweeps_a}x"
        assert sweeps_d > 0, "default cold start should have swept"
        # gate 4: skipping the sweeps must show up on the wall clock
        assert wall_a < wall_d, (prec_name, wall_a, wall_d)
        # gate 3: the served plans ARE the searched plans
        n_plans = check_reproduction(cache_a, art)
        print(f"  reproduction: {n_plans} plan(s) match the artifact "
              f"decision-for-decision")
        import numpy as np
        err = float(np.max(np.abs(np.asarray(logits_a, dtype=np.float64)
                                  - np.asarray(logits_d,
                                               dtype=np.float64))))
        print(f"  logits vs default replay: max|Δ| {err:.2e}")
        results[prec_name] = dict(
            objective=art.objective,
            default_objective=art.default_objective,
            wall_default_s=wall_d, wall_artifact_s=wall_a,
            sweeps_default=sweeps_d, sweeps_artifact=sweeps_a)
    print("\nall search gates passed (objective, zero-sweep, "
          "reproduction, cold-start wall clock) at both precisions")
    if json_out is not None:
        doc = bench_result(
            "search_bench",
            config=dict(smoke=smoke, cfg=B1_SMOKE.name, iters=iters,
                        n_requests=len(trace), buckets=list(SPEC["buckets"]),
                        trace=trace_path if trace_path is not None
                        else FIXTURE),
            metrics=results,
            gates={f"{p}_{g}": ok for p, r in results.items()
                   for g, ok in (
                       ("objective", r["objective"]
                        <= r["default_objective"]),
                       ("zero_sweep", r["sweeps_artifact"] == 0),
                       ("cold_start_faster", r["wall_artifact_s"]
                        < r["wall_default_s"]))})
        write_result(json_out, doc)
        print(f"ledger written to {json_out}")
    return results


def main():
    from repro.common.compile_cache import use_compile_cache
    use_compile_cache()
    argv = sys.argv[1:]
    run(smoke="--smoke" in argv,
        trace_path=flag_value(argv, "--trace"),
        out_dir=flag_value(argv, "--out"),
        iters=int(flag_value(argv, "--iters") or 64),
        json_out=flag_value(argv, "--json"))


if __name__ == "__main__":
    main()
