"""Benchmark aggregator + perf-ledger regression gate.

    PYTHONPATH=src python -m benchmarks.run
    PYTHONPATH=src python -m benchmarks.run --compare-ledger OLD.json NEW.json [--tol PCT]

The default invocation runs one section per paper table/figure plus the
roofline sweeps and exits non-zero on any gate failure.

``--compare-ledger`` diffs two ``BENCH_*.json`` perf ledgers
(``repro.obs.ledger`` schema) and exits non-zero when the NEW run
regresses the OLD one: any gate that was green goes red, or any
cost-like numeric metric (delivered HBM, launch counts, search
objective, dispatch/padding counts, error bounds) grows by more than
``--tol`` percent (default 2).  Wall-clock / timing leaves are never
gated — on the CPU interpreter they measure machine load, not the
schedule.  This is ROADMAP item 6's "perf regression fails CI the way a
correctness regression does": CI replays the smoke benchmark and
compares its fresh ledger against the committed
``benchmarks/ledger/BENCH_SMOKE.json``.
"""
from __future__ import annotations

import sys
import time

# Metric-path substrings where a LARGER value is a perf regression.
# Matched against dot-joined paths into the ledger's "metrics" dict.
_HIGHER_IS_WORSE = ("hbm", "launch", "objective", "dispatch", "padded",
                    "demotion", "sweep", "err", "evals")
# ...unless the path also says it's a benefit metric (hbm_saving_x,
# occupancy, GOPS, utilization): those regress by SHRINKING, which the
# benchmarks' own boolean gates already police.
_HIGHER_IS_BETTER = ("saving", "occupancy", "gops", "util", "exact")
# Timing leaves (wall_s, t_ref, drift tables) are machine-load noise on
# the CPU interpreter — never gated.
_TIMING_SEGMENTS = ("wall", "time", "drift")


def _skip(path: str) -> bool:
    segs = path.lower().split(".")
    return any(s.startswith(_TIMING_SEGMENTS) or s.startswith("t_")
               for s in segs)


def _gated(path: str) -> bool:
    p = path.lower()
    if _skip(path) or any(k in p for k in _HIGHER_IS_BETTER):
        return False
    return any(k in p for k in _HIGHER_IS_WORSE)


def _numeric_leaves(node, prefix: str = "") -> dict:
    """Flatten a ledger's metrics tree to {dot.path: float}; bools are
    not numbers here."""
    out: dict[str, float] = {}
    if isinstance(node, dict):
        for k, v in node.items():
            key = f"{prefix}.{k}" if prefix else str(k)
            out.update(_numeric_leaves(v, key))
    elif isinstance(node, bool):
        pass
    elif isinstance(node, (int, float)):
        out[prefix] = float(node)
    return out


def compare_ledgers(old_path: str, new_path: str,
                    tol_pct: float = 2.0) -> list:
    """Diff two perf ledgers; returns the list of regression strings
    (empty = NEW is no worse than OLD within tolerance)."""
    from repro.obs import load_result
    old, new = load_result(old_path), load_result(new_path)
    if old["name"] != new["name"]:
        raise SystemExit(
            f"cannot compare ledgers from different benchmarks: "
            f"{old['name']!r} vs {new['name']!r}")
    bad: list[str] = []
    for gate, was in sorted(old["gates"].items()):
        now = new["gates"].get(gate)
        if was and now is False:
            bad.append(f"gate {gate!r}: green -> red")
    o, n = _numeric_leaves(old["metrics"]), _numeric_leaves(new["metrics"])
    gated = sorted(set(o) & set(n) & {p for p in o if _gated(p)})
    for path in gated:
        ov, nv = o[path], n[path]
        if ov == 0.0:
            grew = nv > 0.0
            rel = float("inf") if grew else 0.0
        else:
            rel = 100.0 * (nv - ov) / abs(ov)
            grew = rel > tol_pct
        if grew and (ov != 0.0 or nv > 0.0):
            bad.append(f"metric {path}: {ov:g} -> {nv:g} "
                       f"(+{rel:.1f}% > {tol_pct:g}% tol)")
    print(f"compare-ledger: {old['name']} {old_path} -> {new_path}: "
          f"{len(gated)} cost metric(s) + {len(old['gates'])} gate(s) "
          f"checked, {len(bad)} regression(s)")
    for line in bad:
        print(f"  REGRESSION {line}")
    return bad


def _compare_main(argv) -> None:
    from repro.obs import flag_value
    i = argv.index("--compare-ledger")
    paths = [a for a in argv[i + 1:i + 3] if not a.startswith("--")]
    if len(paths) != 2:
        raise SystemExit("--compare-ledger needs OLD.json NEW.json")
    tol = float(flag_value(argv, "--tol") or 2.0)
    sys.exit(1 if compare_ledgers(paths[0], paths[1], tol) else 0)


def main() -> None:
    if "--compare-ledger" in sys.argv:
        _compare_main(sys.argv)     # light path: no benchmark imports
        return
    from repro.common.compile_cache import use_compile_cache
    use_compile_cache()
    t0 = time.time()
    from benchmarks import fig6_utilization, kernel_bench, roofline, \
        table2_comparison

    print("=" * 72)
    fig6 = fig6_utilization.run()
    print("\n" + "=" * 72)
    t2 = table2_comparison.run()
    print("\n" + "=" * 72)
    kb = kernel_bench.run()
    print("\n" + "=" * 72)
    roofline.run(mesh="single")
    print("\n" + "=" * 72)
    roofline.run(mesh="multi")
    print("\n" + "=" * 72)

    ok = (fig6["overall_util"] > 0.95
          and abs(t2["gops"] - 780.2) / 780.2 < 0.05
          and kb["max_err"] < 1e-2)
    print(f"\nbenchmarks completed in {time.time() - t0:.0f}s — "
          f"{'PASS' if ok else 'CHECK FAILURES ABOVE'}")
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
