#!/usr/bin/env python3
"""Read the control of a configuration's ``correct`` limit.

    python3 bench/control.py --config <name> --seeds 1 2 3 [--pool 64]

For each seed: the configuration's weights and image pool as a run of
that seed makes them, the plain reference at the configuration's stated
arithmetic, and the same reference at the control's (the configuration
file's ``control``: the next precision down, ``bf16x3`` products for
float32 under "highest", 4-bit for int8).  Prints ``logit_err`` of the
control against the stated reference over the pool: the control's
reading, which the limit must sit below.  On the chip this runs at the
cell's own size; the benchmark's runs do not call it.
"""
import argparse
import json
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))

from benchlib import correct, spec, system  # noqa: E402


def readings(cfg: dict, ref, seeds, pool: int) -> list:
    out = []
    for seed in seeds:
        params, images = system.make_inputs(ref, cfg, pool, seed)
        idx = range(pool)
        want = correct.reference_logits(ref, cfg, params, images, idx)
        got = correct.reference_logits(ref, cfg, params, images, idx,
                                       **cfg["control"])
        out.append(correct.worst(got.items(), want))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--pool", type=int, default=64)
    args = ap.parse_args(argv)
    cfg = spec.load_json(BENCH_DIR / "configs" / f"{args.config}.json")
    ref = spec.load_module(BENCH_DIR / "configs" / f"{cfg['reference']}.py",
                           "bench_ref")
    import jax
    jax.config.update("jax_default_matmul_precision",
                      cfg["matmul_precision"])
    dev = jax.devices()[0]
    t = time.perf_counter()
    errs = readings(cfg, ref, args.seeds, args.pool)
    for seed, e in zip(args.seeds, errs):
        print(f"control {cfg['control']} seed {seed}: logit_err {e!r}")
    print(json.dumps({"config": args.config, "control": cfg["control"],
                      "limit": cfg["correct"]["logit_err"],
                      "readings": dict(zip(map(str, args.seeds), errs)),
                      "device": dev.device_kind,
                      "seconds": time.perf_counter() - t}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
