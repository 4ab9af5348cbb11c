#!/usr/bin/env python3
"""Serve EfficientViT-B1 at 224 px on a TPU through the normal entry
points, and check what comes back.

    python chip_smoke.py              # one chip: fp32 and int8 serving
    python chip_smoke.py --chips 4    # four chips: batch-sharded serving
                                      # against one device, nothing else

One chip: for each precision a ``VisionEngine`` with batch buckets
(1, 2, 4, 8) is warmed at 224 px, then 24 seeded requests are served
through ``scheduler().serve`` in waves of 8, 8, 4, 2, 1, 1 (drained
one after another, so every bucket dispatches).  The run fails if the
platform is not ``tpu``, a plan runs the Pallas interpreter or leaves a
fusible site unfused, the serving stack degraded, rebuilt or retried
anything, a request did not complete, a logit is not finite, or the
logits disagree with the plain reference (``execute(..., plan=None)``)
beyond ``TOL`` (argued at ``check_close``).  int8 is compared with the
int8 reference three ways: its logits within 4x the distance a one-ulp
input nudge moves the reference (the int8 model amplifies rounding), at
bucket 1 the int8 codes of every fused site and super-site against its
reference on the same input (``check_sites``), and every image's logits
bit for bit against the same image served alone.

The engines are traced, warmed and served, like the reference, under
``jax.default_matmul_precision("highest")``: at the chip's default
precision an fp32 matmul or convolution rounds its operands to
bfloat16, and that rounding (printed as a reading: the reference's
default-vs-"highest" gap) would be larger than the faults the check
has to see.

Four chips: the same waves served batch-sharded over four devices
(``VisionServeConfig(devices=...)``) and by a single-device engine in
this process, both under "highest"; fp must agree within ``TOL``, int8
bit for bit.

Everything runs in this one process.  Timings printed are smoke
timings of one cold process, compilation included, not benchmark
results.  The last line of stdout is ``{"ok": true, "device": {...}}``
and is printed only when every check passed.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

SIZE = 224
BUCKETS = (1, 2, 4, 8)
WAVES = (8, 8, 4, 2, 1, 1)          # 24 requests; every bucket dispatches
MUST_BE_ZERO = ("degraded", "pinned_fp", "executor_build_failed",
                "dispatch_failures", "retries")
TOL = 1e-4                          # relative to max |reference logit|
CODE_SHARE = 1e-3                   # int8 codes that may differ per site


class SmokeFailure(Exception):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def rel_err(a, b) -> float:
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def highest():
    import jax
    return jax.default_matmul_precision("highest")


def check_close(name: str, got, ref, tol: float = TOL) -> float:
    """Served logits against the reference, or against another
    compilation of the served path, relative to the reference's largest
    logit.

    fp, ``TOL``: under "highest" every product on both sides is
    fp32-accurate, so the paths differ only in summation order and FMA
    contraction: about 1e-7 relative per op, which compounds over B1's
    ~30 layers to 1e-6, a bound of 1e-5 at the outside.  ``TOL`` = 1e-4
    sits ten times above that.  The faults the fused kernels risk sit
    above it: a SAME anchor shifted by one pixel at a stride-2 site, a
    lost halo row of every super-site band, each moves B1@224's logits
    by 5.9e-3 relative or more (such mutations, run on the CPU) even
    after global pooling dilutes it.

    int8 is held to 4x the reference's own one-ulp sensitivity
    (``reference``) instead: a ceiling for gross faults only, since
    ``check_sites`` and the batch check are the fine ones.
    """
    err = rel_err(got, ref)
    log(f"  {name}: max|d|/max|ref| = {err:.3e} (tolerance {tol:.3e})")
    if not err <= tol:
        raise SmokeFailure(f"{name}: logits differ by {err:.3e} > {tol:.3e}")
    return err


def make_requests(images):
    from repro.serving.scheduler import Request
    return [Request(rid=i, image=img) for i, img in enumerate(images)]


def serve_waves(engine, images, waves=WAVES):
    """Serve ``images`` through one scheduler in ``waves``, each drained
    before the next arrives; returns (logits, requests)."""
    import numpy as np
    sched = engine.scheduler()
    reqs = make_requests(images)
    outs, i = [], 0
    for n in waves:
        outs.append(sched.serve(reqs[i:i + n]))
        i += n
    return np.concatenate(outs), reqs


def check_served(engine, reqs, logits, precision: str, size: int) -> None:
    import numpy as np
    from repro.core.fusion import launch_counts
    tel = engine.telemetry
    for b in engine.cache.buckets:
        ex = engine.cache.get(b, size)
        plan = ex.plan
        if plan is None or plan.interpret:
            raise SmokeFailure(f"{precision} bucket {b}: plan is "
                               f"{'absent' if plan is None else 'interpreted'}")
        unfused = [n for n, d in plan.decisions.items() if not d.fused]
        if unfused or len(plan.decisions) != len(ex.program.fusible()):
            raise SmokeFailure(f"{precision} bucket {b}: unfused sites "
                               f"{unfused}")
        stats = tel.bucket((b, size, engine.cache.precision))
        log(f"  bucket {b}: {launch_counts(plan)['fused']} fused launches, "
            f"{stats.dispatches} dispatches, {stats.samples} requests")
        if stats.dispatches == 0:
            raise SmokeFailure(f"{precision}: bucket {b} never dispatched")
    counters = {k: tel.counters.get(k, 0) for k in MUST_BE_ZERO}
    log(f"  counters: {counters}")
    if any(counters.values()):
        raise SmokeFailure(f"{precision}: serving moved off the fused "
                           f"path: {counters}")
    bad = [r.rid for r in reqs if r.status != "completed"]
    if bad:
        raise SmokeFailure(f"{precision}: requests {bad} did not complete")
    if not np.all(np.isfinite(logits)):
        raise SmokeFailure(f"{precision}: non-finite logits")


def reference(cfg, size, params, images):
    """Plain forward (no plan, no kernels) under "highest": ``(logits,
    gap, sensitivity)``.  ``gap`` is its relative distance from the same
    forward at the default precision, a reading of what the chip's
    default rounding costs.  ``sensitivity`` is how far its logits move
    when every input pixel moves by one ulp (the larger of x * (1 +
    2**-23) and x * (1 - 2**-24)): the distance at which two correct but
    differently rounded computations of this model may land."""
    import jax
    import numpy as np
    from repro.core.program import execute, lower
    program = lower(cfg, batch=len(images), image_size=size)
    x = jax.numpy.asarray(images)
    with highest():
        fwd = jax.jit(lambda p, v: execute(program, p, v))
        ref = jax.device_get(fwd(params, x))
        moved = [jax.device_get(fwd(params, jax.numpy.asarray(
            images * np.float32(1 + e)))) for e in (2 ** -23, -2 ** -24)]
    default = jax.jit(lambda p, v: execute(program, p, v))(params, x)
    return (ref, rel_err(jax.device_get(default), ref),
            max(rel_err(m, ref) for m in moved))


class SiteTap:
    """``execute(profile=...)`` hook that keeps every site's output (no
    barrier, so it runs under ``jit``)."""

    def __init__(self):
        self.out = {}

    def begin(self, site):
        pass

    def end(self, site, y):
        self.out[site.name] = y
        return y


def check_sites(engine, size: int, params, image) -> None:
    """int8 at bucket 1, site by site: each fused site, and each
    super-site chain, and its reference (the registry's ``ref``) take the
    same input, the fused path's own activation there, and their outputs
    are compared as int8 codes (an emitted ``QTensor``, or the per-image
    quantization of an fp output).

    The int8 model cannot be held closer at its logits: every boundary
    requantizes by a per-image absmax, so a code flipped by one rounding
    step moves everything downstream (``reference``'s sensitivity,
    about 2e-2 relative for B1's int8 logits).  Per site,
    the integer arithmetic is identical, and the fp32 epilogues around
    it (dequant, Hardswish, absmax, divide) differ by a few ulps, which
    flips a code only where a value sits within those ulps of a rounding
    boundary: at most ~2 * 1e-6 * 127, under 3e-4 of the codes, by one.
    The faults the kernels risk reach at least a whole row of a map (a
    lost halo row: 1/112 of the codes at the stem, 1/7 at S4) or most of
    it (a shifted SAME anchor, another image's scale).  So at most
    ``CODE_SHARE`` of a site's codes may differ, and by one step each.
    """
    import jax
    import numpy as np
    from repro.core.program import SuperSite, execute, params_at
    from repro.core.quantization import QTensor, act_fp, quantize_act
    from repro.kernels.registry import get_kernel
    ex = engine.cache.get(1, size)
    program, plan = ex.program, ex.plan
    fused_sites = [st for st in program.sites
                   if st.name in plan.decisions
                   and plan.decisions[st.name].fused]

    def codes(y):
        return y if isinstance(y, QTensor) else quantize_act(y)

    def fp_view(y):
        if not isinstance(y, QTensor) or y.fp is not None:
            return act_fp(y)
        scale = jax.numpy.reshape(y.scale, (-1,) + (1,) * (y.q.ndim - 1))
        return y.q.astype(jax.numpy.float32) * scale

    def pairs(p, v):
        tap = SiteTap()
        execute(program, p, v, plan=plan, profile=tap)
        inputs, prev = {}, v
        for st in program.sites:
            inputs[st.name], prev = prev, tap.out[st.name]
        out = {}
        for st in fused_sites:
            d = plan.decisions[st.name]
            ep = plan.epilogues.get(st.name)
            ep = ep if (ep is not None and ep.emits_q
                        and not st.residual) else None
            impl = get_kernel(st.kind, d.precision)
            sp = params_at(p, st.param_path)
            got = impl.apply(sp, inputs[st.name], st, d,
                             interpret=plan.interpret,
                             epilogue=ep)
            want = impl.ref(sp, inputs[st.name], st, epilogue=ep)
            out[st.name] = (codes(got), codes(want))
        for g in plan.groups.values():       # super-sites, whole chains
            sup = SuperSite.of(program, g.members, name=g.name)
            impl = get_kernel("supersite", g.precision)
            ep = plan.epilogues.get(g.members[-1])
            x_in = inputs[g.members[0]]
            got = impl.apply(p, x_in, sup, g, interpret=plan.interpret,
                             epilogue=ep)
            want = impl.ref(p, fp_view(x_in), sup, epilogue=ep)
            out[g.name] = (codes(got), codes(want))
        return out

    with highest():
        res = jax.device_get(jax.jit(pairs)(params, jax.numpy.asarray(image)))
    worst = (0.0, "")
    for name, (got, want) in res.items():
        q, wq = np.asarray(got.q, np.int32), np.asarray(want.q, np.int32)
        share = float(np.mean(q != wq))
        step = int(np.max(np.abs(q - wq)))
        scale = float(np.max(np.abs(np.asarray(got.scale, np.float64)
                                    / np.asarray(want.scale, np.float64)
                                    - 1.0)))
        worst = max(worst, (share, name))
        if share > CODE_SHARE or step > 1 or scale > 1e-5:
            raise SmokeFailure(
                f"int8 site {name}: {share:.2e} of its codes differ from "
                f"the reference (limit {CODE_SHARE:.0e}), by up to {step}, "
                f"scale {scale:.1e} relative")
    log(f"  int8 bucket 1: {len(res)} fused sites and super-sites against "
        f"their reference on the same input; largest share of differing codes "
        f"{worst[0]:.2e} ({worst[1] or 'none'}; limit {CODE_SHARE:.0e})")


def build(seed: int, cfg, size: int, n: int):
    """Params from ``seed`` (fp32 and their int8 quantization) and ``n``
    seeded images."""
    import jax
    import numpy as np
    from repro.core.efficientvit import init_efficientvit
    from repro.core.quantization import quantize_efficientvit
    params = init_efficientvit(jax.random.PRNGKey(seed), cfg)
    trees = {"fp": params, "int8": quantize_efficientvit(params)}
    images = np.asarray(jax.random.normal(jax.random.PRNGKey(seed + 1),
                                          (n, size, size, 3)), np.float32)
    return trees, images


def engine_for(tree, cfg, precision, devices=None):
    from repro.serving.vision import VisionEngine, VisionServeConfig
    return VisionEngine(tree, cfg, VisionServeConfig(
        microbatch=max(BUCKETS), buckets=BUCKETS, precision=precision,
        autotune=False, devices=devices))


def one_chip(cfg, size: int, seed: int) -> None:
    import numpy as np
    n = sum(WAVES)
    trees, images = build(seed, cfg, size, n)
    for precision in ("fp", "int8"):
        tree = trees[precision]
        log(f"[{precision}]")
        t0 = time.perf_counter()
        ref, gap, sens = reference(cfg, size, tree, images)
        t1 = time.perf_counter()
        with highest():
            engine = engine_for(tree, cfg, precision).warmup()
            t2 = time.perf_counter()
            logits, reqs = serve_waves(engine, images)
        t3 = time.perf_counter()
        log(f"  smoke timings: reference {t1 - t0:.1f} s, engine build + "
            f"warm-up of {len(BUCKETS)} buckets {t2 - t1:.1f} s, serving "
            f"{n} requests {t3 - t2:.2f} s")
        log(f"  readings: the reference at the default precision is "
            f"{gap:.3e} from itself under \"highest\"; a one-ulp input "
            f"nudge moves it {sens:.3e}")
        check_served(engine, reqs, logits, precision, size)
        if precision == "fp":
            check_close("fp fused vs fp reference", logits, ref)
            continue
        check_close("int8 fused vs int8 reference", logits, ref, 4 * sens)
        check_sites(engine, size, tree, images[:1])
        # per-image scales: a batch of 8 must give each image exactly
        # what it gets alone (a scale from another image breaks this)
        with highest():
            alone, reqs = serve_waves(engine, images, waves=(1,) * n)
        check_served(engine, reqs, alone, precision, size)
        same = bool(np.array_equal(alone, logits))
        log(f"  int8 served in buckets 8/4/2/1 vs alone at bucket 1: "
            f"bit-exact {same}")
        if not same:
            raise SmokeFailure("int8 logits depend on the batch they are "
                               "served in")


def four_chips(cfg, size: int, seed: int, devices) -> None:
    import numpy as np
    n = sum(WAVES)
    trees, images = build(seed, cfg, size, n)
    for precision in ("fp", "int8"):
        log(f"[{precision}, sharded over {len(devices)} devices]")
        tree = trees[precision]
        t0 = time.perf_counter()
        with highest():
            sharded = engine_for(tree, cfg, precision,
                                 devices=tuple(devices)).warmup()
            single = engine_for(tree, cfg, precision).warmup()
            t1 = time.perf_counter()
            got, reqs = serve_waves(sharded, images)
            want, reqs1 = serve_waves(single, images)
        t2 = time.perf_counter()
        log(f"  smoke timings: build + warm-up {t1 - t0:.1f} s, serving "
            f"twice {t2 - t1:.2f} s")
        check_served(sharded, reqs, got, precision, size)
        check_served(single, reqs1, want, precision, size)
        per_dev = {d: s.dispatches
                   for d, s in sorted(sharded.telemetry.devices.items())}
        log(f"  dispatches per device: {per_dev}")
        if len(per_dev) < len(devices):
            raise SmokeFailure(f"only devices {sorted(per_dev)} served")
        exact = bool(np.array_equal(got, want))
        log(f"  sharded vs single device: bit-exact {exact}")
        if precision == "int8":
            log(f"  int8 sharded vs single device: max|d|/max|ref| = "
                f"{rel_err(got, want):.3e}")
            if not exact:
                raise SmokeFailure("int8 sharded logits are not bit-exact "
                                   "against one device")
        else:
            check_close("fp sharded vs single device", got, want)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: {SRC / 'repro'} not found; run from a checkout "
              f"of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: JAX found no devices: {e}", file=sys.stderr)
        return 2
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform} "
              f"({dev.device_kind}). It never runs on another backend.",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2

    from repro.common.compile_cache import use_compile_cache
    from repro.core.efficientvit import B1
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"compile cache {use_compile_cache()}")
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            four_chips(B1, SIZE, args.seed, devices[:4])
        else:
            one_chip(B1, SIZE, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log(f"smoke wall time {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
