"""End-to-end inference: reference vs fused execution path on B1_SMOKE,
at both precisions (fp32 and FIX8 int8).

Reports, per the EXPERIMENTS.md fusion tables:
  * wall clock for the reference and the fused (plan-routed) forward —
    CPU interpret-mode numbers, meaningful as a consistency check, not
    as TPU latency — for the fp32 model AND its FIX8-quantized twin;
  * kernel-launch counts (the paper's launch-overhead story: one MSA
    module used to be ``(1 + len(scales)) x 2`` attention launches, the
    fused plan issues exactly 1);
  * analytic HBM bytes per fused site from the fusion plan: activation
    traffic (the TMP dataflow's single-load discipline) plus per-launch
    weight reads, where FIX8 cuts weights 4x and the fused-site input
    activations another 4x.

Asserts (CI smoke gate):
  * fused forward matches reference within 1e-3 (fp) / BIT-EXACT at
    batch 1 (int8 vs the int8 reference path — through the full
    producer-epilogue chain);
  * >= 2x analytic HBM-byte reduction on every fused MBConv/MSA site;
  * msa() launch count drops to 1 per module at fp (n_branches at int8:
    attention core + one grouped-aggregation launch per scale);
  * the int8 plan fuses every site the fp plan fuses (zero
    ``"quantized"`` fallbacks) on B1_SMOKE and full B1;
  * int8-fused analytic HBM bytes (act + weights) <= 0.6x fp-fused at
    B1 @224;
  * int8 DATAFLOW gate: every fused int8 conv site's input arrives
    quantized from its producer's epilogue (q_in — the delivered
    1 byte/element fused-site input), and the delivered activation
    bytes measured from the executed program's epilogue dtypes equal
    the analytic steady-state accounting within exactly the residual-fp
    correction;
  * drift gate: B1 @224 stays at ``core.fusion.
    EXPECTED_B1_SUPERSITE_LAUNCHES`` (= 19) fused launches at fp and
    ``EXPECTED_B1_SUPERSITE_LAUNCHES_INT8`` (= 26) at int8 — a
    lowering/planner/registry change that moves either must update the
    expectation explicitly.

Everything here runs through the program IR (``core.program.lower`` /
``execute``) and the generic registry planner
(``core.fusion.plan_program``) — the same single lowering the cycle
model and fig6/table2 consume.

  * model-drift audit (``repro.obs.profile``): profiled per-site
    execution of full B1 @224 at BOTH precisions, reconciled against
    ``site_breakdown`` predicted cycles — every site covered, every
    drift ratio finite (absolute ratios are meaningless on the CPU
    interpreter; coverage and finiteness are the gate, the per-site
    relative profile is the signal).

    PYTHONPATH=src python -m benchmarks.e2e_latency [--json OUT]
"""
from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp

from benchmarks.kernel_bench import _time
from repro.core.efficientvit import B1, B1_SMOKE, init_efficientvit
from repro.core.fusion import (
    EXPECTED_B1_SUPERSITE_LAUNCHES, EXPECTED_B1_SUPERSITE_LAUNCHES_INT8,
    launch_counts, plan_program, plan_report)
from repro.core.program import execute, lower
from repro.core.quantization import quantize_efficientvit
from repro.obs import bench_result, flag_value, write_result
from repro.obs.profile import drift_report, profile_execute


def _delivered_gate(plan, rows):
    """The int8-dataflow acceptance check: per fused int8 conv site the
    input boundary is 1 byte/element (producer-emitted) and the
    delivered bytes (epilogue dtypes of the executed program) equal the
    analytic steady-state within exactly the residual-fp correction.

    Super-site members follow the chain accounting instead: the first
    member delivers the chain's entry boundary only, interior members
    deliver ZERO (their boundaries never leave VMEM), and the last
    member delivers the exit boundary its epilogue writes."""
    groups = getattr(plan, "groups", None) or {}
    first_of = {g.members[0] for g in groups.values()}
    last_of = {g.members[-1] for g in groups.values()}
    checked = 0
    for r in rows:
        if not (r["fused"] and r["kind"] in ("mbconv", "dsconv")
                and r["precision"] == "int8"):
            continue
        assert r["q_in"], \
            f"{r['site']}: fused int8 input not producer-emitted"
        B, H, W, C, _, F, stride = plan.get(r["site"]).shape
        outn = (B * (H // stride) * (W // stride) * F
                if r["kind"] == "mbconv" else B * H * W * F)
        ep = r["epilogue"]
        if r.get("group"):
            want = 0
            if r["site"] in first_of:
                want += B * H * W * C * (1 if r["q_in"] else 4)
            if r["site"] in last_of:
                want += (outn * 4 if ep is None or not ep.emits_q
                         else outn * (1 + (4 if ep.keeps_fp else 0)))
            assert r["hbm_delivered"] == want, r["site"]
        else:
            corr = (0 if ep is None or not ep.emits_q
                    else outn if ep.keeps_fp else -3 * outn)
            assert r["hbm_delivered"] == r["hbm_fused"] + corr, r["site"]
        checked += 1
    assert checked, "no fused int8 conv sites to gate"
    return checked


def _print_rows(rows):
    print(f"{'site':<16} {'kind':<7} {'route':<9} {'prec':<5} "
          f"{'HBM unfused':>12} {'HBM fused':>10} {'saved':>6} "
          f"{'weights':>9} {'launches':>9}")
    for r in rows:
        route = "fused" if r["fused"] else f"ref({r['reason']})"
        print(f"{r['site']:<16} {r['kind']:<7} {route:<9} "
              f"{r['precision']:<5} "
              f"{r['hbm_unfused'] / 1e6:>10.2f}MB "
              f"{r['hbm_fused'] / 1e6:>8.2f}MB "
              f"{r['saving_x']:>5.1f}x "
              f"{r['hbm_w'] / 1e6:>7.2f}MB "
              f"{r['launches_ref']:>4} ->{r['launches_fused']:>3}")


def drift_section(program, params, qparams, *, image_size: int):
    """Model-drift audit: profiled per-site execution (reference
    interpreter, eager, ``block_until_ready`` per site) vs the cycle
    model, at BOTH precisions.  Coverage + finiteness are the gate.

    The int8 reference interpreter is ~150x slower per eager pass than
    fp on the CPU backend, so it profiles with a single unwarmed
    repeat — absolute numbers are interpreter artifacts either way.
    """
    key = jax.random.PRNGKey(1)
    x = jax.random.normal(key, (1, image_size, image_size, 3))
    reports = {}
    for prec, tree, repeats, warmup in (("fp", params, 3, 1),
                                        ("int8", qparams, 1, 0)):
        prof = profile_execute(program, tree, x, plan=None,
                               repeats=repeats, warmup=warmup)
        rep = drift_report(program, prof, plan=None, precision=prec)
        assert len(rep.rows) == len(program.sites), \
            (len(rep.rows), len(program.sites))
        assert rep.finite(), \
            [r["site"] for r in rep.rows if not (r["predicted_ms"] > 0)]
        reports[prec] = rep
        print(f"\n## model drift — {prec}, {len(rep.rows)} sites, "
              f"{repeats} repeat(s) (CPU interpreter: relative profile "
              f"only)")
        print(rep.table())
    return reports


def run(batch: int = 2, autotune: bool = True,
        json_out: str | None = None):
    cfg = B1_SMOKE
    key = jax.random.PRNGKey(0)
    params = init_efficientvit(key, cfg)
    x = jax.random.normal(key, (batch, cfg.image_size, cfg.image_size, 3))

    program = lower(cfg, batch=batch)        # ONE lowering for everything
    t0 = time.perf_counter()
    plan = plan_program(program, params, autotune=autotune)
    t_plan = time.perf_counter() - t0

    ref_fwd = jax.jit(lambda p, x: execute(program, p, x))
    fus_fwd = jax.jit(lambda p, x: execute(program, p, x, plan=plan))

    ref = ref_fwd(params, x)
    fus = fus_fwd(params, x)
    err = float(jnp.max(jnp.abs(ref - fus)))
    assert err < 1e-3, f"fused path diverged: max|Δ| = {err:.2e}"

    t_ref = _time(ref_fwd, params, x)
    t_fus = _time(fus_fwd, params, x)

    rows = plan_report(plan)
    lc = launch_counts(plan)

    print(f"# e2e inference — {cfg.name} @{cfg.image_size}px, batch={batch}")
    print(f"plan: {plan.n_fused()}/{len(rows)} sites fused "
          f"(built+autotuned in {t_plan:.1f}s, cached on disk)")
    print(f"numerics: max|Δ| fused vs reference = {err:.2e}")
    print(f"wall clock (CPU interpret, not a TPU number): "
          f"reference {t_ref * 1e3:.0f} ms, fused {t_fus * 1e3:.0f} ms")
    print(f"kernel launches on fusible sites: {lc['reference']} -> "
          f"{lc['fused']}")
    print()
    _print_rows(rows)

    for r in rows:
        if r["fused"] and r["kind"] in ("mbconv", "msa"):
            assert r["saving_x"] >= 2.0, (r["site"], r["saving_x"])
        if r["fused"] and r["kind"] == "msa":
            assert r["launches_fused"] == 1, r
    total_u = sum(r["hbm_unfused"] for r in rows)
    total_f = sum(r["hbm_fused"] for r in rows)
    print(f"\ntotal analytic HBM activation bytes on fusible sites: "
          f"{total_u / 1e6:.1f} MB -> {total_f / 1e6:.1f} MB "
          f"({total_u / total_f:.1f}x)")

    # ---------------------------------------------------------------
    # FIX8: quantized model through the int8 fused path
    # ---------------------------------------------------------------
    qparams = quantize_efficientvit(params)
    qplan = plan_program(program, qparams, autotune=autotune)
    assert not any(d.reason == "quantized" for d in qplan.decisions.values())
    # >= because int8 may fuse MORE sites than fp (4x smaller VMEM tiles)
    assert qplan.n_fused() >= plan.n_fused(), \
        "int8 plan fuses fewer sites than fp"

    # batch 1 parity runs on a batch-1 program so the producer-epilogue
    # chain (per-batch-element scales) is bit-identical to the reference
    program1 = lower(cfg, batch=1)
    qplan1 = plan_program(program1, qparams, autotune=autotune)
    assert qplan1.epilogues, "int8 plan assigned no producer epilogues"
    qref_fwd = jax.jit(lambda p, x: execute(program1, p, x))
    qfus_fwd = jax.jit(lambda p, x: execute(program1, p, x, plan=qplan1))
    x1 = x[:1]                      # batch 1: in-kernel requant scales are
    qref = qref_fwd(qparams, x1)    # bit-identical to the reference chain
    qfus = qfus_fwd(qparams, x1)
    qerr = float(jnp.max(jnp.abs(qref - qfus)))
    argmax_ok = bool((jnp.argmax(qref, -1) == jnp.argmax(qfus, -1)).all())
    assert qerr == 0.0, \
        f"int8 epilogue chain not bit-exact at batch 1: max|Δ| = {qerr:.2e}"
    assert argmax_ok, "int8 fused changed the top-1 label"

    t_qref = _time(qref_fwd, qparams, x1)
    t_qfus = _time(qfus_fwd, qparams, x1)
    qrows = plan_report(qplan)

    print(f"\n# FIX8 — {cfg.name}, int8 megakernels (batch=1 parity)")
    print(f"plan: {qplan.n_fused()}/{len(qrows)} sites fused int8 "
          f"(zero 'quantized' fallbacks)")
    print(f"numerics: max|Δ| int8-fused vs int8-reference = {qerr:.2e} "
          f"(bit-exact through the producer-epilogue chain), "
          f"argmax bit-exact = {argmax_ok}")
    print(f"wall clock (CPU interpret): int8 reference {t_qref * 1e3:.0f} ms, "
          f"int8 fused {t_qfus * 1e3:.0f} ms")
    print()
    _print_rows(qrows)

    # the int8 dataflow: delivered = analytic within the residual-fp
    # correction, on the SMOKE plan (batch 2) and the batch-1 plan
    n_gated = _delivered_gate(qplan, qrows)
    n_gated += _delivered_gate(qplan1, plan_report(qplan1))
    q_deliv = sum(r["hbm_delivered"] for r in qrows)
    q_ana = sum(r["hbm_fused"] for r in qrows)
    print(f"\nint8 dataflow: {n_gated} fused conv sites gated; delivered "
          f"act bytes {q_deliv / 1e6:.2f} MB vs analytic steady-state "
          f"{q_ana / 1e6:.2f} MB (residual-fp correction only)")

    # ---------------------------------------------------------------
    # analytic fp-fused vs int8-fused at full B1 @224 (act + weights)
    # + the launch-count drift gate: the super-site grouping pass
    # collapses S1's and S2's conv chains, so B1 lands on 19 fp /
    # 26 int8 fused launches, and any change that moves either must
    # update core.fusion.EXPECTED_B1_SUPERSITE_LAUNCHES* explicitly.
    # ---------------------------------------------------------------
    b1_program = lower(B1, batch=1)
    b1_params = init_efficientvit(key, B1)
    b1_fp_plan = plan_program(b1_program, b1_params, autotune=False)
    b1_q_plan = plan_program(b1_program, quantize_efficientvit(b1_params),
                             autotune=False)
    for p_, want in ((b1_fp_plan, EXPECTED_B1_SUPERSITE_LAUNCHES),
                     (b1_q_plan, EXPECTED_B1_SUPERSITE_LAUNCHES_INT8)):
        lc_b1 = launch_counts(p_)
        assert lc_b1["fused"] == want, (lc_b1, want)
        assert p_.groups, "B1 plan formed no super-site groups"
    b1_fp = plan_report(b1_fp_plan)
    b1_q = plan_report(b1_q_plan)
    assert all(r["fused"] for r in b1_q), \
        {r["site"]: r["reason"] for r in b1_q if not r["fused"]}
    _delivered_gate(b1_q_plan, b1_q)    # full-B1 int8 dataflow coverage
    fp_tot = sum(r["hbm_total"] for r in b1_fp)
    q_tot = sum(r["hbm_total"] for r in b1_q)
    ratio = q_tot / fp_tot
    print(f"\nB1 @224 batch 1, analytic fused-site HBM (activations + "
          f"weights per launch):")
    print(f"  fp-fused   {fp_tot / 1e6:6.1f} MB "
          f"(act {sum(r['hbm_fused'] for r in b1_fp) / 1e6:.1f} + "
          f"w {sum(r['hbm_w'] for r in b1_fp) / 1e6:.1f})")
    print(f"  int8-fused {q_tot / 1e6:6.1f} MB "
          f"(act {sum(r['hbm_fused'] for r in b1_q) / 1e6:.1f} + "
          f"w {sum(r['hbm_w'] for r in b1_q) / 1e6:.1f})  "
          f"= {ratio:.2f}x of fp-fused")
    assert ratio <= 0.6, f"int8-fused HBM ratio {ratio:.3f} > 0.6"

    # single-load weight residency: each site's weights counted ONCE
    # per forward — the B1 int8 weight total is the paper's ~4.4 MB
    # "read-once" budget; super-site chains read their members' packed
    # weights in one resident VMEM block per launch
    q_w = sum(r["hbm_w"] for r in b1_q)
    assert abs(q_w - 4.4e6) / 4.4e6 < 0.05, \
        f"B1 int8 delivered weight HBM {q_w / 1e6:.2f} MB != ~4.4 MB"
    q_launches = launch_counts(b1_q_plan)["fused"]
    print(f"weights read once: {q_w / 1e6:.2f} MB int8 across "
          f"{q_launches} launches "
          f"({len(b1_q_plan.groups)} super-site group(s): "
          f"{ {g.name: list(g.members) for g in b1_q_plan.groups.values()} })")

    # B1 @384 fp: the banded super-site chain retires the lone-kernel
    # VMEM demotions — S1's whole-map fp tiles didn't fit at 384, the
    # grouped spatially-banded chain does, so the plan carries ZERO
    # "vmem" fallbacks and still lands on the grouped launch count
    b1_384 = lower(B1, batch=1, image_size=384)
    plan_384 = plan_program(b1_384, b1_params, autotune=False)
    vmem_384 = [d.name for d in plan_384.decisions.values()
                if d.reason == "vmem"]
    assert vmem_384 == [], f"B1@384 fp still demotes {vmem_384}"
    assert launch_counts(plan_384)["fused"] \
        == EXPECTED_B1_SUPERSITE_LAUNCHES
    print(f"B1 @384 fp: zero VMEM demotions (banded super-sites), "
          f"{launch_counts(plan_384)['fused']} fused launches, groups "
          f"{ {g.name: dict(g.blocks) for g in plan_384.groups.values()} }")

    # ---------------------------------------------------------------
    # measured vs predicted: profiled B1 @224 at both precisions
    # ---------------------------------------------------------------
    drift = drift_section(b1_program, b1_params,
                          quantize_efficientvit(b1_params),
                          image_size=B1.image_size)

    out = {"max_err": err, "t_ref": t_ref, "t_fused": t_fus,
           "launches": lc, "hbm_saving_x": total_u / total_f,
           "int8_max_err": qerr, "int8_argmax_exact": argmax_ok,
           "t_int8_ref": t_qref, "t_int8_fused": t_qfus,
           "int8_vs_fp_hbm_ratio": ratio,
           "b1_fused_launches": launch_counts(b1_fp_plan)["fused"],
           "b1_int8_fused_launches": q_launches,
           "b1_int8_weight_mb": q_w / 1e6,
           "b1_384_vmem_demotions": len(vmem_384),
           "drift": {p: r.to_dict() for p, r in drift.items()}}
    if json_out is not None:
        doc = bench_result(
            "e2e_latency",
            config=dict(cfg=cfg.name, batch=batch, autotune=autotune,
                        drift_cfg=B1.name, drift_image_size=B1.image_size),
            metrics=out,
            gates=dict(
                fp_parity=err < 1e-3,
                int8_bit_exact=(qerr == 0.0 and argmax_ok),
                b1_fp_launches=True,     # asserted above (== 19)
                b1_int8_launches=True,   # asserted above (== 26)
                int8_hbm_ratio=ratio <= 0.6,
                weights_read_once=True,  # asserted above (~4.4 MB int8)
                no_vmem_demotions_at_384=True,   # asserted above
                drift_all_sites=all(
                    len(r.rows) == len(b1_program.sites)
                    for r in drift.values()),
                drift_finite=all(r.finite() for r in drift.values())))
        write_result(json_out, doc)
        print(f"\nledger written to {json_out}")
    return out


def main():
    from repro.common.compile_cache import use_compile_cache
    use_compile_cache()
    run(json_out=flag_value(sys.argv[1:], "--json"))


if __name__ == "__main__":
    main()
