"""Program IR + kernel registry: one lowering, consumed everywhere.

The contract under test: ``core.program.lower`` is the single source of
truth — the fusion plan's site set, the layer manifest's MACs, and the
analytic HBM accounting all derive from the same ``Site`` sequence and
therefore cannot drift from what ``execute`` actually runs.  Plus the
launch-count drift gate: EfficientViT-B1 @224 fuses to exactly
``core.fusion.EXPECTED_B1_SUPERSITE_LAUNCHES`` (fp) / ``_INT8`` launches
until someone updates that expectation explicitly.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

from repro.core.efficientvit import (
    B1, B1_SMOKE, efficientvit, init_efficientvit, total_macs)
from repro.core.fusion import (
    EXPECTED_B1_SUPERSITE_LAUNCHES, EXPECTED_B1_SUPERSITE_LAUNCHES_INT8,
    launch_counts, plan_program, plan_report, site_traffic)
from repro.core.program import FUSIBLE_KINDS, execute, lower, manifest, params_at
from repro.core.quantization import quantize_efficientvit
from repro.kernels import registry

# Hand-written manifest totals of the pre-IR walk, pinned: the
# IR-derived manifest must reproduce them exactly.
LEGACY_TOTAL_MACS = {"B1": 518_963_712, "B1_SMOKE": 2_038_080}
LEGACY_N_RECORDS = {"B1": 90, "B1_SMOKE": 36}


# ---------------------------------------------------------------------------
# IR consistency: one lowering feeds plan, manifest, accounting
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cfg", [B1, B1_SMOKE], ids=["b1", "smoke"])
def test_program_sites_match_plan_keys(cfg, tmp_autotune_cache):
    """Fusible site set == plan_program decision keys (both precisions)."""
    program = lower(cfg, batch=1)
    params = init_efficientvit(jax.random.PRNGKey(0), cfg)
    ir_names = [s.name for s in program.fusible()]
    assert len(set(ir_names)) == len(ir_names)      # unique
    for tree in (params, quantize_efficientvit(params)):
        plan = plan_program(program, tree, autotune=False)
        assert list(plan.decisions) == ir_names
        for s in program.fusible():
            d = plan.get(s.name)
            assert d.kind == s.kind and s.kind in FUSIBLE_KINDS


@pytest.mark.parametrize("name,cfg", [("B1", B1), ("B1_SMOKE", B1_SMOKE)])
def test_ir_manifest_matches_legacy(name, cfg):
    """total_macs / record count from the IR == the legacy manifest."""
    assert total_macs(cfg) == LEGACY_TOTAL_MACS[name]
    records = manifest(lower(cfg))
    assert len(records) == LEGACY_N_RECORDS[name]


def test_param_paths_resolve():
    """Every site's param_path indexes a real subtree of the init tree."""
    params = init_efficientvit(jax.random.PRNGKey(1), B1_SMOKE)
    for site in lower(B1_SMOKE).sites:
        if site.param_path:
            assert params_at(params, site.param_path) is not None


@pytest.mark.parametrize("precision", ["fp", "int8"])
def test_plan_report_matches_site_traffic(precision, tmp_autotune_cache):
    """plan_report HBM totals == registry-side accounting from the IR.

    The decisions' frozen shape tuples and the Program's site geometry
    are two derivations of the same numbers; they must agree per site
    for both precisions."""
    program = lower(B1_SMOKE, batch=1)
    params = init_efficientvit(jax.random.PRNGKey(2), B1_SMOKE)
    if precision == "int8":
        params = quantize_efficientvit(params)
    plan = plan_program(program, params, autotune=False)
    rows = {r["site"]: r for r in plan_report(plan)}
    # the delivered column reads epilogues off the ANNOTATED program
    # (the executor-cache view) — plus the producer's epilogue for q_in
    annotated = program.with_epilogues(plan)
    prev = {cur.name: prv for prv, cur in
            zip(annotated.sites, annotated.sites[1:])}
    for site in annotated.fusible():
        d = plan.get(site.name)
        q_in = prev[site.name].epilogue.emits_q
        want = site_traffic(site, precision=d.precision, q_in=q_in)
        got = rows[site.name]
        for k in ("hbm_unfused", "hbm_w", "launches_ref"):
            assert got[k] == want[k], (site.name, k)
        if got["fused"]:
            assert got["hbm_fused"] == want["hbm_fused"]
            assert got["hbm_delivered"] == want["hbm_delivered"]
            assert got["launches_fused"] == want["launches_fused"]


def test_execute_is_the_forward(tmp_autotune_cache):
    """efficientvit() and raw execute() are the same function,
    and the plan-routed program agrees with the reference program."""
    key = jax.random.PRNGKey(3)
    params = init_efficientvit(key, B1_SMOKE)
    x = jax.random.normal(key, (2, 64, 64, 3))
    program = lower(B1_SMOKE, batch=2, image_size=64)
    ref = execute(program, params, x)
    shim = efficientvit(params, x, B1_SMOKE)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(shim))
    plan = plan_program(program, params, autotune=False)
    fus = execute(program, params, x, plan=plan)
    assert_allclose(np.asarray(fus), np.asarray(ref), rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# drift gate (CI): B1 @224 launch counts are pinned
# ---------------------------------------------------------------------------

def test_b1_fused_launch_drift_gate(tmp_autotune_cache):
    """19 fused launches at B1/224 fp and 26 at int8: super-site
    grouping collapses the S1 pair (-1) and the S2 triple (-2) of B1's
    22 fusible sites into one launch each at both precisions (the
    grouped aggregation kernel adds one launch per scale per fused MSA
    module at int8).  If a lowering or planner change moves any of
    these, update EXPECTED_B1_SUPERSITE_LAUNCHES / _INT8 and the
    EXPERIMENTS.md narrative explicitly — this test failing is the
    drift alarm, not an inconvenience to silence."""
    program = lower(B1, batch=1)
    assert len(program.fusible()) == 22
    params = init_efficientvit(jax.random.PRNGKey(4), B1)
    expected = {"fp": EXPECTED_B1_SUPERSITE_LAUNCHES,
                "int8": EXPECTED_B1_SUPERSITE_LAUNCHES_INT8}
    for prec, tree in (("fp", params),
                       ("int8", quantize_efficientvit(params))):
        plan = plan_program(program, tree, autotune=False)
        lc = launch_counts(plan)
        assert lc["fused"] == expected[prec], (prec, lc)
        assert lc["reference"] > lc["fused"]
        assert {g.name: list(g.members) for g in plan.groups.values()} \
            == {"S1.ss0": ["S1.mb0", "S1.mb1"],
                "S2.ss0": ["S2.mb0", "S2.mb1", "S2.mb2"]}


# ---------------------------------------------------------------------------
# kernel registry
# ---------------------------------------------------------------------------

def test_registry_builtin_registrations():
    have = registry.available()
    for kind in ("dsconv", "mbconv", "msa"):
        for prec in ("fp", "int8"):
            assert (kind, prec) in have
            impl = registry.get_kernel(kind, prec)
            assert impl.kind == kind and impl.precision == prec
    # the grouped MSA aggregation kernel ships int8-only (the ROADMAP
    # worked example, landed); the probe resolves it without an fp twin
    assert ("group_agg", "int8") in have
    assert registry.get_probe("group_agg").precision == "int8"
    with pytest.raises(KeyError, match="no kernel registered"):
        registry.get_kernel("group_agg", "fp")
    # int8-dataflow capability flags on the FIX8 impls
    for kind in ("dsconv", "mbconv", "msa"):
        impl = registry.get_kernel(kind, "int8")
        assert impl.takes_q and impl.emits_q
        assert not registry.get_kernel(kind, "fp").emits_q


def test_registry_new_kernel_slots_in():
    """The worked example from the registry docstring: a new (kind,
    precision) registers and resolves without touching the planner."""
    @registry.register
    class DummyKernel(registry.KernelBase):
        kind, precision, dtype = "unit_dummy", "int8", "i8"

        def apply(self, params, x, site, decision=None, *, interpret=None):
            return x

    try:
        impl = registry.get_kernel("unit_dummy", "int8")
        assert impl.apply(None, 7, None) == 7
        assert ("unit_dummy", "int8") in registry.available()
    finally:
        registry.unregister("unit_dummy", "int8")
    assert ("unit_dummy", "int8") not in registry.available()


def test_registry_new_kernel_plans_and_executes(tmp_autotune_cache):
    """The additive flow end-to-end: a kind unknown to core.fusion /
    core.program still plans (enabled by default, probe resolution,
    decision shape, report) and executes (apply when fused, ref when
    not) purely via its registration."""
    from repro.core.program import Program, Site

    @registry.register
    class DoubleKernel(registry.KernelBase):
        kind, precision, dtype = "unit_double", "fp", "f32"

        def site_precision(self, params):
            return "fp"

        def tune(self, site, *, autotune=True, interpret=None):
            return {"block": 1}

        def apply(self, params, x, site, decision=None, *, interpret=None):
            return x * 2.0

        def ref(self, params, x, site, **kw):
            return x * 2.0

    try:
        site = Site("X.unit0", "unit_double", "X", (), (1, 4, 4, 8),
                    (1, 4, 4, 8))
        program = Program(B1_SMOKE, 1, 4, (site,))
        assert program.fusible() == (site,)
        plan = plan_program(program, {}, autotune=False)
        d = plan.get("X.unit0")
        assert d.fused and d.reason == "ok" and d.blocks == {"block": 1}
        x = jnp.ones((1, 4, 4, 8))
        want = np.full((1, 4, 4, 8), 2.0, np.float32)
        np.testing.assert_array_equal(
            np.asarray(execute(program, {}, x, plan=plan)), want)
        np.testing.assert_array_equal(          # plan=None -> impl.ref
            np.asarray(execute(program, {}, x)), want)
        row = plan_report(plan)[0]              # no analytic model: zeros
        assert row["launches_fused"] == 1 and row["hbm_w"] == 0
    finally:
        registry.unregister("unit_double", "fp")


def test_registry_precision_policies():
    """Conv kinds demote on mismatch; MSA never falls back (its core is
    precision-agnostic, only the projections switch)."""
    conv = registry.get_kernel("mbconv", "fp")
    assert conv.resolve_precision("int8", "auto") == ("int8", None)
    assert conv.resolve_precision("int8", "fp") == ("fp", "quantized")
    assert conv.resolve_precision("fp", "int8") == ("fp", "not-quantized")
    assert conv.resolve_precision("mixed", "auto") == ("fp", "mixed")
    msa = registry.get_kernel("msa", "fp")
    assert msa.resolve_precision("int8", "fp") == ("fp", None)
    assert msa.resolve_precision("fp", "int8") == ("fp", None)
    assert msa.resolve_precision("int8", "auto") == ("int8", None)
