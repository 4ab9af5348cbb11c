"""Inter-layer super-site fusion + single-load weight residency.

The contracts under test (ISSUE 10 / ROADMAP item 2):

  * ``SuperSite.of`` validates member chains at plan time (typed
    ``LoweringError``, never a shape error inside a jitted executor);
  * the grouping pass in ``plan_program`` collapses consecutive fused
    conv sites of one stage into one launch, and the grouped forward
    matches the site-by-site interpreter — fp to <1e-5, int8 BIT-EXACT;
  * weights are resident per launch: one ``WeightPack`` holds every
    member tensor once, each on an aligned row at lane 0, the pack built
    inside a jitted forward equals the host-built one, and the plan
    report counts each member's weight bytes exactly once with interior
    activation traffic at zero;
  * ``SiteOverride.group_break`` splits a chain exactly where pinned
    (the offline search's split/merge lever);
  * the fault ladder demotes a blamed member OUT of its group — the
    survivors regroup or run per-site, the key does not fall straight
    to the reference interpreter.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

from repro.common.errors import LoweringError
from repro.core.efficientvit import EfficientViTConfig, init_efficientvit
from repro.core.fusion import (
    SiteOverride, launch_counts, plan_program, plan_report)
from repro.core.program import SuperSite, execute, lower
from repro.core.quantization import quantize_efficientvit
from repro.kernels.supersite.pack import pack_weights
from repro.serving.executors import ExecutorCache

# Deep enough to form real chains (B1_SMOKE's depths of 1 group
# nothing): stem.ss0 = [stem.ds0, stem.ds1], S1.ss0 = [S1.mb0, S1.mb1],
# S2.ss0 = [S2.mb0, S2.mb1, S2.mb2].
CFG = EfficientViTConfig(name="ss-smoke", widths=(8, 16, 24, 32, 48),
                         depths=(2, 2, 3, 1, 1), head_widths=(64, 64),
                         num_classes=10, image_size=64)
N_GROUPS = 3


@pytest.fixture
def params():
    return init_efficientvit(jax.random.PRNGKey(0), CFG)


def _images(n, res=64, seed=1):
    return np.asarray(jax.random.normal(
        jax.random.PRNGKey(seed), (n, res, res, 3)), np.float32)


def _groups(plan):
    return {g.name: tuple(g.members) for g in plan.groups.values()}


def _per_site(plan):
    """The plan with its super-site groups taken out: every member
    launches its own per-site kernel, the baseline the chain parity
    tests compare against."""
    return dataclasses.replace(plan, groups={}, decisions={
        n: dataclasses.replace(d, group="")
        for n, d in plan.decisions.items()})


# ---------------------------------------------------------------------------
# SuperSite validation
# ---------------------------------------------------------------------------

def test_supersite_of_validates(params):
    program = lower(CFG, batch=1, image_size=64)
    sup = SuperSite.of(program, ("S2.mb0", "S2.mb1", "S2.mb2"))
    assert sup.stage == "S2" and len(sup.sites) == 3
    with pytest.raises(LoweringError):
        SuperSite.of(program, ("S2.mb0",))             # < 2 members
    with pytest.raises(LoweringError):
        SuperSite.of(program, ("S2.mb0", "S2.mb2"))    # not consecutive
    with pytest.raises(LoweringError):
        SuperSite.of(program, ("S1.mb1", "S2.mb0"))    # stage boundary


# ---------------------------------------------------------------------------
# grouping pass + chain parity vs the site-by-site interpreter
# ---------------------------------------------------------------------------

def test_grouping_pass_forms_expected_chains(params, tmp_autotune_cache):
    program = lower(CFG, batch=1, image_size=64)
    for tree in (params, quantize_efficientvit(params)):
        plan = plan_program(program, tree, autotune=False)
        assert _groups(plan) == {
            "stem.ss0": ("stem.ds0", "stem.ds1"),
            "S1.ss0": ("S1.mb0", "S1.mb1"),
            "S2.ss0": ("S2.mb0", "S2.mb1", "S2.mb2")}
        flat = _per_site(plan)
        # each chain of k members collapses k launches into 1
        saved = sum(len(g.members) - 1 for g in plan.groups.values())
        assert launch_counts(flat)["fused"] \
            == launch_counts(plan)["fused"] + saved


def test_supersite_chain_parity_fp(params, tmp_autotune_cache):
    """Grouped vs site-by-site fused: <1e-5; both vs reference: close."""
    batch = 2
    program = lower(CFG, batch=batch, image_size=64)
    x = _images(batch)
    grouped = plan_program(program, params, autotune=False)
    flat = _per_site(grouped)
    assert grouped.groups and not flat.groups
    ref = execute(program, params, x)
    y_grouped = execute(program, params, x, plan=grouped)
    y_flat = execute(program, params, x, plan=flat)
    assert float(jnp.max(jnp.abs(y_grouped - y_flat))) < 1e-5
    assert_allclose(np.asarray(y_grouped), np.asarray(ref),
                    rtol=1e-3, atol=1e-3)


def test_supersite_chain_parity_int8_bit_exact(params, tmp_autotune_cache):
    """The grouped int8 chain is BIT-EXACT vs the site-by-site fused
    path: identical integer arithmetic, identical per-map quantization
    boundaries — the whole-map grid never re-quantizes mid-chain."""
    qparams = quantize_efficientvit(params)
    for batch in (1, 2):
        program = lower(CFG, batch=batch, image_size=64)
        x = _images(batch)
        grouped = plan_program(program, qparams, autotune=False)
        flat = _per_site(grouped)
        assert all(g.precision == "int8" for g in grouped.groups.values())
        y_grouped = execute(program, qparams, x, plan=grouped)
        y_flat = execute(program, qparams, x, plan=flat)
        np.testing.assert_array_equal(np.asarray(y_grouped),
                                      np.asarray(y_flat))


# ---------------------------------------------------------------------------
# single-load weight residency
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("precision", ["fp", "int8"])
def test_weight_pack_holds_each_member_tensor_once(params, precision,
                                                   tmp_autotune_cache):
    """Every member tensor sits in its own rows of the pack, starting on
    the dtype's sublane tile at lane 0, and reads back unchanged."""
    from repro.kernels.supersite.pack import (
        _member_fp_tensors, _member_int8_tensors)
    from repro.core.program import params_at
    tree = params if precision == "fp" else quantize_efficientvit(params)
    program = lower(CFG, batch=1, image_size=64)
    plan = plan_program(program, tree, autotune=False)
    g = plan.groups["S2.ss0"]
    sup = SuperSite.of(program, g.members, name=g.name)
    pack = pack_weights(tree, sup, g.precision)
    for k, site in enumerate(sup.sites):
        p = params_at(tree, site.param_path)
        if precision == "int8":
            qs, fs = _member_int8_tensors(p, site.kind)
            halves = ((pack.q, pack.q_offsets[k], qs, 32),
                      (pack.fp, pack.fp_offsets[k], fs, 8))
        else:
            halves = ((pack.fp, pack.fp_offsets[k],
                       _member_fp_tensors(p, site.kind), 8),)
        for mat, offs, tensors, align in halves:
            assert len(offs) == len(tensors)
            for off, t in zip(offs, tensors):
                t = np.asarray(t).reshape(-1, np.shape(t)[-1])
                assert off % align == 0
                got = np.asarray(mat[off:off + t.shape[0], :t.shape[1]])
                np.testing.assert_array_equal(got, t.astype(got.dtype))
    assert pack.fp.shape[1] % 128 == 0
    q_bytes = int(pack.q.size) if pack.q is not None else 0
    assert pack.nbytes == int(pack.fp.size) * 4 + q_bytes


def test_weight_pack_built_in_jit_matches_host_pack(params,
                                                    tmp_autotune_cache):
    """The executors jit the forward with params as arguments, so the
    pack is built inside the trace: it must equal the host-built pack,
    and the jitted grouped forward the eager one."""
    program = lower(CFG, batch=2, image_size=64)
    plan = plan_program(program, params, autotune=False)
    g = plan.groups["S1.ss0"]
    sup = SuperSite.of(program, g.members, name=g.name)
    host = pack_weights(params, sup, "fp")
    traced = jax.jit(lambda p: pack_weights(p, sup, "fp").fp)(params)
    np.testing.assert_array_equal(np.asarray(traced), np.asarray(host.fp))
    x = _images(2)
    eager = execute(program, params, x, plan=plan)
    jitted = jax.jit(lambda p, v: execute(program, p, v, plan=plan))(
        params, x)
    assert_allclose(np.asarray(jitted), np.asarray(eager),
                    rtol=1e-6, atol=1e-6)


def test_plan_report_counts_group_weights_once(params, tmp_autotune_cache):
    """Grouping never double-counts weight HBM, and interior members
    deliver ZERO activation bytes."""
    program = lower(CFG, batch=1, image_size=64)
    plan = plan_program(program, params, autotune=False)
    flat_plan = _per_site(plan)
    rep, flat_rep = plan_report(plan), plan_report(flat_plan)
    assert sum(r["hbm_w"] for r in rep) \
        == sum(r["hbm_w"] for r in flat_rep)
    rows = {r["site"]: r for r in rep}
    for grp in plan.groups.values():
        for interior in grp.members[1:-1]:
            assert rows[interior]["hbm_delivered"] == 0, interior
        assert sum(rows[m]["launches_fused"] for m in grp.members) == 1


# ---------------------------------------------------------------------------
# split/merge pins + the fault ladder
# ---------------------------------------------------------------------------

def test_group_break_override_splits_exactly_there(params,
                                                   tmp_autotune_cache):
    program = lower(CFG, batch=1, image_size=64)
    plan = plan_program(
        program, params, autotune=False,
        overrides={"S2.mb1": SiteOverride(group_break=True)})
    gs = _groups(plan)
    # the chain may not extend ACROSS S2.mb1: S2.mb0 is left alone
    # (a run of one groups nothing) and a new chain starts AT S2.mb1
    assert ("S2.mb1", "S2.mb2") in gs.values()
    assert not any("S2.mb0" in m for m in gs.values())
    assert gs["stem.ss0"] == ("stem.ds0", "stem.ds1")   # others intact
    assert gs["S1.ss0"] == ("S1.mb0", "S1.mb1")


def test_fault_demotion_splits_group_not_reference(params,
                                                   tmp_autotune_cache):
    """Blaming one member demotes THAT site (reason "fault") and the
    surviving members regroup — level 1 of the ladder, with a live
    fused plan, not a fall to the reference interpreter."""
    cache = ExecutorCache(params, CFG, buckets=(1,), autotune=False)
    healthy = cache.get(1, 64)
    assert "S2.ss0" in healthy.plan.groups
    state = cache.degrade(1, 64, site="S2.mb0")
    assert state.level == 1 and state.demoted == {"S2.mb0"}
    ex = cache.get(1, 64)
    assert ex.plan is not None                    # NOT the interpreter
    d = ex.plan.decisions["S2.mb0"]
    assert not d.fused and d.reason == "fault" and d.group == ""
    gs = _groups(ex.plan)
    assert gs["S2.ss0"] == ("S2.mb1", "S2.mb2")   # survivors regroup
    assert gs["S1.ss0"] == ("S1.mb0", "S1.mb1")
    # the degraded plan still serves correctly
    x = _images(1)
    program = lower(CFG, batch=1, image_size=64)
    ref = execute(program, params, x)
    out = execute(program, params, x, plan=ex.plan)
    assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-3, atol=1e-3)
