"""EfficientViT's L series through the program: the lowering, the
FusedMBConv kernel and the L-shaped forward against the benchmark's
plain reference (``bench/configs/efficientvit_l.py``, which imports
nothing of the program) on seeded weights, on the CPU at a small size,
and the program's work count against the references' at full size."""
import dataclasses
import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.efficientvit import (
    B1, L2, L_SMOKE, EfficientViTConfig, init_efficientvit, total_macs)
from repro.core.fusion import plan_program
from repro.core.program import execute, lower
from repro.kernels.fmbconv.kernel import fmbconv_fused
from repro.kernels.fmbconv.ref import fmbconv_ref

BENCH = Path(__file__).resolve().parents[1] / "bench"


def _module(name):
    spec = importlib.util.spec_from_file_location(
        f"ref_{name}", BENCH / "configs" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _bench_cfg(cfg: EfficientViTConfig) -> dict:
    """The benchmark's dict form of a program config."""
    m = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)
         if f.name not in ("name", "image_size", "dtype")}
    m["expand_ratios"] = m["expand_ratios"] or (m["expand_ratio"],) * 5
    return {"model": m, "image_size": cfg.image_size}


def _rel_err(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


@pytest.fixture(scope="module")
def l_smoke():
    """Seeded reference weights (the served tree), images, and the plain
    reference's logits at the smoke size, under highest precision."""
    ref = _module("efficientvit_l")
    cfg = _bench_cfg(L_SMOKE)
    key = jax.random.PRNGKey(16)
    params = ref.init_params(jax.random.fold_in(key, 0), cfg)
    x = ref.images(jax.random.fold_in(key, 1), 2, L_SMOKE.image_size)
    with jax.default_matmul_precision("highest"):
        want = ref.forward(params, x, cfg)
    return params, x, want


def test_l2_lowering():
    program = lower(L2, batch=8)
    kinds = [s.kind for s in program.sites]
    assert {k: kinds.count(k) for k in set(kinds)} == {
        "conv_bn": 2, "resblock": 1, "fmbconv": 6, "mbconv": 18, "msa": 8,
        "gap": 1, "fc": 2}
    s = program.site("S1.down")
    assert (s.stride, s.residual, s.attrs["mid"]) == (2, False, 512)
    assert program.site("S4.down").attrs["mid"] == 6144
    assert program.site("S4.evit0.msa").attrs["heads"] == 16
    assert program.site("S4.evit0.msa").attrs["head_dim"] == 32
    assert {s.act for s in program.sites if s.act} == {"gelu_tanh"}
    assert program.site("head.fc1").attrs == {"norm": "ln"}
    # the B pattern's defaults lower B1 as before
    assert {s.act for s in lower(B1).sites if s.act} == {"hswish"}
    assert [s.name for s in lower(B1).by_kind("mbconv")][:3] == \
        ["S1.mb0", "S1.mb1", "S2.mb0"]


def test_l2_plan_fuses_every_fmbconv_site_at_bucket_8():
    params = jax.eval_shape(lambda k: init_efficientvit(k, L2),
                            jax.random.PRNGKey(0))
    plan = plan_program(lower(L2, batch=8), params, autotune=False,
                        interpret=False)
    fmb = [d for d in plan.decisions.values() if d.kind == "fmbconv"]
    assert len(fmb) == 6 and all(d.fused for d in fmb)
    res = plan.get("stem.res0")
    assert (res.kind, res.fused, res.reason) == ("resblock", True, "ok")
    demoted = {d.name: d.reason for d in plan.decisions.values()
               if not d.fused}
    # the planner's analytic VMEM budget keeps S3.down's 2048-wide
    # expansion at 28x28 on the reference path
    assert demoted == {"S3.down": "vmem"}
    # B1 has no ResBlock: its plan is the 22 fused DSConv/MBConv/MSA sites
    b1 = plan_program(lower(B1, batch=8), jax.eval_shape(
        lambda k: init_efficientvit(k, B1), jax.random.PRNGKey(0)),
        autotune=False, interpret=False)
    assert sorted((d.kind, d.fused) for d in b1.decisions.values()) == \
        [("dsconv", True)] + [("mbconv", True)] * 14 + [("msa", True)] * 7


def test_unplanned_program_matches_the_plain_reference(l_smoke):
    params, x, want = l_smoke
    with jax.default_matmul_precision("highest"):
        got = execute(lower(L_SMOKE, batch=2), params, x)
    # fp32 at highest on both sides; only the summation order of convs
    # and attention differs (~1e-7 per layer over ~20 layers).  A bf16
    # product anywhere reads ~1e-3, a wrong pad or activation ~1e-1.
    assert _rel_err(got, want) < 1e-5


def test_fused_plan_matches_the_plain_reference(l_smoke):
    params, x, want = l_smoke
    program = lower(L_SMOKE, batch=2)
    with jax.default_matmul_precision("highest"):
        plan = plan_program(program, params, autotune=False)
        assert all(d.fused for d in plan.decisions.values())
        assert plan.get("stem.res0").kind == "resblock"
        got = execute(program, params, x, plan=plan)
        unplanned = execute(program, params, x)
    # the interpreted kernels sum taps and mid tiles in their own order:
    # fp32 roundoff only, as for the unplanned program
    assert _rel_err(got, want) < 1e-5
    assert _rel_err(got, unplanned) < 1e-5


def test_demoted_resblock_matches_the_plain_reference(l_smoke):
    """A plan that sends the stem's ResBlock to the reference path (the
    ladder's ``demote``) runs it as XLA convs plus ``execute``'s own
    residual add, every other site fused."""
    params, x, want = l_smoke
    program = lower(L_SMOKE, batch=2)
    with jax.default_matmul_precision("highest"):
        plan = plan_program(program, params, autotune=False,
                            demote=("stem.res0",))
        assert {d.name for d in plan.decisions.values() if not d.fused} \
            == {"stem.res0"}
        got = execute(program, params, x, plan=plan)
    assert _rel_err(got, want) < 1e-5     # fp32 roundoff, as above


@pytest.mark.parametrize("stride,residual", [(1, False), (1, True),
                                             (2, False)])
@pytest.mark.parametrize("block_m", [128, 256])
def test_fmbconv_kernel_matches_its_oracle(stride, residual, block_m):
    B, H, C, M = 2, 16, 32, 192      # M not a multiple of 128: a padded tile
    F = C if residual else 48
    ks = jax.random.split(jax.random.PRNGKey(stride + 2 * residual), 5)
    x = jax.random.normal(ks[0], (B, H, H, C))
    w1 = jax.random.normal(ks[1], (3, 3, C, M)) * (9 * C) ** -0.5
    b1 = 0.1 * jax.random.normal(ks[2], (M,))
    w2 = jax.random.normal(ks[3], (M, F)) * M ** -0.5
    b2 = 0.1 * jax.random.normal(ks[4], (F,))
    with jax.default_matmul_precision("highest"):
        got = fmbconv_fused(x, w1, b1, w2, b2, stride=stride,
                            block_m=block_m, residual=residual)
        want = fmbconv_ref(x, w1, b1, w2, b2, stride=stride,
                           residual=residual)
    assert got.shape == (B, H // stride, H // stride, F)
    # 9 taps x C products summed tap by tap vs XLA's conv order, then the
    # projection summed per mid tile: fp32 roundoff of O(1) values
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_mbconv_and_attention_kernels_at_l_shapes():
    from repro.kernels.mbconv.kernel import mbconv_fused
    from repro.kernels.mbconv.ref import mbconv_ref
    from repro.kernels.relu_attn.kernel import relu_attn_noncausal
    from repro.kernels.relu_attn.ref import relu_attn_noncausal_ref
    ks = jax.random.split(jax.random.PRNGKey(7), 8)
    C, M, F = 32, 128, 64
    args = (jax.random.normal(ks[0], (2, 8, 8, C)),
            jax.random.normal(ks[1], (C, M)) * C ** -0.5,
            0.1 * jax.random.normal(ks[2], (M,)),
            jax.random.normal(ks[3], (3, 3, M)) / 3,
            0.1 * jax.random.normal(ks[4], (M,)),
            jax.random.normal(ks[5], (M, F)) * M ** -0.5,
            jnp.zeros((F,)))
    q, k, v = (jax.random.normal(kk, (4, 49, 32)) for kk in ks[5:8])
    with jax.default_matmul_precision("highest"):
        for stride in (1, 2):
            np.testing.assert_allclose(
                np.asarray(mbconv_fused(*args, stride=stride,
                                        act="gelu_tanh")),
                np.asarray(mbconv_ref(*args, stride=stride,
                                      act="gelu_tanh")),
                rtol=1e-5, atol=1e-5)    # fp32 roundoff, as above
        # head_dim 32, 49 tokens (S4 of L2 at 224 px): one whole-map tile
        np.testing.assert_allclose(
            np.asarray(relu_attn_noncausal(q, k, v)),
            np.asarray(relu_attn_noncausal_ref(q, k, v)),
            rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("cfg,ref", [(L_SMOKE, "efficientvit_l"),
                                     (B1, "efficientvit"),
                                     (L2, "efficientvit_l")],
                         ids=["l-smoke", "b1", "l2"])
def test_program_work_equals_the_references(cfg, ref):
    assert total_macs(cfg) == _module(ref).macs_per_image(_bench_cfg(cfg))


def test_l2_file_matches_the_program_preset():
    cfg = json.loads((BENCH / "configs" / "l2-r224-fp32.json").read_text())
    m = {k: tuple(v) if isinstance(v, list) else v
         for k, v in cfg["model"].items()}
    assert EfficientViTConfig(name=L2.name, image_size=224, **m) == L2


def test_int8_refuses_the_l_series():
    from repro.core.quantization import quantize_efficientvit
    params = jax.eval_shape(lambda k: init_efficientvit(k, L_SMOKE),
                            jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="'resblock'.*stem_res"):
        quantize_efficientvit(params)
    with pytest.raises(ValueError, match="'fmbconv'"):
        quantize_efficientvit(params)
    gelu_b1 = dataclasses.replace(B1, act="gelu_tanh")
    b1_params = init_efficientvit(jax.random.PRNGKey(0), B1_TINY)
    with pytest.raises(ValueError, match="gelu_tanh"):
        quantize_efficientvit(b1_params, gelu_b1)
    quantize_efficientvit(b1_params, B1_TINY)        # B1 itself quantizes


B1_TINY = dataclasses.replace(
    B1, widths=(8, 16, 24, 32, 48), depths=(1, 1, 1, 1, 1),
    head_widths=(64, 64), num_classes=10, image_size=64)
