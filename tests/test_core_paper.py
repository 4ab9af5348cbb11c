"""Paper-core tests: EfficientViT model, MSA, FIX8 quantization, BN fold,
and the cycle-level accelerator model's reproduction of Fig. 6 / Table II.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

from proptest import sweep

from repro.core.accelerator_model import TABLE_II, analyze_program
from repro.core.efficientvit import (
    B1, B1_SMOKE, efficientvit, init_efficientvit, total_macs)
from repro.core.program import lower, manifest
from repro.core.quantization import (
    conv2d_int8, fold_bn_into_conv, quantization_error, quantize_efficientvit,
    quantize_tensor)
from repro.core.relu_attention import (
    MSAConfig, init_msa, msa, relu_global_attention)
from repro.layers.conv import conv2d
from repro.layers.norms import batchnorm, bn_fold_scale_bias, init_batchnorm


# ---------------------------------------------------------------------------
# functional model
# ---------------------------------------------------------------------------

def test_efficientvit_forward():
    key = jax.random.PRNGKey(0)
    params = init_efficientvit(key, B1_SMOKE)
    x = jax.random.normal(key, (2, 64, 64, 3))
    logits = jax.jit(lambda p, x: efficientvit(p, x, B1_SMOKE))(params, x)
    assert logits.shape == (2, B1_SMOKE.num_classes)
    assert jnp.isfinite(logits).all()


def test_efficientvit_b1_macs():
    """EfficientViT-B1 @224 is a ~0.52 GMACs model (Cai et al. Table 2)."""
    g = total_macs(B1) / 1e9
    assert 0.45 < g < 0.60, g


def test_msa_equals_kernel_oracle():
    """MSA's attention core == the Pallas kernel's oracle == fused kernel."""
    from repro.kernels.relu_attn.ops import msa_attention_fn
    key = jax.random.PRNGKey(1)
    cfg = MSAConfig(channels=32, head_dim=16, scales=(3,))
    params = init_msa(key, cfg)
    x = jax.random.normal(key, (2, 8, 8, 32))
    out_ref = msa(params, x, cfg)                                # jnp path
    out_kern = msa(params, x, cfg, attention_fn=msa_attention_fn)  # Pallas
    assert_allclose(np.asarray(out_kern), np.asarray(out_ref),
                    rtol=2e-4, atol=2e-4)


@sweep(n_cases=5, seed=21)
def test_relu_attention_normalization(rng):
    """Attention weights must sum to 1 per query (the divisor path)."""
    b, n, h, d = 1, int(rng.integers(4, 33)), 2, 16
    q = jnp.asarray(np.abs(rng.standard_normal((b, n, h, d))), jnp.float32)
    k = jnp.asarray(np.abs(rng.standard_normal((b, n, h, d))), jnp.float32)
    v = jnp.ones((b, n, h, d), jnp.float32)
    out = relu_global_attention(q, k, v)
    # with V = 1 the normalized combination must return exactly 1
    assert_allclose(np.asarray(out), np.ones_like(out), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# BN fold + FIX8
# ---------------------------------------------------------------------------

def test_bn_fold_exact():
    key = jax.random.PRNGKey(2)
    c_in, c_out = 8, 16
    conv_p = {"w": jax.random.normal(key, (3, 3, c_in, c_out)) * 0.1}
    bn_p = init_batchnorm(c_out)
    bn_p = {k: jax.random.normal(jax.random.fold_in(key, i), v.shape) * 0.3
            + (1.0 if k in ("scale", "var") else 0.0)
            for i, (k, v) in enumerate(bn_p.items())}
    bn_p["var"] = jnp.abs(bn_p["var"]) + 0.1
    x = jax.random.normal(jax.random.fold_in(key, 9), (2, 8, 8, c_in))
    ref = batchnorm(bn_p, conv2d(conv_p, x))
    w, b = fold_bn_into_conv(conv_p, bn_p)
    out = conv2d({"w": w, "b": b}, x)
    assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


@sweep(n_cases=6, seed=22)
def test_quantize_roundtrip_error_bound(rng):
    x = jnp.asarray(rng.standard_normal((64, 64)), jnp.float32)
    q, scale = quantize_tensor(x)
    back = q.astype(jnp.float32) * scale
    # max error bounded by scale/2 per element
    assert float(jnp.max(jnp.abs(x - back))) <= float(scale) * 0.5 + 1e-7


def test_fix8_efficientvit_parity():
    """FIX8 model output within a few percent of fp32 (paper's datapath)."""
    key = jax.random.PRNGKey(3)
    params = init_efficientvit(key, B1_SMOKE)
    x = jax.random.normal(key, (2, 64, 64, 3))
    fp = efficientvit(params, x, B1_SMOKE)
    qparams = quantize_efficientvit(params)
    qq = efficientvit(qparams, x, B1_SMOKE)
    err = float(quantization_error(fp, qq))
    assert err < 0.15, f"relative L2 error {err:.3f}"


def test_conv2d_int8_matches_fp():
    key = jax.random.PRNGKey(4)
    from repro.core.quantization import quantize_conv_bn
    p = {"conv": {"w": jax.random.normal(key, (3, 3, 8, 16)) * 0.2},
         "bn": init_batchnorm(16)}
    x = jax.random.normal(jax.random.fold_in(key, 1), (1, 8, 8, 8))
    ref = batchnorm(p["bn"], conv2d(p["conv"], x))
    qp = quantize_conv_bn(p)
    out = conv2d_int8(qp["qconv"], x)
    err = float(quantization_error(ref, out))
    assert err < 0.05, err


# ---------------------------------------------------------------------------
# accelerator cycle model: the paper's headline numbers
# ---------------------------------------------------------------------------

def test_table2_reproduction():
    rep, stages, _ = analyze_program(lower(B1))
    paper = TABLE_II["Paper (ZCU102)"]
    assert abs(rep.gops - paper["gops"]) / paper["gops"] < 0.05, rep.gops
    assert abs(rep.gops_per_w - paper["eff"]) / paper["eff"] < 0.05
    assert 0.70 < rep.gops_per_dsp < 0.82          # paper: 0.76
    assert rep.utilization > 0.95                   # paper: >95%


def test_fig6_stage_profile():
    rep, stages, sched = analyze_program(lower(B1))
    # Fig. 6 observation (1): the 3-channel stem conv has low utilization
    first = next(s for s in sched if s.name == "conv1")
    assert first.util < 0.5
    # transformer stages sustain high utilization thanks to TMP fusion
    for st in ("S2", "S3", "S4"):
        assert stages[st]["util"] > 0.9, (st, stages[st]["util"])


def test_tmp_fusion_ablation():
    """Fusion must strictly help both cycles and DRAM traffic (§III-D)."""
    fused, _, _ = analyze_program(lower(B1), fuse=True)
    unfused, _, _ = analyze_program(lower(B1), fuse=False)
    assert fused.total_cycles < unfused.total_cycles
    assert fused.dram_bytes <= unfused.dram_bytes
    assert fused.gops > unfused.gops


def test_speedups_vs_cpu_baseline():
    """Paper: 14.3x throughput / 21.1x efficiency vs Snapdragon CPU."""
    rep, _, _ = analyze_program(lower(B1))
    cpu = TABLE_II["EfficientViT [8] (CPU)"]
    speedup = rep.gops / cpu["gops"]
    eff_gain = rep.gops_per_w / cpu["eff"]
    assert 13.0 < speedup < 16.0, speedup
    assert 19.0 < eff_gain < 23.0, eff_gain


def test_manifest_macs_consistent():
    ops = manifest(lower(B1))
    assert sum(o.macs for o in ops) == total_macs(B1)
    assert all(o.macs > 0 for o in ops)


def test_vision_config_registry():
    """The paper's models are selectable configs (B1/B2/B3)."""
    from repro.configs import VISION
    from repro.core.efficientvit import total_macs
    assert set(VISION) == {"efficientvit-b1", "efficientvit-b2",
                           "efficientvit-b3"}
    macs = {k: total_macs(v) / 1e9 for k, v in VISION.items()}
    # monotone family scaling, B1 anchored at ~0.52 GMACs
    assert macs["efficientvit-b1"] < macs["efficientvit-b2"] < \
        macs["efficientvit-b3"]
    assert 0.45 < macs["efficientvit-b1"] < 0.60


def test_w8_lm_serving_parity():
    """Weight-only int8 (FIX8 serving): decode logits close to fp across
    families, bytes ~3.7x smaller (fp32 smoke params)."""
    from repro.configs import get_arch, smoke_variant
    from repro.core.quantization import quantize_lm_params
    from repro.models.registry import build_model
    for arch in ("granite-3-2b", "kimi-k2-1t-a32b", "zamba2-1.2b"):
        cfg = smoke_variant(get_arch(arch))
        m = build_model(cfg)
        p = m.init(jax.random.PRNGKey(0))
        qp = quantize_lm_params(p)
        caches = m.init_caches(2, 64)
        lg_fp, _ = m.decode(p, caches, jnp.zeros((2, 1), jnp.int32),
                            jnp.int32(0))
        lg_q, _ = m.decode(qp, caches, jnp.zeros((2, 1), jnp.int32),
                           jnp.int32(0))
        rel = float(jnp.linalg.norm(lg_q - lg_fp)
                    / jnp.linalg.norm(lg_fp))
        assert rel < 0.12, (arch, rel)
        nb = sum(x.nbytes for x in jax.tree_util.tree_leaves(p))
        qb = sum(x.nbytes for x in jax.tree_util.tree_leaves(qp))
        assert nb / qb > 3.0, (arch, nb / qb)
