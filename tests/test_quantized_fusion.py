"""FIX8 fused path: int8 megakernels with in-kernel requantization.

The contract under test: a ``quantize_efficientvit`` tree routed through
a ``plan_program(..., precision="auto"|"int8")`` plan must fuse every site
the fp plan fuses (zero ``"quantized"`` fallbacks) and agree with the
int8 *reference* path — bit-exactly at batch 1, where the in-kernel
per-batch-element requantization scales coincide with the reference
whole-tensor ones, and within quantization noise otherwise.
"""
import jax
import jax.numpy as jnp
import numpy as np
from numpy.testing import assert_allclose

from proptest import sweep

from repro.core.efficientvit import (
    B1_SMOKE, dsconv, efficientvit, init_dsconv, init_efficientvit,
    init_mbconv, mbconv)
from repro.core.program import execute, lower
from repro.core.quantization import (
    calibrate_act_scale, quantize_efficientvit, quantize_tensor)
from repro.kernels.dsconv.kernel import dsconv_fused_int8
from repro.kernels.dsconv.ops import dsconv_apply_int8
from repro.kernels.dsconv.ref import dsconv_int8_ref
from repro.kernels.mbconv.kernel import mbconv_fused_int8
from repro.kernels.mbconv.ops import mbconv_apply_int8
from repro.kernels.mbconv.ref import mbconv_int8_ref


def _rand_q(rng, shape):
    return jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)


def _rand_s(rng, n):
    return jnp.asarray(rng.uniform(0.005, 0.05, (n,)), jnp.float32)


# ---------------------------------------------------------------------------
# int8 megakernels vs jnp oracles (stride 1/2, per-channel scales,
# ragged c_out tiles)
# ---------------------------------------------------------------------------

@sweep(n_cases=8, seed=21)
def test_mbconv_int8_fused_sweep(rng):
    b = int(rng.integers(1, 3))
    hw = int(rng.choice([8, 12, 16]))
    c = int(rng.choice([4, 8, 16]))
    m = c * int(rng.choice([2, 4]))
    f = int(rng.choice([8, 16, 24]))
    stride = int(rng.choice([1, 2]))
    bf = int(rng.choice([8, 64, f]))  # exercises ragged c_out tiles
    args = (_rand_q(rng, (b, hw, hw, c)), jnp.float32(rng.uniform(0.01, 0.1)),
            _rand_q(rng, (c, m)), _rand_s(rng, m),
            jnp.asarray(rng.standard_normal((m,)), jnp.float32),
            _rand_q(rng, (3, 3, m)), _rand_s(rng, m),
            jnp.asarray(rng.standard_normal((m,)), jnp.float32),
            _rand_q(rng, (m, f)), _rand_s(rng, f),
            jnp.asarray(rng.standard_normal((f,)), jnp.float32))
    out = mbconv_fused_int8(*args, stride=stride, block_f=bf)
    ref = mbconv_int8_ref(*args, stride=stride)
    assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


@sweep(n_cases=6, seed=22)
def test_dsconv_int8_fused_sweep(rng):
    b = int(rng.integers(1, 3))
    hw = int(rng.choice([8, 12]))
    c = int(rng.choice([4, 8]))
    f = int(rng.choice([8, 12]))
    stride = int(rng.choice([1, 2]))
    bf = int(rng.choice([4, 128]))
    args = (_rand_q(rng, (b, hw, hw, c)), jnp.float32(rng.uniform(0.01, 0.1)),
            _rand_q(rng, (3, 3, c)), _rand_s(rng, c),
            jnp.asarray(rng.standard_normal((c,)), jnp.float32),
            _rand_q(rng, (c, f)), _rand_s(rng, f),
            jnp.asarray(rng.standard_normal((f,)), jnp.float32))
    out = dsconv_fused_int8(*args, stride=stride, block_f=bf)
    ref = dsconv_int8_ref(*args, stride=stride)
    assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# apply wrappers vs the reference quantized model blocks (conv2d_int8
# chain).  Batch 1: in-kernel requant scales == reference scales.
# ---------------------------------------------------------------------------

def test_mbconv_apply_int8_matches_quantized_block():
    key = jax.random.PRNGKey(0)
    for stride in (1, 2):
        qp = quantize_efficientvit(init_mbconv(key, 8, 16, 4, jnp.float32))
        x = jax.random.normal(jax.random.fold_in(key, stride), (1, 16, 16, 8))
        ref = mbconv(qp, x, stride=stride)
        out = mbconv_apply_int8(qp, x, stride=stride, block_f=128)
        assert_allclose(np.asarray(out), np.asarray(ref),
                        rtol=1e-5, atol=1e-5)


def test_dsconv_apply_int8_matches_quantized_block():
    from repro.core.quantization import conv2d_int8
    key = jax.random.PRNGKey(1)
    qp = quantize_efficientvit(init_dsconv(key, 8, 8, jnp.float32))
    x = jax.random.normal(key, (1, 12, 12, 8))
    ref = dsconv(qp, x)
    out = dsconv_apply_int8(qp, x)
    assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)
    # stride 2: SAME anchoring must match the conv2d_int8 chain exactly
    y = jax.nn.hard_swish(conv2d_int8(qp["dw"]["qconv"], x, stride=2,
                                      groups=8))
    ref2 = conv2d_int8(qp["pw"]["qconv"], y)
    out2 = dsconv_apply_int8(qp, x, stride=2)
    assert_allclose(np.asarray(out2), np.asarray(ref2),
                    rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# MSA projections through the W8A8 Pallas GEMM
# ---------------------------------------------------------------------------

def test_conv1x1_w8a8_matches_conv2d_int8():
    from repro.core.quantization import conv2d_int8
    from repro.kernels.int8_matmul.ops import conv1x1_w8a8
    rng = np.random.default_rng(3)
    B, H, W, C, F = 2, 7, 7, 16, 48
    x = jnp.asarray(rng.standard_normal((B, H, W, C)), jnp.float32)
    qp = {"q": _rand_q(rng, (1, 1, C, F)), "scale": _rand_s(rng, F),
          "bias": jnp.asarray(rng.standard_normal((F,)), jnp.float32)}
    ref = conv2d_int8(qp, x)
    out = conv1x1_w8a8(qp, x)
    assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_quantized_msa_fused_matches_reference(tmp_autotune_cache):
    from repro.core.fusion import plan_program
    from repro.core.relu_attention import MSAConfig, msa
    from repro.kernels.registry import get_kernel
    key = jax.random.PRNGKey(4)
    params = init_efficientvit(key, B1_SMOKE)
    qparams = quantize_efficientvit(params)
    program = lower(B1_SMOKE, batch=1)
    plan = plan_program(program, qparams, autotune=False)
    site = "S3.evit0.msa"
    d = plan.get(site)
    assert d.fused and d.precision == "int8"
    c = B1_SMOKE.widths[3]
    mcfg = MSAConfig(c, B1_SMOKE.head_dim, tuple(B1_SMOKE.msa_scales))
    p = qparams["stage3"]["blocks"][0]["msa"]
    x = jax.random.normal(key, (1, 8, 8, c))
    ref = msa(p, x, mcfg)                       # reference quantized path
    out = get_kernel("msa", d.precision).apply(      # the fused route
        p, x, program.site(site), d, interpret=plan.interpret)
    assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# plan: precision dispatch, no "quantized" bail-outs
# ---------------------------------------------------------------------------

def test_plan_fuses_every_quantized_site(tmp_autotune_cache):
    from repro.core.fusion import plan_program
    key = jax.random.PRNGKey(5)
    params = init_efficientvit(key, B1_SMOKE)
    qparams = quantize_efficientvit(params)
    program = lower(B1_SMOKE, batch=1)
    fp_plan = plan_program(program, params, autotune=False)
    q_plan = plan_program(program, qparams, autotune=False)
    assert not any(d.reason == "quantized"
                   for d in q_plan.decisions.values())
    assert q_plan.n_fused() >= fp_plan.n_fused()
    assert all(d.precision == "int8"
               for d in q_plan.decisions.values() if d.fused)
    # explicit int8 request on the quantized tree: identical routing
    q_plan2 = plan_program(program, qparams, autotune=False,
                           precision="int8")
    assert {d.name: d.precision for d in q_plan2.decisions.values()} == \
        {d.name: d.precision for d in q_plan.decisions.values()}
    # int8 requested on an fp tree -> conv sites demote to reference
    fp_forced = plan_program(program, params, autotune=False,
                             precision="int8")
    conv = [d for d in fp_forced.decisions.values()
            if d.kind in ("dsconv", "mbconv")]
    assert conv and all(not d.fused and d.reason == "not-quantized"
                        for d in conv)


def test_mixed_tree_demotes_gracefully(tmp_autotune_cache):
    """Hand-edited trees (site part-quantized) must fall back, not crash:
    conv sites demote with reason="mixed", an MSA with an fp proj keeps
    its projections on the reference path (precision "fp")."""
    from repro.core.fusion import plan_program
    key = jax.random.PRNGKey(11)
    params = init_efficientvit(key, B1_SMOKE)
    qparams = quantize_efficientvit(params)
    mixed = dict(qparams)
    # un-quantize one mbconv subblock and one msa proj
    mixed["stage1"] = [dict(qparams["stage1"][0],
                            pw1=params["stage1"][0]["pw1"])]
    s3 = {"down": qparams["stage3"]["down"],
          "blocks": [{"msa": dict(qparams["stage3"]["blocks"][0]["msa"],
                                  proj=params["stage3"]["blocks"][0]
                                  ["msa"]["proj"],
                                  proj_bn=params["stage3"]["blocks"][0]
                                  ["msa"]["proj_bn"]),
                      "mbconv": qparams["stage3"]["blocks"][0]["mbconv"]}]}
    mixed["stage3"] = s3
    program = lower(B1_SMOKE, batch=1)
    plan = plan_program(program, mixed, autotune=False)
    d_mb = plan.get("S1.mb0")
    assert not d_mb.fused and d_mb.reason == "mixed"
    d_msa = plan.get("S3.evit0.msa")
    assert d_msa.fused and d_msa.precision == "fp"
    x = jax.random.normal(key, (1, 64, 64, 3))
    out = execute(program, mixed, x, plan=plan)   # must not crash
    ref = efficientvit(mixed, x, B1_SMOKE)
    assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-3, atol=1e-3)


def test_quantized_full_forward_bit_exact_batch1(tmp_autotune_cache):
    from repro.core.fusion import plan_program
    key = jax.random.PRNGKey(6)
    qparams = quantize_efficientvit(init_efficientvit(key, B1_SMOKE))
    program = lower(B1_SMOKE, batch=1)
    plan = plan_program(program, qparams, autotune=False)
    x = jax.random.normal(key, (1, 64, 64, 3))
    ref = jax.jit(lambda p, x: efficientvit(p, x, B1_SMOKE))(qparams, x)
    fus = jax.jit(
        lambda p, x: execute(program, p, x, plan=plan))(qparams, x)
    assert bool((jnp.argmax(ref, -1) == jnp.argmax(fus, -1)).all())
    assert float(jnp.max(jnp.abs(ref - fus))) < 1e-2
    # conv megakernel sites are bit-identical at batch 1; the msa qkv/proj
    # epilogue may differ by float-mult associativity ulps only
    assert_allclose(np.asarray(fus), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_quantized_full_forward_batch2_within_noise(tmp_autotune_cache):
    from repro.core.fusion import plan_program
    key = jax.random.PRNGKey(7)
    qparams = quantize_efficientvit(init_efficientvit(key, B1_SMOKE))
    program = lower(B1_SMOKE, batch=2)
    plan = plan_program(program, qparams, autotune=False)
    x = jax.random.normal(key, (2, 64, 64, 3))
    ref = efficientvit(qparams, x, B1_SMOKE)
    fus = execute(program, qparams, x, plan=plan)
    assert bool((jnp.argmax(ref, -1) == jnp.argmax(fus, -1)).all())
    assert float(jnp.max(jnp.abs(ref - fus))) < 1e-2


def test_vision_engine_quantized_mode(tmp_autotune_cache):
    from repro.serving.vision import VisionEngine, VisionServeConfig
    key = jax.random.PRNGKey(8)
    params = init_efficientvit(key, B1_SMOKE)
    eng = VisionEngine.quantized(
        params, B1_SMOKE, VisionServeConfig(microbatch=1, autotune=False))
    assert all(d.precision == "int8"
               for d in eng.plan.decisions.values() if d.fused)
    imgs = jax.random.normal(key, (2, 64, 64, 3))
    logits = eng.logits(imgs)
    assert logits.shape == (2, B1_SMOKE.num_classes)
    # per-sample reference: dynamic act scales are per-microbatch (=1)
    ref = jnp.concatenate([efficientvit(eng.params, imgs[i:i + 1], B1_SMOKE)
                           for i in range(2)])
    assert_allclose(np.asarray(logits), np.asarray(ref),
                    rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# linear_w8a8: calibrated static activation scale
# ---------------------------------------------------------------------------

def test_linear_w8a8_static_scale_matches_dynamic():
    from repro.kernels.int8_matmul.ops import linear_w8a8
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.standard_normal((5, 32)), jnp.float32)
    w_q = _rand_q(rng, (32, 16))
    w_s = _rand_s(rng, 16)
    dyn = linear_w8a8(x, w_q, w_s)
    # the dynamic path is per-batch-element absmax (quantize_act's
    # scheme): each row is quantized with its own scale, so one row's
    # numerics never depend on its batch-mates
    from repro.core.quantization import quantize_act
    qt = quantize_act(x)
    want = (np.asarray(qt.q, np.int32) @ np.asarray(w_q, np.int32)
            ).astype(np.float32) * np.asarray(qt.scale)[:, None] \
        * np.asarray(w_s)[None, :]
    assert_allclose(np.asarray(dyn), want, rtol=1e-5, atol=1e-5)
    # a static scale calibrated on the same tensor agrees to within the
    # coarser per-tensor int8 quantization error
    static = linear_w8a8(x, w_q, w_s, x_scale=calibrate_act_scale(x))
    assert_allclose(np.asarray(static), np.asarray(dyn), rtol=0, atol=0.3)
    # scale calibrated over several batches covers each of them
    xs = [jnp.asarray(rng.standard_normal((4, 32)), jnp.float32)
          for _ in range(3)]
    s = calibrate_act_scale(xs)
    for xi in xs:
        got = linear_w8a8(xi, w_q, w_s, x_scale=s)
        xq = jnp.clip(jnp.round(xi / s), -128, 127).astype(jnp.int8)
        want = (xq.astype(jnp.int32) @ w_q.astype(jnp.int32)
                ).astype(jnp.float32) * s * w_s[None, :]
        assert_allclose(np.asarray(got), np.asarray(want),
                        rtol=1e-5, atol=1e-5)


def test_quantize_with_scale_matches_quantize_tensor():
    from repro.core.quantization import quantize_with_scale
    rng = np.random.default_rng(10)
    x = jnp.asarray(rng.standard_normal((6, 6)), jnp.float32)
    q_ref, s = quantize_tensor(x)
    q = quantize_with_scale(x, s)
    assert q.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q_ref))
