"""End-to-end int8 dataflow: producer-side activation emission.

The contract under test: ``plan_program``'s producer->consumer pass
assigns ``Epilogue`` descriptors so every fused int8 consumer receives
int8 activations emitted by its producer (in-kernel for the Pallas
megakernels, XLA-fused for structural convs), residual adds stay fp,
and the executed chain remains BIT-EXACT vs the int8 reference at
batch 1 — the quantize arithmetic moved across the producer/consumer
boundary, it did not change.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

from repro.core.efficientvit import (
    B1, B1_SMOKE, init_dsconv, init_efficientvit, init_mbconv)
from repro.core.fusion import plan_program, plan_report
from repro.core.program import Epilogue, Program, Site, execute, lower
from repro.core.quantization import (
    QTensor, quantize_act, quantize_efficientvit, quantize_tensor)
from repro.kernels import registry


def _qtree(seed, cfg=B1_SMOKE):
    return quantize_efficientvit(
        init_efficientvit(jax.random.PRNGKey(seed), cfg))


# ---------------------------------------------------------------------------
# epilogue assignment: structure at serving resolutions
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("res", [192, 224, 256])
@pytest.mark.parametrize("batch", [1, 4])
def test_b1_epilogue_assignment(res, batch, tmp_autotune_cache):
    """At every serving resolution/bucket the full B1 chain is covered:
    every fused int8 site's input arrives quantized, every producer's
    residual policy matches the (producer, consumer) residual pair."""
    qparams = _qtree(0, B1)
    program = lower(B1, batch=batch, image_size=res)
    plan = plan_program(program, qparams, autotune=False)
    assert all(d.fused and d.precision == "int8"
               for d in plan.decisions.values())
    assert all(d.q_in for d in plan.decisions.values())
    by_name = {s.name: s for s in program.sites}
    consumer = {prv.name: cur for prv, cur in
                zip(program.sites, program.sites[1:])}
    # the structural quantized stem conv and head conv take part too
    assert "stem.conv1" in plan.epilogues
    for name, ep in plan.epilogues.items():
        site = by_name[name]
        assert ep.out_dtype == "int8" and ep.scale == "dynamic"
        if site.residual:
            assert ep.residual == "post-add", name
        elif consumer[name].residual:
            assert ep.residual == "keep-fp", name
        else:
            assert ep.residual == "none", name
    # annotated program mirrors the plan (the executor-cache view)
    annotated = program.with_epilogues(plan)
    for s in annotated.sites:
        assert s.epilogue == plan.epilogues.get(s.name, s.epilogue) \
            or not s.epilogue.emits_q


def test_fp_plan_assigns_no_epilogues(tmp_autotune_cache):
    params = init_efficientvit(jax.random.PRNGKey(1), B1_SMOKE)
    plan = plan_program(lower(B1_SMOKE), params, autotune=False)
    assert plan.epilogues == {}
    assert not any(d.q_in for d in plan.decisions.values())


# ---------------------------------------------------------------------------
# producer-epilogue kernel parity vs the XLA-quantize reference
# ---------------------------------------------------------------------------

def test_mbconv_emit_matches_xla_quantize():
    """In-kernel emission == running the non-emitting kernel and
    quantizing its output in XLA, bit for bit (both keep-fp and pure)."""
    from repro.kernels.mbconv.ops import mbconv_apply_int8
    key = jax.random.PRNGKey(4)
    qp = quantize_efficientvit(init_mbconv(key, 8, 16, 4, jnp.float32))
    for stride in (1, 2):
        x = jax.random.normal(jax.random.fold_in(key, stride),
                              (2, 16, 16, 8))
        base = mbconv_apply_int8(qp, x, stride=stride, block_f=128)
        want = quantize_act(base)
        for residual in ("none", "keep-fp"):
            got = mbconv_apply_int8(
                qp, x, stride=stride,
                epilogue=Epilogue("int8", "dynamic", residual))
            assert isinstance(got, QTensor)
            np.testing.assert_array_equal(np.asarray(got.q),
                                          np.asarray(want.q))
            # scales may differ by FMA-fusion ulps between compilation
            # contexts (per-batch-element scale arithmetic reassociates)
            assert_allclose(np.asarray(got.scale), np.asarray(want.scale),
                            rtol=1e-6, atol=0)
            if residual == "keep-fp":   # fp preserved for the consumer's
                np.testing.assert_array_equal(   # residual add
                    np.asarray(got.fp), np.asarray(base))
            else:
                assert got.fp is None


def test_dsconv_emit_matches_xla_quantize():
    from repro.kernels.dsconv.ops import dsconv_apply_int8
    key = jax.random.PRNGKey(5)
    qp = quantize_efficientvit(init_dsconv(key, 8, 8, jnp.float32))
    x = jax.random.normal(key, (2, 12, 12, 8))
    base = dsconv_apply_int8(qp, x)
    want = quantize_act(base)
    got = dsconv_apply_int8(qp, x,
                            epilogue=Epilogue("int8", "dynamic", "none"))
    np.testing.assert_array_equal(np.asarray(got.q), np.asarray(want.q))
    # scales may differ by FMA-fusion ulps between compilation contexts
    assert_allclose(np.asarray(got.scale), np.asarray(want.scale),
                    rtol=1e-6, atol=0)


def test_dsconv_consumes_qtensor_bit_exact():
    """A producer-emitted QTensor input reproduces the fp-input path
    exactly at batch 1 (same absmax arithmetic, just moved)."""
    from repro.kernels.dsconv.ops import dsconv_apply_int8
    key = jax.random.PRNGKey(6)
    qp = quantize_efficientvit(init_dsconv(key, 8, 8, jnp.float32))
    x = jax.random.normal(key, (1, 12, 12, 8))
    via_fp = dsconv_apply_int8(qp, x)
    via_qt = dsconv_apply_int8(qp, quantize_act(x))
    np.testing.assert_array_equal(np.asarray(via_fp), np.asarray(via_qt))


def test_conv1x1_w8a8_emit_and_qtensor():
    from repro.core.quantization import conv2d_int8
    from repro.kernels.int8_matmul.ops import conv1x1_w8a8
    rng = np.random.default_rng(7)
    B, H, W, C, F = 2, 6, 6, 16, 32
    x = jnp.asarray(rng.standard_normal((B, H, W, C)), jnp.float32)
    qp = {"q": jnp.asarray(rng.integers(-127, 128, (1, 1, C, F)), jnp.int8),
          "scale": jnp.asarray(rng.uniform(0.005, 0.05, (F,)), jnp.float32),
          "bias": jnp.asarray(rng.standard_normal((F,)), jnp.float32)}
    base = conv1x1_w8a8(qp, x)
    # in-kernel emission == XLA quantize of the same fp output
    want = quantize_act(base)
    got = conv1x1_w8a8(qp, x, epilogue=Epilogue("int8", "dynamic", "none"))
    np.testing.assert_array_equal(np.asarray(got.q), np.asarray(want.q))
    assert_allclose(np.asarray(got.scale), np.asarray(want.scale),
                    rtol=1e-6, atol=0)
    # QTensor input at batch 1: same int8 values into the GEMM as the
    # conv2d_int8 reference quantize — dequant-epilogue ulps only (the
    # same 1e-5 window the pre-epilogue conv1x1 parity test uses)
    x1 = x[:1]
    ref = conv2d_int8(qp, x1)
    out = conv1x1_w8a8(qp, quantize_act(x1))
    assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_group_agg_matches_reference_chain():
    """The grouped int8 aggregation kernel == the reference
    conv2d_int8(dw) -> conv2d_int8(pw) chain, bit-exact at batch 1."""
    from repro.core.quantization import conv2d_int8
    from repro.core.relu_attention import MSAConfig, init_msa
    from repro.kernels.group_conv.ops import group_agg_apply_int8
    key = jax.random.PRNGKey(8)
    cfg = MSAConfig(32, head_dim=16, scales=(5,))
    qmsa = quantize_efficientvit(init_msa(key, cfg))
    agg = qmsa["aggreg"][0]
    C = 3 * cfg.total_dim
    qkv = jax.random.normal(key, (1, 8, 8, C))
    ref = conv2d_int8(agg["dw"]["qconv"], qkv, groups=C)
    ref = conv2d_int8(agg["pw"]["qconv"], ref, groups=3 * cfg.n_heads)
    out = group_agg_apply_int8(agg, qkv)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    # registry face: int8-only kind, apply == wrapper, ref == chain
    impl = registry.get_kernel("group_agg", "int8")
    assert impl.takes_q and impl.site_precision(agg) == "int8"
    site = Site("X.agg", "group_agg", "X", (), qkv.shape, qkv.shape,
                attrs={"scale": 5})
    np.testing.assert_array_equal(np.asarray(impl.apply(agg, qkv, site)),
                                  np.asarray(out))
    np.testing.assert_array_equal(np.asarray(impl.ref(agg, qkv, site)),
                                  np.asarray(ref))


# ---------------------------------------------------------------------------
# the chain: fused-with-epilogues vs the int8 reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("res,batch", [(32, 1), (32, 4), (64, 1), (96, 2)])
def test_int8_chain_parity_across_buckets(res, batch, tmp_autotune_cache):
    """Producer-epilogue chain vs the XLA-quantize reference across the
    serving (resolution, batch-bucket) grid: identical int8 arithmetic
    at batch 1 (same quantize decisions at every boundary; the logits
    may carry dequant-epilogue FMA ulps, and the pinned
    benchmarks/e2e_latency configuration is literally bit-exact),
    within quantization noise (top-1 preserved) otherwise."""
    qparams = _qtree(9)
    program = lower(B1_SMOKE, batch=batch, image_size=res)
    plan = plan_program(program, qparams, autotune=False)
    assert plan.epilogues, "no epilogues assigned"
    x = jax.random.normal(jax.random.PRNGKey(res + batch),
                          (batch, res, res, 3))
    ref = execute(program, qparams, x)
    fus = execute(program, qparams, x, plan=plan)
    assert bool((jnp.argmax(ref, -1) == jnp.argmax(fus, -1)).all())
    if batch == 1:
        assert_allclose(np.asarray(fus), np.asarray(ref),
                        rtol=1e-5, atol=1e-7)
    else:
        assert float(jnp.max(jnp.abs(ref - fus))) < 1e-2


def test_residual_adds_stay_fp(tmp_autotune_cache, int8_parity):
    """A residual consumer's add must see the producer's fp activation,
    never a dequantized int8 round-trip: the keep-fp boundaries exist in
    the plan, stripping one to a pure-int8 epilogue trips the fp guard
    (``act_fp``) instead of silently degrading, and the chain with the
    assigned plan emits bit-exact int8 codes at every boundary vs the
    all-fp-residual reference, its fp32 tail within the ulps
    ``int8_parity`` argues."""
    import dataclasses as dc
    qparams = _qtree(10)
    program = lower(B1_SMOKE, batch=1, image_size=64)
    plan = plan_program(program, qparams, autotune=False)
    keep_fp_sites = [n for n, ep in plan.epilogues.items()
                     if ep.residual == "keep-fp"]
    assert keep_fp_sites, "no keep-fp boundaries in the chain"
    x = jax.random.normal(jax.random.PRNGKey(11), (1, 64, 64, 3))
    int8_parity(program, qparams, x, plan)  # reference residuals run fp
    # a mis-assigned pure-int8 boundary in front of a residual consumer
    # must fail loudly (epilogue-assignment invariant), not approximate
    lossy_eps = dict(plan.epilogues)
    lossy_eps[keep_fp_sites[0]] = Epilogue("int8", "dynamic", "none")
    lossy = dc.replace(plan, epilogues=lossy_eps)
    with pytest.raises(ValueError, match="kept fp activation"):
        execute(program, qparams, x, plan=lossy)


def test_quantize_act_contract():
    """Per-batch-element scales == quantize_tensor at batch 1; keep_fp
    carries the exact input."""
    x = jax.random.normal(jax.random.PRNGKey(12), (3, 5, 5, 4))
    qt = quantize_act(x, keep_fp=True)
    assert qt.q.dtype == jnp.int8 and qt.scale.shape == (3,)
    assert qt.fp is x
    q1, s1 = quantize_tensor(x[:1])
    np.testing.assert_array_equal(np.asarray(qt.q[:1]), np.asarray(q1))
    assert float(qt.scale[0]) == float(s1)
    assert quantize_act(x).fp is None


# ---------------------------------------------------------------------------
# plan reuse: exact-batch donors for batch-dependent tile families
# ---------------------------------------------------------------------------

def test_reuse_exact_batch_for_batch_dependent_tiles(tmp_autotune_cache):
    """A kernel family that tunes batch-dependent tiles only inherits
    donor blocks from the SAME batch; per-sample-geometry matching
    (the default) keeps sharing across buckets."""

    class _Base(registry.KernelBase):
        kind, precision, dtype = "unit_bdt", "fp", "f32"

        def site_precision(self, params):
            return "fp"

        def tune(self, site, *, autotune=True, interpret=None):
            return {"block": site.in_shape[0]}    # batch-dependent!

        def apply(self, params, x, site, decision=None, *, interpret=None,
                  epilogue=None):
            return x

    def _program(batch):
        site = Site("X.bdt0", "unit_bdt", "X", (),
                    (batch, 4, 4, 8), (batch, 4, 4, 8))
        return Program(B1_SMOKE, batch, 4, (site,))

    try:
        registry.register(type("BDT", (_Base,),
                               {"batch_dependent_tiles": True}))
        donor = plan_program(_program(4), {}, autotune=False)
        assert donor.get("X.bdt0").blocks == {"block": 4}
        # different batch: no safe donor -> re-tuned, not reused
        other = plan_program(_program(2), {}, autotune=False, reuse=donor)
        d = other.get("X.bdt0")
        assert not d.reused and d.blocks == {"block": 2}
        # exact batch: donor accepted
        same = plan_program(_program(4), {}, autotune=False, reuse=donor)
        assert same.get("X.bdt0").reused
        # default (per-sample-geometry) families still share across batch
        registry.register(type("NBDT", (_Base,), {}))
        donor2 = plan_program(_program(4), {}, autotune=False)
        shared = plan_program(_program(2), {}, autotune=False, reuse=donor2)
        assert shared.get("X.bdt0").reused
    finally:
        registry.unregister("unit_bdt", "fp")


# ---------------------------------------------------------------------------
# serving: the quantized engine runs the int8 dataflow
# ---------------------------------------------------------------------------

def test_vision_engine_quantized_epilogue_dataflow(tmp_autotune_cache):
    from repro.core.efficientvit import efficientvit
    from repro.serving.vision import VisionEngine, VisionServeConfig
    key = jax.random.PRNGKey(13)
    params = init_efficientvit(key, B1_SMOKE)
    eng = VisionEngine.quantized(
        params, B1_SMOKE, VisionServeConfig(microbatch=2, autotune=False))
    # the plan assigns producer epilogues, and the cached (annotated)
    # program exposes the delivered dtypes
    assert eng.plan.epilogues
    assert any(s.epilogue.emits_q for s in eng.program.sites)
    imgs = jax.random.normal(key, (3, 64, 64, 3))
    logits = eng.logits(imgs)
    ref = jnp.concatenate(
        [efficientvit(eng.params, imgs[i:i + 1], B1_SMOKE)
         for i in range(3)])
    # the ragged tail runs a 1-bucket: that sample is the batch-1
    # producer-epilogue chain vs its per-sample reference (dequant ulps)
    assert_allclose(np.asarray(logits[2:]), np.asarray(ref[2:]),
                    rtol=1e-5, atol=1e-7)
    assert_allclose(np.asarray(logits), np.asarray(ref),
                    rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# accounting: delivered bytes and the cycle model's residual-fp charge
# ---------------------------------------------------------------------------

def test_delivered_bytes_match_analytic_within_residual_fp(
        tmp_autotune_cache):
    """Per fused int8 conv site: delivered == analytic steady-state
    + outn (residual-fp correction) when the epilogue keeps fp,
    - 3*outn when the boundary is pure int8, both sides exact."""
    qparams = _qtree(14)
    program = lower(B1_SMOKE, batch=1, image_size=64)
    plan = plan_program(program, qparams, autotune=False)
    for r in plan_report(plan):
        if not (r["fused"] and r["kind"] in ("mbconv", "dsconv")):
            continue
        assert r["q_in"], r["site"]
        B, H, W, C, _, F, stride = plan.get(r["site"]).shape
        outn = (B * (H // stride) * (W // stride) * F
                if r["kind"] == "mbconv" else B * H * W * F)
        ep = r["epilogue"]
        if ep is None or not ep.emits_q:
            corr = 0
        elif ep.keeps_fp:
            corr = outn          # fp copy + int8 copy cross the boundary
        else:
            corr = -3 * outn     # pure 1 byte/element boundary
        assert r["hbm_delivered"] == r["hbm_fused"] + corr, r["site"]


def test_cycle_model_charges_residual_fp(tmp_autotune_cache):
    from repro.core.accelerator_model import analyze_program
    qparams = _qtree(15, B1)
    program = lower(B1, batch=1)
    plan = plan_program(program, qparams, autotune=False)
    plain, _, _ = analyze_program(program)
    annotated, _, _ = analyze_program(program.with_epilogues(plan))
    assert annotated.dram_bytes >= plain.dram_bytes
    assert annotated.total_macs == plain.total_macs
