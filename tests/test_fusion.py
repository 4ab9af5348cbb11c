"""Fused inference execution path: megakernels, fusion-plan dispatch,
block-size autotuner.

The contract under test: routing through the fused Pallas kernels must be
a pure performance decision — ``plan=None`` is byte-identical to the seed
reference path, and any plan-routed forward agrees with it within 1e-3.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

from proptest import sweep

from repro.common.errors import PlanError
from repro.core.efficientvit import (
    B1_SMOKE, efficientvit, init_efficientvit, init_mbconv, mbconv)
from repro.core.program import execute, lower
from repro.core.relu_attention import MSAConfig, init_msa, msa
from repro.kernels import autotune as autotune_mod
from repro.kernels.autotune import autotune, pad_to_multiple
from repro.kernels.mbconv.kernel import mbconv_fused
from repro.kernels.mbconv.ops import mbconv_apply
from repro.kernels.mbconv.ref import mbconv_ref
from repro.kernels.relu_attn.kernel import relu_attn_noncausal
from repro.kernels.relu_attn.ops import msa_batched_attention
from repro.kernels.relu_attn.ref import relu_attn_noncausal_ref


# ---------------------------------------------------------------------------
# fused MBConv megakernel
# ---------------------------------------------------------------------------

@sweep(n_cases=8, seed=11)
def test_mbconv_fused_sweep(rng):
    b = int(rng.integers(1, 3))
    hw = int(rng.choice([8, 12, 16]))
    c = int(rng.choice([4, 8, 16]))
    m = c * int(rng.choice([2, 4]))
    f = int(rng.choice([8, 16, 24]))
    stride = int(rng.choice([1, 2]))
    bf = int(rng.choice([8, 64, f]))  # exercises ragged c_out tiles
    x = jnp.asarray(rng.standard_normal((b, hw, hw, c)), jnp.float32)
    w1 = jnp.asarray(rng.standard_normal((c, m)) * 0.3, jnp.float32)
    b1 = jnp.asarray(rng.standard_normal((m,)), jnp.float32)
    dw_w = jnp.asarray(rng.standard_normal((3, 3, m)) * 0.3, jnp.float32)
    dw_b = jnp.asarray(rng.standard_normal((m,)), jnp.float32)
    w2 = jnp.asarray(rng.standard_normal((m, f)) * 0.3, jnp.float32)
    b2 = jnp.asarray(rng.standard_normal((f,)), jnp.float32)
    out = mbconv_fused(x, w1, b1, dw_w, dw_b, w2, b2, stride=stride,
                       block_f=bf)
    ref = mbconv_ref(x, w1, b1, dw_w, dw_b, w2, b2, stride=stride)
    assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_mbconv_apply_matches_model_block():
    """BN-folded megakernel == core.efficientvit.mbconv (SAME, stride 1/2)."""
    key = jax.random.PRNGKey(0)
    for stride in (1, 2):
        p = init_mbconv(key, 8, 16, 4, jnp.float32)
        x = jax.random.normal(jax.random.fold_in(key, stride), (2, 16, 16, 8))
        ref = mbconv(p, x, stride=stride)
        out = mbconv_apply(p, x, stride=stride, block_f=128)
        assert_allclose(np.asarray(out), np.asarray(ref),
                        rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# single-pass attention (incl. ragged N -> padded tiles) + folded MSA launch
# ---------------------------------------------------------------------------

@sweep(n_cases=8, seed=12)
def test_relu_attn_singlepass_ragged_sweep(rng):
    """Token counts NOT divisible by block_n must pad, not fall back."""
    bh = int(rng.integers(1, 5))
    n = int(rng.integers(5, 200))                 # deliberately ragged
    d = int(rng.choice([16, 32]))
    bn = int(rng.choice([16, 32, 64]))
    q, k, v = (jnp.asarray(rng.standard_normal((bh, n, d)), jnp.float32)
               for _ in range(3))
    out = relu_attn_noncausal(q, k, v, block_n=bn)
    ref = relu_attn_noncausal_ref(q, k, v)
    assert out.shape == ref.shape
    assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_msa_batched_matches_per_branch():
    """Folding (scale, batch, head) into one grid axis == per-branch calls."""
    rng = np.random.default_rng(0)
    S, B, N, h, d = 3, 2, 17, 2, 16
    qkv = jnp.asarray(rng.standard_normal((S, B, N, 3 * h * d)), jnp.float32)
    out = msa_batched_attention(qkv, h, d, block_n=16)
    for s in range(S):
        t = qkv[s].reshape(B, N, 3, h, d)
        for hi in range(h):
            ref = relu_attn_noncausal_ref(t[:, :, 0, hi], t[:, :, 1, hi],
                                          t[:, :, 2, hi])
            got = out[s].reshape(B, N, h, d)[:, :, hi]
            assert_allclose(np.asarray(got), np.asarray(ref),
                            rtol=2e-5, atol=2e-5)


def test_msa_plan_matches_reference(tmp_autotune_cache):
    """The fused MSA module (what a plan routes a fused msa site to)
    matches the reference module, multi-scale and ragged."""
    from repro.kernels.relu_attn.ops import msa_fused_apply
    key = jax.random.PRNGKey(1)
    cfg = MSAConfig(channels=32, head_dim=16, scales=(3, 5))
    params = init_msa(key, cfg)
    x = jax.random.normal(key, (2, 7, 7, 32))     # ragged N = 49
    ref = msa(params, x, cfg)
    out = msa_fused_apply(params, x, cfg.n_heads, cfg.head_dim)
    assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# fusion plan: full-model forward + dispatch behavior
# ---------------------------------------------------------------------------

def test_efficientvit_fused_forward_matches_reference(tmp_autotune_cache):
    from repro.core.fusion import launch_counts, plan_program
    key = jax.random.PRNGKey(0)
    params = init_efficientvit(key, B1_SMOKE)
    x = jax.random.normal(key, (2, 64, 64, 3))
    program = lower(B1_SMOKE, batch=2)
    plan = plan_program(program, params, autotune=False)
    assert plan.n_fused() == len(plan.decisions)  # everything qualifies
    ref = jax.jit(lambda p, x: efficientvit(p, x, B1_SMOKE))(params, x)
    fus = jax.jit(
        lambda p, x: execute(program, p, x, plan=plan))(params, x)
    assert_allclose(np.asarray(fus), np.asarray(ref), rtol=1e-3, atol=1e-3)
    lc = launch_counts(plan)
    assert lc["fused"] == len(plan.decisions)     # one launch per site
    assert lc["reference"] > lc["fused"]
    # every MSA module collapses to exactly one attention launch
    for r_ in plan.decisions.values():
        assert r_.fused


def test_quantized_blocks_forced_fp_route_to_reference(tmp_autotune_cache):
    """precision="fp" on a FIX8 tree preserves the old demotion behavior
    (the fp megakernels can't consume int8 weights) — and the plan-routed
    forward still matches the reference quantized path."""
    from repro.core.fusion import plan_program
    from repro.core.quantization import quantize_efficientvit
    key = jax.random.PRNGKey(2)
    params = init_efficientvit(key, B1_SMOKE)
    qparams = quantize_efficientvit(params)
    program = lower(B1_SMOKE, batch=1)
    plan = plan_program(program, qparams, autotune=False, precision="fp")
    conv_sites = [d for d in plan.decisions.values()
                  if d.kind in ("dsconv", "mbconv")]
    assert conv_sites and all(not d.fused and d.reason == "quantized"
                              for d in conv_sites)
    x = jax.random.normal(key, (1, 64, 64, 3))
    ref = efficientvit(qparams, x, B1_SMOKE)
    out = execute(program, qparams, x, plan=plan)
    assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-3, atol=1e-3)


def test_vision_engine_serves_fused(tmp_autotune_cache):
    from repro.serving.vision import VisionEngine, VisionServeConfig
    key = jax.random.PRNGKey(3)
    params = init_efficientvit(key, B1_SMOKE)
    eng = VisionEngine(params, B1_SMOKE,
                       VisionServeConfig(microbatch=2, autotune=False))
    imgs = jax.random.normal(key, (3, 64, 64, 3))   # ragged microbatch
    logits = eng.logits(imgs)
    assert logits.shape == (3, B1_SMOKE.num_classes)
    ref = efficientvit(params, imgs, B1_SMOKE)
    assert_allclose(np.asarray(logits), np.asarray(ref),
                    rtol=1e-3, atol=1e-3)
    labels = eng.classify(imgs)
    assert labels.shape == (3,)


# ---------------------------------------------------------------------------
# autotuner cache
# ---------------------------------------------------------------------------

def test_autotune_cache_roundtrip(tmp_autotune_cache):
    calls = []

    def bench(cand):
        calls.append(cand["b"])
        return jnp.zeros(())

    cands = [{"b": 8}, {"b": 16}]
    first = autotune("unit", (3, 5, "f32"), cands, bench)
    assert first in cands and calls
    assert tmp_autotune_cache.exists()

    # fresh process simulation: drop memory, reload from disk -> no sweep
    autotune_mod.clear_memory_cache()
    calls.clear()
    again = autotune("unit", (3, 5, "f32"), cands, bench)
    assert again == first
    assert calls == []                       # identical choice, no re-sweep

    # unknown key without a bench (jit tracing) -> heuristic first candidate
    assert autotune("unit", (9, 9, "f32"), cands, None) == {"b": 8}


def test_autotune_disqualifies_failing_candidates(tmp_autotune_cache):
    def bench(cand):
        if cand["b"] == 8:
            raise ValueError("tile too big for VMEM")
        return jnp.zeros(())

    with pytest.warns(RuntimeWarning, match=r"\{'b': 8\} disqualified"):
        choice = autotune("unit2", (1,), [{"b": 8}, {"b": 16}], bench)
    assert choice == {"b": 16}


def test_autotune_all_candidates_failing(tmp_autotune_cache):
    """Interpreter: the heuristic default, uncached.  Compiled backend:
    a typed error naming every refusal — never a silent default."""
    def bench(cand):
        raise ValueError(f"tile {cand['b']} refused")

    cands = [{"b": 8}, {"b": 16}]
    with pytest.warns(RuntimeWarning, match="disqualified"):
        assert autotune("unit3", (1,), cands, bench,
                        interpret=True) == {"b": 8}
    # an omitted flag resolves from the backend: the cpu interprets
    with pytest.warns(RuntimeWarning, match="disqualified"):
        assert autotune("unit3", (3,), cands, bench) == {"b": 8}
    with pytest.warns(RuntimeWarning, match="disqualified"), \
            pytest.raises(PlanError, match="tile 8 refused.*tile 16 refused"):
        autotune("unit3", (2,), cands, bench, interpret=False)
    assert autotune_mod.export_entries() == {}      # nothing was cached


def test_pad_to_multiple():
    x = jnp.ones((2, 5, 3))
    padded, n = pad_to_multiple(x, 1, 4)
    assert padded.shape == (2, 8, 3) and n == 5
    assert float(padded[:, 5:].sum()) == 0.0
    same, n2 = pad_to_multiple(x, 1, 5)
    assert same is x and n2 == 5


@pytest.mark.parametrize("backend,expect", [("cpu", True), ("tpu", False),
                                            ("gpu", RuntimeError)])
def test_default_interpret_follows_backend(backend, expect, monkeypatch):
    """Interpret only on the CPU backend, compile on TPU, and refuse any
    other backend instead of quietly interpreting there."""
    from repro.kernels import compat
    monkeypatch.setattr(compat, "_backend", lambda: backend)
    assert compat.default_interpret(True) is True
    assert compat.default_interpret(False) is False
    if expect is RuntimeError:
        with pytest.raises(RuntimeError, match="gpu"):
            compat.default_interpret()
    else:
        assert compat.default_interpret() is expect
