"""The path the benchmark serves, at smoke size.

Each benchmark cell builds its engine one way (``bench/benchlib/
system.py::build_engine``): bucket 8 only, no autotuning, the
configuration's precision, requests through ``engine.scheduler()``.
These tests build the engine the same way on the smoke configurations
and hold its answers to the plain reference forward, and they check
that importing the vision serving path loads nothing of the seed's
language-model stack.
"""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest
from numpy.testing import assert_allclose

from repro.core.efficientvit import (
    B1_SMOKE, L_SMOKE, efficientvit, init_efficientvit)
from repro.core.quantization import quantize_efficientvit
from repro.serving.scheduler import Request
from repro.serving.vision import VisionEngine, VisionServeConfig

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

# the language-model stack's modules, none of which the vision path uses
LM_MODULES = ("repro.models", "repro.configs.base", "repro.serving.engine",
              "repro.layers.attention", "repro.layers.moe",
              "repro.layers.mamba2")


def test_vision_path_imports_no_lm_stack():
    code = ("import sys\n"
            "import repro.serving.vision, repro.serving.scheduler\n"
            "print('\\n'.join(sorted(sys.modules)))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.abspath(SRC))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300,
                         check=True).stdout.split()
    assert "repro.serving.vision" in out
    loaded = [m for m in out
              if any(m == lm or m.startswith(lm + ".") for lm in LM_MODULES)]
    assert not loaded, loaded


def _rel_err(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


# (cell, smoke config, precision as build_engine passes it)
CELLS = [("b1-fp32", B1_SMOKE, "fp"), ("b1-int8", B1_SMOKE, "int8"),
         ("l2-fp32", L_SMOKE, "fp")]


@pytest.mark.parametrize("cell,cfg,precision", CELLS,
                         ids=[c[0] for c in CELLS])
def test_benchmark_engine_matches_the_reference(cell, cfg, precision,
                                                tmp_autotune_cache):
    params = init_efficientvit(jax.random.PRNGKey(18), cfg)
    if precision == "int8":
        params = quantize_efficientvit(params)
    x = np.asarray(jax.random.normal(
        jax.random.PRNGKey(19), (8, cfg.image_size, cfg.image_size, 3)),
        np.float32)
    with jax.default_matmul_precision("highest"):
        eng = VisionEngine(params, cfg, VisionServeConfig(
            microbatch=8, buckets=(8,), precision=precision,
            autotune=False))
        # every site of the served plan runs its kernel: the smoke sizes
        # fit every kernel's VMEM budget at both precisions
        assert all(d.fused for d in eng.plan.decisions.values()), \
            eng.plan.table()
        assert all(d.precision == precision
                   for d in eng.plan.decisions.values())
        got = eng.scheduler().serve(
            [Request(rid=i, image=x[i]) for i in range(8)])
        if precision == "int8":
            # dynamic activation scales are per image in the kernels and
            # per tensor in the reference, so the reference runs image
            # by image
            want = np.concatenate([efficientvit(params, x[i:i + 1], cfg)
                                   for i in range(8)])
        else:
            want = efficientvit(params, x, cfg)
    assert eng.cache.keys()[0].batch == 8
    if precision == "int8":
        # per-image scales make each image's codes the per-image
        # reference's; the fp32 tail carries dequant FMA ulps only
        # (conftest.int8_parity's argument)
        assert_allclose(got, np.asarray(want), rtol=1e-5, atol=1e-7)
    else:
        # fp32 at highest on both sides; the interpreted kernels only sum
        # in their own order (test_fused_plan_matches_the_plain_reference)
        assert _rel_err(got, want) < 1e-5
