"""Compile the main-path Pallas kernels for a TPU v5e, without the chip.

The TPU compiler is installed next to the CPU backend, so every kernel
of the served B1@224 forward can be lowered and compiled for a
*described* v5e (``get_topology_desc``) from a CPU-only process.  What
Mosaic refuses here — unaligned blocks, gathers, too much scoped VMEM —
is what would silently demote a site (or a whole executor) to the
reference path on the chip, so these compiles guard the chip path at no
chip time.  Nothing here runs a kernel: interpret-mode parity lives in
the other test files.

The topology is described only inside a module-scoped fixture: a TPU
library loaded while a module is imported would make pytest-xdist
workers collect different test sets.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.efficientvit import B1, init_efficientvit
from repro.core.program import SuperSite, execute, lower
from repro.core.quantization import quantize_efficientvit

BATCHES = (1, 8)
SIZE = 224


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep it out entirely."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


@pytest.fixture(scope="module")
def b1_params():
    fp = init_efficientvit(jax.random.PRNGKey(0), B1)
    return {"fp": fp, "int8": quantize_efficientvit(fp)}


def _compile(fn, args, sharding):
    """AOT-compile ``fn`` for the described chip; returns the HLO text."""
    sds = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        args)
    text = jax.jit(fn).lower(*sds).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"
    return text


def _z(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


# ---------------------------------------------------------------------------
# per-site conv kernels: one stride-1 and one stride-2 site per family
# ---------------------------------------------------------------------------

# (name, (H, W, C), mid, F, stride) at B1@224
MBCONV_SITES = {
    "S3.evit0.mb": ((14, 14, 128), 512, 128, 1),
    "S3.down": ((28, 28, 64), 256, 128, 2),
}
# stem.ds0 is B1's only dsconv (stride 1); the stride-2 case is the same
# shape at stride 2, which the kernel family supports
DSCONV_SITES = {
    "stem.ds0": ((112, 112, 16), 16, 1),
    "stem.ds0@s2": ((112, 112, 16), 16, 2),
}


def _mbconv_case(variant, b, hwc, mid, f, s):
    from repro.kernels.mbconv import kernel as k
    H, W, C = hwc
    i8, f32 = jnp.int8, jnp.float32
    if variant == "fp":
        args = (_z((b, H, W, C)), _z((C, mid)), _z((mid,)), _z((3, 3, mid)),
                _z((mid,)), _z((mid, f)), _z((f,)))
        return (lambda *a: k.mbconv_fused(*a, stride=s, interpret=False),
                args)
    args = (_z((b, H, W, C), i8), _z((b,)), _z((C, mid), i8), _z((mid,)),
            _z((mid,)), _z((3, 3, mid), i8), _z((mid,)), _z((mid,)),
            _z((mid, f), i8), _z((f,)), _z((f,)))
    if variant == "int8":
        return (lambda *a: k.mbconv_fused_int8(*a, stride=s, interpret=False),
                args)
    return (lambda *a: k.mbconv_fused_int8_emit(
        *a, stride=s, keep_fp=True, interpret=False), args)


def _dsconv_case(variant, b, hwc, f, s):
    from repro.kernels.dsconv import kernel as k
    H, W, C = hwc
    i8 = jnp.int8
    if variant == "fp":
        args = (_z((b, H, W, C)), _z((3, 3, C)), _z((C,)), _z((C, f)),
                _z((f,)))
        return (lambda *a: k.dsconv_fused(*a, stride=s, interpret=False),
                args)
    args = (_z((b, H, W, C), i8), _z((b,)), _z((3, 3, C), i8), _z((C,)),
            _z((C,)), _z((C, f), i8), _z((f,)), _z((f,)))
    if variant == "int8":
        return (lambda *a: k.dsconv_fused_int8(*a, stride=s, interpret=False),
                args)
    return (lambda *a: k.dsconv_fused_int8_emit(
        *a, stride=s, keep_fp=True, interpret=False), args)


@pytest.mark.parametrize("batch", BATCHES)
@pytest.mark.parametrize("variant", ["fp", "int8", "int8_emit"])
@pytest.mark.parametrize("site", sorted(MBCONV_SITES))
def test_mbconv_compiles(site, variant, batch, one_chip, no_compile_cache):
    fn, args = _mbconv_case(variant, batch, *MBCONV_SITES[site])
    _compile(fn, args, one_chip)


@pytest.mark.parametrize("batch", BATCHES)
@pytest.mark.parametrize("variant", ["fp", "int8", "int8_emit"])
@pytest.mark.parametrize("site", sorted(DSCONV_SITES))
def test_dsconv_compiles(site, variant, batch, one_chip, no_compile_cache):
    fn, args = _dsconv_case(variant, batch, *DSCONV_SITES[site])
    _compile(fn, args, one_chip)


# EfficientViT-L2@224 at bucket 8: the FusedMBConv kernel at its widest
# stride-2 site and a residual stride-1 site (the plan's mid tiles), and
# the GELU MBConv kernel at S4.down, whose 6144-wide mid is the largest
# block of either model
FMBCONV_SITES = {"S1.down": ((112, 112, 32), 512, 64, 2, False, 128),
                 "S2.fmb0": ((28, 28, 128), 512, 128, 1, True, 256)}


@pytest.mark.parametrize("site", sorted(FMBCONV_SITES))
def test_fmbconv_compiles(site, one_chip, no_compile_cache):
    from repro.kernels.fmbconv.kernel import fmbconv_fused
    (H, W, C), mid, f, s, residual, bm = FMBCONV_SITES[site]
    args = (_z((8, H, W, C)), _z((3, 3, C, mid)), _z((mid,)),
            _z((mid, f)), _z((f,)))
    text = _compile(lambda *a: fmbconv_fused(
        *a, stride=s, block_m=bm, act="gelu_tanh", residual=residual,
        interpret=False), args, one_chip)
    assert "fmbconv_op" in text       # the name the device trace reads


def test_resblock_compiles(one_chip, no_compile_cache):
    """L2@224's ResBlock stem at bucket 8: both 3x3 convs of 32 channels
    at 112x112, GELU, the residual, in one launch."""
    from repro.kernels.resblock.kernel import resblock_fused
    args = (_z((8, 112, 112, 32)), _z((3, 3, 32, 32)), _z((32,)),
            _z((3, 3, 32, 32)), _z((32,)))
    text = _compile(lambda *a: resblock_fused(
        *a, act="gelu_tanh", interpret=False), args, one_chip)
    assert "resblock_op" in text      # the name the device trace reads


def test_mbconv_gelu_compiles_at_l2_s4_down(one_chip, no_compile_cache):
    from repro.kernels.mbconv import kernel as k
    fn, args = _mbconv_case("fp", 8, (14, 14, 256), 6144, 512, 2)
    _compile(lambda *a: k.mbconv_fused(*a, stride=2, act="gelu_tanh",
                                       interpret=False), args, one_chip)


# ---------------------------------------------------------------------------
# super-sites: B1@224's S1 / S2 chains (both start with a stride-2 member)
# ---------------------------------------------------------------------------

SUPERSITES = {"S1": ("S1.mb0", "S1.mb1"),
              "S2": ("S2.mb0", "S2.mb1", "S2.mb2")}


@pytest.mark.parametrize("batch", BATCHES)
@pytest.mark.parametrize("precision", ["fp", "int8"])
@pytest.mark.parametrize("chain", sorted(SUPERSITES))
def test_supersite_compiles(chain, precision, batch, b1_params, one_chip,
                            no_compile_cache):
    from repro.kernels.supersite import kernel as k
    from repro.kernels.supersite.ops import (
        choose_block_rows, make_fp_geom, make_int8_geom)
    from repro.kernels.supersite.pack import pack_weights

    sup = SuperSite.of(lower(B1, batch=batch, image_size=SIZE),
                       SUPERSITES[chain])
    pack = pack_weights(b1_params[precision], sup, precision)
    _, H, W, C = sup.in_shape
    if precision == "fp":
        geom = make_fp_geom(sup, pack, choose_block_rows(sup))
        fn = lambda x, w: k.supersite_fused(x, w, geom=geom,
                                            interpret=False)
        args = (_z((batch, H, W, C)), _z(pack.fp.shape))
    else:
        geom = make_int8_geom(sup, pack)
        fn = lambda x, s, wq, wf: k.supersite_fused_int8(
            x, s, wq, wf, geom=geom, exit_emit=True, keep_fp=True,
            interpret=False)
        args = (_z((batch, H, W, C), jnp.int8), _z((batch,)),
                _z(pack.q.shape, jnp.int8), _z(pack.fp.shape))
    _compile(fn, args, one_chip)


# B1@384 at batch 8: the super-sites the planner forms there (fp bands S1
# and S2 at 16 rows; int8 groups S2 only, S1 runs per site).  The stem
# DSConv at 384 is left out: Mosaic refuses it (118 MiB fp / 98 MiB int8
# of scoped VMEM against the 64 MiB limit: C = 16 pads to 128 lanes).
@pytest.mark.parametrize("precision", ["fp", "int8"])
def test_supersite_compiles_384(precision, b1_params, one_chip,
                                no_compile_cache):
    from repro.core.fusion import plan_program
    from repro.kernels.supersite import kernel as k
    from repro.kernels.supersite.ops import make_fp_geom, make_int8_geom
    from repro.kernels.supersite.pack import pack_weights

    params, batch = b1_params[precision], 8
    program = lower(B1, batch=batch, image_size=384)
    plan = plan_program(program, params, interpret=False, autotune=False)
    groups = {g.name: g for g in plan.groups.values()}
    assert sorted(groups) == (["S1.ss0", "S2.ss0"] if precision == "fp"
                              else ["S2.ss0"])
    for g in groups.values():
        sup = SuperSite.of(program, g.members, name=g.name)
        pack = pack_weights(params, sup, precision)
        _, H, W, C = sup.in_shape
        if precision == "fp":
            geom = make_fp_geom(sup, pack, g.blocks["block_rows"])
            fn = lambda x, w, geom=geom: k.supersite_fused(
                x, w, geom=geom, interpret=False)
            args = (_z((batch, H, W, C)), _z(pack.fp.shape))
        else:
            geom = make_int8_geom(sup, pack)
            fn = lambda x, s, wq, wf, geom=geom: k.supersite_fused_int8(
                x, s, wq, wf, geom=geom, exit_emit=True, keep_fp=True,
                interpret=False)
            args = (_z((batch, H, W, C), jnp.int8), _z((batch,)),
                    _z(pack.q.shape, jnp.int8), _z(pack.fp.shape))
        _compile(fn, args, one_chip)


# ---------------------------------------------------------------------------
# MSA sites: attention core, W8A8 projections, grouped aggregation
# ---------------------------------------------------------------------------

# (H*W tokens, channels, heads) of B1@224's S3 and S4 MSA sites; two
# branches (identity + one 5x5 aggregation scale), head_dim 16
MSA_SITES = {"S3": (196, 128, 8), "S4": (49, 256, 16)}


@pytest.mark.parametrize("batch", BATCHES)
@pytest.mark.parametrize("stage", sorted(MSA_SITES))
def test_relu_attn_compiles(stage, batch, one_chip, no_compile_cache):
    from repro.kernels.relu_attn.kernel import relu_attn_noncausal
    n, _, heads = MSA_SITES[stage]
    bh = 2 * batch * heads
    args = (_z((bh, n, 16)),) * 3
    _compile(lambda q, k, v: relu_attn_noncausal(q, k, v, interpret=False),
             args, one_chip)


@pytest.mark.parametrize("batch", BATCHES)
@pytest.mark.parametrize("variant", ["qkv", "proj_emit"])
@pytest.mark.parametrize("stage", sorted(MSA_SITES))
def test_int8_matmul_compiles(stage, variant, batch, one_chip,
                              no_compile_cache):
    from repro.kernels.int8_matmul import kernel as k
    n, c, _ = MSA_SITES[stage]
    m = batch * n
    if variant == "qkv":          # (B*N, C) @ (C, 3C), per-row scales
        args = (_z((m, c), jnp.int8), _z((c, 3 * c), jnp.int8), _z((m,)),
                _z((3 * c,)))
        fn = lambda x, w, xs, ws: k.int8_matmul(x, w, xs, ws,
                                                interpret=False)
    else:                         # (B*N, 2C) @ (2C, C), emits int8 per image
        args = (_z((m, 2 * c), jnp.int8), _z((2 * c, c), jnp.int8),
                _z((batch,)), _z((c,)), _z((c,)))
        fn = lambda x, w, xs, ws, b: k.int8_matmul_emit(
            x, w, xs, ws, rows_per_group=n, bias=b, keep_fp=True,
            interpret=False)
    _compile(fn, args, one_chip)


@pytest.mark.parametrize("batch", BATCHES)
@pytest.mark.parametrize("stage", sorted(MSA_SITES))
def test_group_agg_compiles(stage, batch, one_chip, no_compile_cache):
    from repro.kernels.group_conv.kernel import group_agg_int8
    n, c, _ = MSA_SITES[stage]
    hw = int(round(n ** 0.5))
    C = 3 * c
    args = (_z((batch, hw, hw, C), jnp.int8), _z((batch,)),
            _z((5, 5, C), jnp.int8), _z((C,)), _z((C,)),
            _z((C, C), jnp.int8), _z((C,)), _z((C,)))
    _compile(lambda *a: group_agg_int8(*a, interpret=False), args, one_chip)


# ---------------------------------------------------------------------------
# the whole served forward, as the executor cache jits it
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("precision", ["fp", "int8"])
def test_forward_compiles(precision, b1_params, one_chip, no_compile_cache):
    from repro.core.fusion import launch_counts, plan_program
    params = b1_params[precision]
    program = lower(B1, batch=8, image_size=SIZE)
    plan = plan_program(program, params, interpret=False, autotune=False)
    assert not plan.interpret
    assert all(d.fused for d in plan.decisions.values()), plan.table()
    text = _compile(lambda p, x: execute(program, p, x, plan=plan),
                    (params, _z((8, SIZE, SIZE, 3))), one_chip)
    # every planned fused launch is a Mosaic kernel in the program (the
    # int8 MSA sites add their projection GEMMs on top)
    assert text.count("tpu_custom_call") >= launch_counts(plan)["fused"]
