"""Jitted wrapper + registry impl: the fused FusedMBConv kernel.

``fmbconv_apply(params, x)`` consumes the L series' {'spatial', 'point'}
conv+BN pair (folding BN on the fly) and runs the kernel, the block's
residual add included.  The kind ships fp32 only: int8 L-series trees
are refused by ``core.quantization.quantize_efficientvit``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.quantization import fold_bn_into_conv
from repro.kernels.autotune import autotune, shape_key
from repro.kernels.compat import VMEM_BUDGET_BYTES, default_interpret
from repro.kernels.fmbconv.kernel import fmbconv_fused
from repro.kernels.registry import KernelBase, register

# mid tiles: the lane dim of the 3x3 weight block, so a multiple of 128
# (or the whole mid, which the kernel uses whenever mid <= block_m)
BLOCK_M_CANDIDATES = ({"block_m": 256}, {"block_m": 512}, {"block_m": 128})


def fmbconv_vmem_bytes(h: int, w: int, c_in: int, mid: int, f: int,
                       stride: int = 1, block_m: int = 256) -> int:
    """Analytic per-grid-step VMEM (logical fp32 bytes): the input block
    and its padded scratch, one mid tile with its weights, the
    projection accumulator and the output block."""
    ho, wo = h // stride, w // stride
    bm = min(block_m, mid)
    return 4 * (h * w * c_in + (h + 2) * (w + 2) * c_in
                + ho * wo * bm + 9 * c_in * bm + bm * f
                + 2 * ho * wo * f)


def fitting_blocks(x_shape, mid: int, f: int, stride: int = 1) -> tuple:
    """The candidate mid tiles whose analytic VMEM fits the budget, in
    preference order."""
    _, H, W, C = x_shape
    return tuple(c for c in BLOCK_M_CANDIDATES if fmbconv_vmem_bytes(
        H, W, C, mid, f, stride, c["block_m"]) <= VMEM_BUDGET_BYTES)


def tune_block_m(x_shape, mid: int, f: int, *, stride: int = 1,
                 allow_sweep: bool = True,
                 interpret: bool | None = None) -> int:
    """Autotuned mid tile for a FusedMBConv shape (cached on disk, keyed
    like every conv family: batch, spatial dims, channels, backend),
    among the tiles that fit; without a sweep, the first that fits."""
    B, H, W, C = x_shape
    cands = fitting_blocks(x_shape, mid, f, stride) or BLOCK_M_CANDIDATES
    interpret = default_interpret(interpret)
    key = shape_key(batch=B, spatial=(H, W), c=C, mid=mid, f=f,
                    stride=stride, dtype="f32",
                    backend="interp" if interpret else "compiled")

    def bench(cand):
        return fmbconv_fused(
            jnp.zeros((B, H, W, C), jnp.float32),
            jnp.zeros((3, 3, C, mid)), jnp.zeros((mid,)),
            jnp.zeros((mid, f)), jnp.zeros((f,)), stride=stride,
            block_m=cand["block_m"], interpret=interpret)

    choice = autotune("fmbconv", key, cands,
                      bench if allow_sweep else None, interpret=interpret)
    return choice["block_m"]


@functools.partial(jax.jit, static_argnames=(
    "stride", "block_m", "act", "residual", "interpret"))
def fmbconv_op(x, w1, b1, w2, b2, *, stride: int = 1, block_m: int = 256,
               act: str = "gelu_tanh", residual: bool = False,
               interpret: bool | None = None):
    return fmbconv_fused(x, w1, b1, w2, b2, stride=stride, block_m=block_m,
                         act=act, residual=residual, interpret=interpret)


def fmbconv_apply(params, x, *, stride: int = 1, block_m: int | None = None,
                  act: str = "gelu_tanh", residual: bool = False,
                  interpret: bool | None = None):
    """{'spatial', 'point'} conv+BN block -> the fused kernel; matches
    ``core.efficientvit.fmbconv`` (plus ``x`` when ``residual``)."""
    w1, b1 = fold_bn_into_conv(params["spatial"]["conv"],
                               params["spatial"]["bn"])      # (3,3,C,M)
    w2_4, b2 = fold_bn_into_conv(params["point"]["conv"],
                                 params["point"]["bn"])
    w2 = w2_4[0, 0]                                          # (M, F)
    if block_m is None:
        block_m = tune_block_m(x.shape, w1.shape[-1], w2.shape[-1],
                               stride=stride, allow_sweep=False,
                               interpret=interpret)
    out = fmbconv_op(x, w1, b1, w2, b2, stride=stride, block_m=block_m,
                     act=act, residual=residual, interpret=interpret)
    return out.astype(x.dtype)


@register
class FmbconvKernel(KernelBase):
    """(fmbconv, fp): the FusedMBConv kernel behind ``fmbconv_apply``;
    it adds the site's residual itself."""
    kind, precision, dtype = "fmbconv", "fp", "f32"
    vmem_budget = VMEM_BUDGET_BYTES
    adds_residual = True

    def vmem_bytes(self, site, dtype=None):
        """At the smallest candidate tile: the site fuses when any fits."""
        _, H, W, C = site.in_shape
        return min(fmbconv_vmem_bytes(H, W, C, site.attrs["mid"],
                                      site.out_shape[-1], site.stride,
                                      c["block_m"])
                   for c in BLOCK_M_CANDIDATES)

    def tune(self, site, *, autotune=True, interpret=None):
        bm = tune_block_m(site.in_shape, site.attrs["mid"],
                          site.out_shape[-1], stride=site.stride,
                          allow_sweep=autotune, interpret=interpret)
        return {"block_m": bm}

    def candidates(self, site):
        return BLOCK_M_CANDIDATES

    def block_work(self, site, blocks):
        from repro.kernels.autotune import tile_work
        return tile_work(site.attrs["mid"], blocks["block_m"])

    def apply(self, params, x, site, decision=None, *, interpret=None,
              epilogue=None):
        blocks = decision.blocks if decision is not None else {}
        return fmbconv_apply(params, x, stride=site.stride,
                             block_m=blocks.get("block_m"),
                             act=site.act or "gelu_tanh",
                             residual=site.residual, interpret=interpret)

    def ref(self, params, x, site, *, epilogue=None, **kw):
        """The block without its residual, which ``execute`` adds on the
        reference path."""
        from repro.core.efficientvit import fmbconv
        return fmbconv(params, x, stride=site.stride,
                       act=site.act or "gelu_tanh")
