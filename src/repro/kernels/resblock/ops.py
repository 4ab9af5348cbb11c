"""Jitted wrapper + registry impl: the fused ResBlock kernel.

``resblock_apply(params, x)`` consumes the L series' {'conv1', 'conv2'}
conv+BN pair (folding BN on the fly) and runs the kernel, the block's
residual add included.  The kind ships fp32 only: int8 L-series trees
are refused by ``core.quantization.quantize_efficientvit``.
"""
from __future__ import annotations

import math

from repro.core.quantization import fold_bn_into_conv
from repro.kernels.compat import VMEM_LIMIT_BYTES
from repro.kernels.registry import KernelBase, register
from repro.kernels.resblock.kernel import (BAND_ROWS, LANES, folded_groups,
                                           resblock_fused)

# The kind's own budget, a quarter of the scoped limit.  The shared
# VMEM_BUDGET_BYTES (an eighth) leaves room for Mosaic to pad blocks of
# few channels to 128 lanes; the folded layout fills every lane, so
# ``resblock_vmem_bytes`` is the bytes the kernel holds, and even with
# every block and weight double-buffered a count within this budget
# needs at most half of VMEM_LIMIT_BYTES.  L2@224's stem counts 9.38 MB;
# compiled for a v5e at batch 8, its scoped VMEM is under 5 MiB.
RESBLOCK_VMEM_BUDGET_BYTES = VMEM_LIMIT_BYTES // 4


def resblock_vmem_bytes(h: int, w: int, c: int, mid: int) -> float:
    """Analytic per-grid-step VMEM (logical fp32 bytes): the folded input
    and output blocks, the two padded scratches, both banded weights and
    biases, and one band's working set (the accumulator, the tap being
    contracted, its product and the band stored).  A block the folded
    layout cannot hold (C not dividing 128, mid != C, W not a multiple
    of 128 // C) never fits."""
    if LANES % c or mid != c or w % (LANES // c):
        return float("inf")
    g, gc = folded_groups(w, c)
    rows = math.gcd(h, BAND_ROWS)
    return 4 * LANES * (2 * h * g + 2 * (h + 2) * (gc + 2)
                        + 2 * 9 * LANES + 2
                        + 3 * rows * gc + rows * g)


def resblock_apply(params, x, *, act: str = "gelu_tanh",
                   interpret: bool | None = None):
    """{'conv1', 'conv2'} conv+BN block -> the fused kernel; matches
    ``x + core.efficientvit.resblock(params, x)``."""
    w1, b1 = fold_bn_into_conv(params["conv1"]["conv"], params["conv1"]["bn"])
    w2, b2 = fold_bn_into_conv(params["conv2"]["conv"], params["conv2"]["bn"])
    out = resblock_fused(x, w1, b1, w2, b2, act=act, interpret=interpret)
    return out.astype(x.dtype)


@register
class ResblockKernel(KernelBase):
    """(resblock, fp): the ResBlock kernel behind ``resblock_apply``; it
    adds the site's residual itself."""
    kind, precision, dtype = "resblock", "fp", "f32"
    vmem_budget = RESBLOCK_VMEM_BUDGET_BYTES
    adds_residual = True

    def vmem_bytes(self, site, dtype=None):
        _, H, W, C = site.in_shape
        return resblock_vmem_bytes(H, W, C, site.attrs["mid"])

    def apply(self, params, x, site, decision=None, *, interpret=None,
              epilogue=None):
        return resblock_apply(params, x, act=site.act or "gelu_tanh",
                              interpret=interpret)

    def ref(self, params, x, site, *, epilogue=None, **kw):
        """The block without its residual, which ``execute`` adds on the
        reference path."""
        from repro.core.efficientvit import resblock
        return resblock(params, x, act=site.act or "gelu_tanh")
