"""Jitted wrapper: fused DSConv for framework param trees.

``dsconv_apply(params, x)`` consumes the EfficientViT {'dw','pw'} conv+BN
block pair (folding BN on the fly) and runs the fused kernel; shapes whose
VMEM tile would exceed the budget fall back to the reference path.

``dsconv_apply_int8(params, x)`` consumes the *quantized* pair (each
subblock a ``qconv`` from ``core.quantization.quantize_efficientvit``)
and runs the FIX8 kernel with in-kernel requantization.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.quantization import QTensor, fold_bn_into_conv, quantize_act
from repro.kernels.autotune import autotune, shape_key
from repro.kernels.compat import VMEM_BUDGET_BYTES, default_interpret
from repro.kernels.dsconv.kernel import (
    dsconv_fused, dsconv_fused_int8, dsconv_fused_int8_emit)
from repro.kernels.dsconv.ref import dsconv_int8_ref, dsconv_ref
from repro.kernels.registry import KernelBase, register

# c_out tiles: a tile is the lane dim of the weight/output blocks, so
# Mosaic takes a multiple of 128 (or the whole extent, which the kernel
# uses whenever F <= block_f)
BLOCK_F_CANDIDATES = ({"block_f": 128}, {"block_f": 256})


def dsconv_vmem_bytes(h: int, w: int, c: int, stride: int = 1, *,
                      dtype: str = "f32") -> int:
    """Analytic per-grid-step VMEM: padded input block + DW scratch.

    ``dtype="i8"``: int8 input block and int8 requantized scratch (4x
    less than fp32)."""
    per = 1 if dtype == "i8" else 4
    return per * ((h + 2) * (w + 2) * c + (h * w // stride ** 2) * c)


def tune_block_f(x_shape, f: int, *, stride: int = 1,
                 allow_sweep: bool = True, interpret: bool | None = None,
                 dtype: str = "f32") -> int:
    """Autotuned c_out tile for a DSConv shape (cached on disk).

    Cache keys carry batch + spatial dims (``autotune.shape_key``) so
    serving buckets at other (batch, resolution) pairs tune and cache
    independently of each other, and int8 separately from fp32.
    """
    B, H, W, C = x_shape
    interpret = default_interpret(interpret)
    backend = "interp" if interpret else "compiled"
    key = shape_key(batch=B, spatial=(H, W), c=C, f=f, stride=stride,
                    dtype=dtype, backend=backend)

    def bench(cand):
        if dtype == "i8":
            return dsconv_fused_int8(
                jnp.zeros((B, H, W, C), jnp.int8), jnp.float32(1.0),
                jnp.zeros((3, 3, C), jnp.int8), jnp.ones((C,)),
                jnp.zeros((C,)), jnp.zeros((C, f), jnp.int8),
                jnp.ones((f,)), jnp.zeros((f,)), stride=stride,
                block_f=cand["block_f"], interpret=interpret)
        return dsconv_fused(
            jnp.zeros((B, H, W, C), jnp.float32), jnp.zeros((3, 3, C)),
            jnp.zeros((C,)), jnp.zeros((C, f), jnp.float32),
            jnp.zeros((f,)), stride=stride, block_f=cand["block_f"],
            interpret=interpret)

    choice = autotune("dsconv", key, BLOCK_F_CANDIDATES,
                      bench if allow_sweep else None,
                      interpret=interpret)
    return choice["block_f"]


@functools.partial(jax.jit,
                   static_argnames=("stride", "act", "block_f", "interpret"))
def dsconv_op(x, dw_w, dw_b, pw_w, pw_b, *, stride: int = 1, act: bool = True,
              block_f: int = 128, interpret: bool | None = None):
    B, H, W, C = x.shape
    if dsconv_vmem_bytes(H, W, C, stride) > VMEM_BUDGET_BYTES:
        return dsconv_ref(x, dw_w, dw_b, pw_w, pw_b, stride=stride, act=act)
    return dsconv_fused(x, dw_w, dw_b, pw_w, pw_b, stride=stride, act=act,
                        block_f=block_f, interpret=interpret)


def dsconv_apply(params, x, *, stride: int = 1, block_f: int = 128,
                 interpret: bool | None = None):
    """EfficientViT {'dw': conv+bn, 'pw': conv+bn} block -> fused kernel.

    Matches core.efficientvit.dsconv / the mbconv dw->pw2 tail: BN is
    folded into both convolutions, Hardswish between them, no activation
    after the projection (paper §II).
    """
    dw_w4, dw_b = fold_bn_into_conv(params["dw"]["conv"], params["dw"]["bn"])
    pw_w4, pw_b = fold_bn_into_conv(params["pw"]["conv"], params["pw"]["bn"])
    dw_w = dw_w4[:, :, 0, :]          # (3,3,1,C) -> (3,3,C)
    pw_w = pw_w4[0, 0]                # (1,1,C,F) -> (C,F)
    out = dsconv_op(x, dw_w, dw_b, pw_w, pw_b, stride=stride, act=True,
                    block_f=block_f, interpret=interpret)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# FIX8 path
# ---------------------------------------------------------------------------

@functools.partial(jax.jit,
                   static_argnames=("stride", "act", "block_f", "interpret"))
def dsconv_op_int8(x_q, x_scale, dw_q, dw_s, dw_b, pw_q, pw_s, pw_b, *,
                   stride: int = 1, act: bool = True, block_f: int = 128,
                   interpret: bool | None = None):
    B, H, W, C = x_q.shape
    if dsconv_vmem_bytes(H, W, C, stride, dtype="i8") > VMEM_BUDGET_BYTES:
        return dsconv_int8_ref(x_q, x_scale, dw_q, dw_s, dw_b, pw_q, pw_s,
                               pw_b, stride=stride, act=act)
    return dsconv_fused_int8(x_q, x_scale, dw_q, dw_s, dw_b, pw_q, pw_s,
                             pw_b, stride=stride, act=act, block_f=block_f,
                             interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("stride", "act", "keep_fp", "interpret"))
def dsconv_op_int8_emit(x_q, x_scale, dw_q, dw_s, dw_b, pw_q, pw_s, pw_b, *,
                        stride: int = 1, act: bool = True,
                        keep_fp: bool = False,
                        interpret: bool | None = None):
    B, H, W, C = x_q.shape
    F = pw_q.shape[-1]
    # full-c_out emit step: fp32 projection + int8 out block (+ fp32 out
    # under keep-fp) beyond what the c_out-tiled byte model counts
    outn = (H // stride) * (W // stride) * F
    emit_extra = outn * (5 + (4 if keep_fp else 0))
    if dsconv_vmem_bytes(H, W, C, stride, dtype="i8") + emit_extra \
            > VMEM_BUDGET_BYTES:
        out = dsconv_int8_ref(x_q, x_scale, dw_q, dw_s, dw_b, pw_q, pw_s,
                              pw_b, stride=stride, act=act)
        qt = quantize_act(out, keep_fp=keep_fp)
        return ((qt.q, qt.scale, qt.fp) if keep_fp else (qt.q, qt.scale))
    return dsconv_fused_int8_emit(x_q, x_scale, dw_q, dw_s, dw_b, pw_q,
                                  pw_s, pw_b, stride=stride, act=act,
                                  keep_fp=keep_fp, interpret=interpret)


def dsconv_apply_int8(params, x, *, stride: int = 1, block_f: int = 128,
                      interpret: bool | None = None, epilogue=None):
    """Quantized {'dw','pw'} pair (``qconv`` subblocks) -> FIX8 kernel.

    ``x`` is the fp activation — quantized here with the whole-tensor
    absmax the reference ``conv2d_int8`` uses (bit-identical first
    stage) — or a producer-emitted ``QTensor`` (no quantize, no fp32
    HBM read).  An int8 ``epilogue`` makes this kernel emit its own
    output quantized in-kernel (``QTensor`` return).  The DW output is
    requantized in-kernel either way.
    """
    qd = params["dw"]["qconv"]
    qp = params["pw"]["qconv"]
    dw_q = qd["q"][:, :, 0, :]         # (3,3,1,C) -> (3,3,C)
    pw_q = qp["q"][0, 0]               # (1,1,C,F) -> (C,F)
    if isinstance(x, QTensor):
        x_q, x_scale = x.q, x.scale
        out_dtype = x.fp.dtype if x.fp is not None else jnp.float32
    else:
        # dynamic per-batch-element entry quantization: one request's
        # numerics never depend on its batch-mates (batch-axis sharding
        # and bucketed batching stay bit-transparent)
        qt = quantize_act(x)
        x_q, x_scale = qt.q, qt.scale
        out_dtype = x.dtype
    args = (x_q, x_scale, dw_q, qd["scale"], qd["bias"], pw_q, qp["scale"],
            qp["bias"])
    if epilogue is not None and epilogue.emits_q:
        keep_fp = epilogue.residual == "keep-fp"
        outs = dsconv_op_int8_emit(*args, stride=stride, act=True,
                                   keep_fp=keep_fp, interpret=interpret)
        fp = outs[2].astype(out_dtype) if keep_fp else None
        return QTensor(outs[0], outs[1], fp)
    out = dsconv_op_int8(*args, stride=stride, act=True, block_f=block_f,
                         interpret=interpret)
    return out.astype(out_dtype)


# ---------------------------------------------------------------------------
# registry impls (consumed by core.fusion.plan_program / core.program)
# ---------------------------------------------------------------------------

@register
class DsconvKernel(KernelBase):
    """(dsconv, fp): the DW+PW megakernel behind ``dsconv_apply``."""
    kind, precision, dtype = "dsconv", "fp", "f32"
    vmem_budget = VMEM_BUDGET_BYTES

    def vmem_bytes(self, site, dtype=None):
        _, H, W, C = site.in_shape
        return dsconv_vmem_bytes(H, W, C, site.stride,
                                 dtype=dtype or self.dtype)

    def tune(self, site, *, autotune=True, interpret=None):
        bf = tune_block_f(site.in_shape, site.out_shape[-1],
                          stride=site.stride, allow_sweep=autotune,
                          interpret=interpret, dtype=self.dtype)
        return {"block_f": bf}

    def candidates(self, site):
        return BLOCK_F_CANDIDATES

    def block_work(self, site, blocks):
        from repro.kernels.autotune import tile_work
        return tile_work(site.out_shape[-1], blocks["block_f"])

    def apply(self, params, x, site, decision=None, *, interpret=None,
              epilogue=None):
        if site.act not in ("", "hswish"):
            raise ValueError(f"{site.name}: the DSConv kernels run "
                             f"Hardswish only, not {site.act!r}")
        blocks = decision.blocks if decision is not None else {}
        return dsconv_apply(params, x, stride=site.stride,
                            block_f=blocks.get("block_f", 128),
                            interpret=interpret)

    def ref(self, params, x, site, *, epilogue=None, **kw):
        from repro.core.efficientvit import dsconv
        out = dsconv(params, x, stride=site.stride, act=site.act or "hswish")
        if epilogue is not None and epilogue.emits_q:
            return quantize_act(out, keep_fp=epilogue.residual == "keep-fp")
        return out


@register
class DsconvInt8Kernel(DsconvKernel):
    """(dsconv, int8): FIX8 twin with in-kernel requantization and
    QTensor boundaries on both sides (the int8 dataflow)."""
    precision, dtype = "int8", "i8"
    takes_q = True
    emits_q = True

    def apply(self, params, x, site, decision=None, *, interpret=None,
              epilogue=None):
        blocks = decision.blocks if decision is not None else {}
        return dsconv_apply_int8(params, x, stride=site.stride,
                                 block_f=blocks.get("block_f", 128),
                                 interpret=interpret, epilogue=epilogue)
