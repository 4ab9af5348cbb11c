"""Jitted wrapper: fused MBConv megakernel for framework param trees.

``mbconv_apply(params, x)`` consumes the EfficientViT
{'pw1','dw','pw2'} conv+BN triple (folding BN on the fly, paper §II) and
runs the megakernel; shapes whose VMEM tiles would blow the budget fall
back to the jnp oracle, which has identical folded-weight numerics.

``mbconv_apply_int8(params, x)`` is the FIX8 twin: it consumes the
*quantized* triple ({'pw1','dw','pw2'} each holding a ``qconv`` from
``core.quantization.quantize_efficientvit``) and runs the int8
megakernel — int8 weights resident in VMEM, int32 MXU accumulation, and
in-kernel requantization so the expanded mid tensor stays int8 on chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.quantization import QTensor, fold_bn_into_conv, quantize_act
from repro.kernels.autotune import autotune, shape_key
from repro.kernels.compat import VMEM_BUDGET_BYTES, default_interpret
from repro.kernels.mbconv.kernel import (
    mbconv_fused, mbconv_fused_int8, mbconv_fused_int8_emit)
from repro.kernels.mbconv.ref import mbconv_int8_ref, mbconv_ref
from repro.kernels.registry import KernelBase, register

# c_out tiles: a tile is the lane dim of the weight/output blocks, so
# Mosaic takes a multiple of 128 (or the whole extent, which the kernel
# uses whenever F <= block_f)
BLOCK_F_CANDIDATES = ({"block_f": 128}, {"block_f": 256})


def mbconv_vmem_bytes(h: int, w: int, c_in: int, mid: int,
                      stride: int = 1, *, dtype: str = "f32") -> int:
    """Analytic per-grid-step VMEM: input block + both fused scratches.

    ``dtype="i8"`` is the FIX8 kernel, counted at 1 byte per element
    (int8 input block and requantized values).  The planner's model of
    logical bytes: the compiled kernel widens the tap scratch to int32
    and pads lanes (``kernels.compat`` sizes the compiler's limit).
    """
    per = 1 if dtype == "i8" else 4
    return per * (h * w * c_in + (h + 2) * (w + 2) * mid
                  + (h * w // stride ** 2) * mid)


def tune_block_f(x_shape, mid: int, f: int, *, stride: int = 1,
                 allow_sweep: bool = True, interpret: bool | None = None,
                 dtype: str = "f32") -> int:
    """Autotuned c_out tile for an MBConv shape (cached on disk).

    The cache key carries batch + spatial dims next to the channel
    geometry, the backend (interpret vs compiled) and the dtype, so
    serving buckets at other (batch, resolution) pairs can never collide
    on a stale block choice, and int8 tiles cache separately from fp32.
    """
    B, H, W, C = x_shape
    interpret = default_interpret(interpret)
    backend = "interp" if interpret else "compiled"
    key = shape_key(batch=B, spatial=(H, W), c=C, mid=mid, f=f,
                    stride=stride, dtype=dtype, backend=backend)

    def bench(cand):
        if dtype == "i8":
            return mbconv_fused_int8(
                jnp.zeros((B, H, W, C), jnp.int8), jnp.float32(1.0),
                jnp.zeros((C, mid), jnp.int8), jnp.ones((mid,)),
                jnp.zeros((mid,)), jnp.zeros((3, 3, mid), jnp.int8),
                jnp.ones((mid,)), jnp.zeros((mid,)),
                jnp.zeros((mid, f), jnp.int8), jnp.ones((f,)),
                jnp.zeros((f,)), stride=stride, block_f=cand["block_f"],
                interpret=interpret)
        kx = jnp.zeros((B, H, W, C), jnp.float32)
        return mbconv_fused(
            kx, jnp.zeros((C, mid), jnp.float32), jnp.zeros((mid,)),
            jnp.zeros((3, 3, mid)), jnp.zeros((mid,)),
            jnp.zeros((mid, f), jnp.float32), jnp.zeros((f,)),
            stride=stride, block_f=cand["block_f"], interpret=interpret)

    choice = autotune("mbconv", key, BLOCK_F_CANDIDATES,
                      bench if allow_sweep else None,
                      interpret=interpret)
    return choice["block_f"]


@functools.partial(jax.jit,
                   static_argnames=("stride", "block_f", "act", "interpret"))
def mbconv_op(x, w1, b1, dw_w, dw_b, w2, b2, *, stride: int = 1,
              block_f: int = 128, act: str = "hswish",
              interpret: bool | None = None):
    B, H, W, C = x.shape
    M = w1.shape[1]
    if mbconv_vmem_bytes(H, W, C, M, stride) > VMEM_BUDGET_BYTES:
        return mbconv_ref(x, w1, b1, dw_w, dw_b, w2, b2, stride=stride,
                          act=act)
    return mbconv_fused(x, w1, b1, dw_w, dw_b, w2, b2, stride=stride,
                        block_f=block_f, act=act, interpret=interpret)


def mbconv_apply(params, x, *, stride: int = 1, block_f: int | None = None,
                 act: str = "hswish", interpret: bool | None = None):
    """EfficientViT {'pw1','dw','pw2'} conv+BN block -> fused megakernel.

    Matches core.efficientvit.mbconv: BN folded into all three convs,
    the activation ``act`` after pw1 and dw, bare projection after pw2.
    """
    w1_4, b1 = fold_bn_into_conv(params["pw1"]["conv"], params["pw1"]["bn"])
    dw_4, dw_b = fold_bn_into_conv(params["dw"]["conv"], params["dw"]["bn"])
    w2_4, b2 = fold_bn_into_conv(params["pw2"]["conv"], params["pw2"]["bn"])
    w1 = w1_4[0, 0]                    # (1,1,C,M) -> (C,M)
    dw_w = dw_4[:, :, 0, :]            # (3,3,1,M) -> (3,3,M)
    w2 = w2_4[0, 0]                    # (1,1,M,F) -> (M,F)
    if block_f is None:
        block_f = tune_block_f(x.shape, w1.shape[1], w2.shape[1],
                               stride=stride, allow_sweep=False,
                               interpret=interpret)
    out = mbconv_op(x, w1, b1, dw_w, dw_b, w2, b2, stride=stride,
                    block_f=block_f, act=act,
                    interpret=interpret)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# FIX8 path
# ---------------------------------------------------------------------------

@functools.partial(jax.jit,
                   static_argnames=("stride", "block_f", "interpret"))
def mbconv_op_int8(x_q, x_scale, w1_q, s1, b1, dw_q, dw_s, dw_b, w2_q, s2,
                   b2, *, stride: int = 1, block_f: int = 128,
                   interpret: bool | None = None):
    B, H, W, C = x_q.shape
    M = w1_q.shape[1]
    if mbconv_vmem_bytes(H, W, C, M, stride, dtype="i8") > VMEM_BUDGET_BYTES:
        return mbconv_int8_ref(x_q, x_scale, w1_q, s1, b1, dw_q, dw_s, dw_b,
                               w2_q, s2, b2, stride=stride)
    return mbconv_fused_int8(x_q, x_scale, w1_q, s1, b1, dw_q, dw_s, dw_b,
                             w2_q, s2, b2, stride=stride, block_f=block_f,
                             interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("stride", "keep_fp", "interpret"))
def mbconv_op_int8_emit(x_q, x_scale, w1_q, s1, b1, dw_q, dw_s, dw_b, w2_q,
                        s2, b2, *, stride: int = 1, keep_fp: bool = False,
                        interpret: bool | None = None):
    B, H, W, C = x_q.shape
    M = w1_q.shape[1]
    F = w2_q.shape[1]
    # the emit kernel runs the FULL c_out extent in one grid step and
    # additionally holds the fp32 projection (quantized in-kernel), the
    # int8 output block, and — under keep-fp — the fp32 output block,
    # none of which the c_out-tiled byte model counts
    outn = (H // stride) * (W // stride) * F
    emit_extra = outn * (5 + (4 if keep_fp else 0))
    if mbconv_vmem_bytes(H, W, C, M, stride, dtype="i8") + emit_extra \
            > VMEM_BUDGET_BYTES:
        out = mbconv_int8_ref(x_q, x_scale, w1_q, s1, b1, dw_q, dw_s, dw_b,
                              w2_q, s2, b2, stride=stride)
        qt = quantize_act(out, keep_fp=keep_fp)
        return ((qt.q, qt.scale, qt.fp) if keep_fp else (qt.q, qt.scale))
    return mbconv_fused_int8_emit(x_q, x_scale, w1_q, s1, b1, dw_q, dw_s,
                                  dw_b, w2_q, s2, b2, stride=stride,
                                  keep_fp=keep_fp, interpret=interpret)


def mbconv_apply_int8(params, x, *, stride: int = 1,
                      block_f: int | None = None,
                      interpret: bool | None = None, epilogue=None):
    """Quantized EfficientViT {'pw1','dw','pw2'} block (each a ``qconv``
    from ``quantize_efficientvit``) -> FIX8 megakernel.

    ``x`` is either the fp activation — quantized here with the same
    whole-tensor absmax the reference ``conv2d_int8`` uses, so the first
    stage is bit-identical — or a ``QTensor`` already emitted by the
    producer's epilogue (no quantize, no fp32 HBM read).  An int8
    ``epilogue`` makes THIS kernel the producer: it returns a
    ``QTensor`` quantized in-kernel, with the fp tensor alongside under
    the "keep-fp" residual policy.  Inter-stage requantization always
    happens in-kernel.
    """
    q1 = params["pw1"]["qconv"]
    qd = params["dw"]["qconv"]
    q2 = params["pw2"]["qconv"]
    w1_q = q1["q"][0, 0]               # (1,1,C,M) -> (C,M)
    dw_q = qd["q"][:, :, 0, :]         # (3,3,1,M) -> (3,3,M)
    w2_q = q2["q"][0, 0]               # (1,1,M,F) -> (M,F)
    if isinstance(x, QTensor):
        x_q, x_scale = x.q, x.scale
        out_dtype = x.fp.dtype if x.fp is not None else jnp.float32
    else:
        # per-batch-element entry quantization (batch-composition
        # invariant; see serving.sharding)
        qt = quantize_act(x)
        x_q, x_scale = qt.q, qt.scale
        out_dtype = x.dtype
    args = (x_q, x_scale, w1_q, q1["scale"], q1["bias"], dw_q, qd["scale"],
            qd["bias"], w2_q, q2["scale"], q2["bias"])
    if epilogue is not None and epilogue.emits_q:
        keep_fp = epilogue.residual == "keep-fp"
        outs = mbconv_op_int8_emit(*args, stride=stride, keep_fp=keep_fp,
                                   interpret=interpret)
        fp = outs[2].astype(out_dtype) if keep_fp else None
        return QTensor(outs[0], outs[1], fp)
    if block_f is None:
        block_f = tune_block_f(x_q.shape, w1_q.shape[1], w2_q.shape[1],
                               stride=stride, allow_sweep=False,
                               interpret=interpret, dtype="i8")
    out = mbconv_op_int8(*args, stride=stride, block_f=block_f,
                         interpret=interpret)
    return out.astype(out_dtype)


# ---------------------------------------------------------------------------
# registry impls (consumed by core.fusion.plan_program / core.program)
# ---------------------------------------------------------------------------

@register
class MbconvKernel(KernelBase):
    """(mbconv, fp): the PW+DW+PW megakernel behind ``mbconv_apply``."""
    kind, precision, dtype = "mbconv", "fp", "f32"
    vmem_budget = VMEM_BUDGET_BYTES

    def vmem_bytes(self, site, dtype=None):
        _, H, W, C = site.in_shape
        return mbconv_vmem_bytes(H, W, C, site.attrs["mid"], site.stride,
                                 dtype=dtype or self.dtype)

    def tune(self, site, *, autotune=True, interpret=None):
        bf = tune_block_f(site.in_shape, site.attrs["mid"],
                          site.out_shape[-1], stride=site.stride,
                          allow_sweep=autotune, interpret=interpret,
                          dtype=self.dtype)
        return {"block_f": bf}

    def candidates(self, site):
        return BLOCK_F_CANDIDATES

    def block_work(self, site, blocks):
        from repro.kernels.autotune import tile_work
        return tile_work(site.out_shape[-1], blocks["block_f"])

    def apply(self, params, x, site, decision=None, *, interpret=None,
              epilogue=None):
        blocks = decision.blocks if decision is not None else {}
        return mbconv_apply(params, x, stride=site.stride,
                            block_f=blocks.get("block_f"),
                            act=site.act or "hswish", interpret=interpret)

    def ref(self, params, x, site, *, epilogue=None, **kw):
        from repro.core.efficientvit import mbconv
        out = mbconv(params, x, stride=site.stride,
                     act=site.act or "hswish")
        if epilogue is not None and epilogue.emits_q:
            return quantize_act(out, keep_fp=epilogue.residual == "keep-fp")
        return out


@register
class MbconvInt8Kernel(MbconvKernel):
    """(mbconv, int8): FIX8 twin — int8 scratches, in-kernel requant,
    QTensor boundaries on both sides (the int8 dataflow)."""
    precision, dtype = "int8", "i8"
    takes_q = True
    emits_q = True

    def apply(self, params, x, site, decision=None, *, interpret=None,
              epilogue=None):
        blocks = decision.blocks if decision is not None else {}
        return mbconv_apply_int8(params, x, stride=site.stride,
                                 block_f=blocks.get("block_f"),
                                 interpret=interpret, epilogue=epilogue)
