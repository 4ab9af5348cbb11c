"""Pallas TPU kernel: fused DWConv(3x3) + Hardswish + PWConv.

TPU translation of the paper's TMP *inter-layer* fusion (Fig. 5): on the
FPGA the DWConv runs on the RPE and streams through an auxiliary buffer
into the PWConv on the MAT engine.  Here the DW stage is VPU work
(9 shifted multiply-adds over a VMEM-resident tile — no input-channel
reduction, so the MXU would idle exactly as the paper's adder-trees
would), its output lives only in VMEM scratch, and the PW stage is an
MXU matmul over that scratch.  The intermediate NEVER touches HBM, which
is the entire point of the fusion.

Grid: (batch, c_out tiles).  The DW result is computed once per batch
element (c_out tile 0) and reused by the remaining c_out tiles from
scratch — the "RPE joins the PW" time-multiplexing becomes scratch reuse.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.compat import default_interpret, tpu_compiler_params
from repro.kernels.quant import int8_dot, requantize_i8, xs_per_batch
from repro.kernels.taps import dw_taps, fill, tap_scratch


def _dsconv_kernel(x_ref, dww_ref, dwb_ref, pww_ref, pwb_ref, o_ref,
                   x_scratch, dw_scratch, *, stride: int, act: bool):
    j = pl.program_id(1)
    H, W, C = x_ref.shape[1], x_ref.shape[2], x_ref.shape[3]
    Ho, Wo = H // stride, W // stride

    @pl.when(j == 0)
    def _dw():  # VPU stage: depthwise 3x3 + bias (+ Hardswish)
        fill(x_scratch, x_ref[0], row0=1, col0=1)      # SAME zero pad
        acc = dw_taps(x_scratch, lambda dy, dx, lo, hi: dww_ref[dy, dx, lo:hi],
                      rows=Ho, cols=Wo, stride=stride)  # anchored at 0
        acc += dwb_ref[...][None]
        if act:
            acc = jax.nn.hard_swish(acc)
        dw_scratch[...] = acc.reshape(Ho * Wo, C)

    # MXU stage: pointwise conv over the VMEM-resident DW output
    out = jnp.dot(dw_scratch[...], pww_ref[...].astype(jnp.float32),
                  preferred_element_type=jnp.float32)
    out += pwb_ref[...]
    o_ref[0] = out.reshape(Ho, Wo, -1)


def dsconv_fused(x, dw_w, dw_b, pw_w, pw_b, *, stride: int = 1,
                 act: bool = True, block_f: int = 128,
                 interpret: bool | None = None):
    """x: (B, H, W, C); dw_w: (3, 3, C); pw_w: (C, F) -> (B, Ho, Wo, F)."""
    from repro.kernels.autotune import pad_to_multiple

    interpret = default_interpret(interpret)
    B, H, W, C = x.shape
    F = pw_w.shape[1]
    assert H % stride == 0 and W % stride == 0
    Ho, Wo = H // stride, W // stride
    bf = min(block_f, F)
    pw_w, _ = pad_to_multiple(pw_w, 1, bf)
    pw_b, _ = pad_to_multiple(pw_b, 0, bf)
    Fp = pw_w.shape[1]
    nf = Fp // bf

    out = pl.pallas_call(
        functools.partial(_dsconv_kernel, stride=stride, act=act),
        grid=(B, nf),
        in_specs=[
            pl.BlockSpec((1, H, W, C), lambda b, j: (b, 0, 0, 0)),
            pl.BlockSpec((3, 3, C), lambda b, j: (0, 0, 0)),
            pl.BlockSpec((1, C), lambda b, j: (0, 0)),
            pl.BlockSpec((C, bf), lambda b, j: (0, j)),
            pl.BlockSpec((1, bf), lambda b, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, Ho, Wo, bf), lambda b, j: (b, 0, 0, j)),
        out_shape=jax.ShapeDtypeStruct((B, Ho, Wo, Fp), jnp.float32),
        scratch_shapes=[tap_scratch(H + 2, W + 2, C),
                        pltpu.VMEM((Ho * Wo, C), jnp.float32)],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, dw_w, dw_b.reshape(1, C), pw_w, pw_b.reshape(1, Fp))
    return out[..., :F]


# ---------------------------------------------------------------------------
# FIX8 variant: int8 weights, int32 MACs, in-kernel requant before the PW
# ---------------------------------------------------------------------------

def _int8_dw(x_ref, xs_ref, dww_ref, dws_ref, dwb_ref, x_scratch, *,
             stride: int, act: bool):
    """VPU stage for one batch element: int32 depthwise 3x3 over the
    int8 input (SAME, anchored at ``stride - 1`` like lax.conv's SAME
    grid), dequant, Hardswish, in-kernel requant.  Returns the int8 DW
    output (Ho*Wo, C) and its scale."""
    H, W, C = x_ref.shape[1], x_ref.shape[2], x_ref.shape[3]
    Ho, Wo = H // stride, W // stride
    fill(x_scratch, x_ref[0], row0=1, col0=1)
    acc = dw_taps(x_scratch,
                  lambda dy, dx, lo, hi: dww_ref[dy, dx, lo:hi].astype(
                      jnp.int32),
                  rows=Ho, cols=Wo, stride=stride,
                  row0=stride - 1, col0=stride - 1)
    y = acc.astype(jnp.float32) * (xs_ref[0] * dws_ref[...])[None] \
        + dwb_ref[...][None]
    if act:
        y = jax.nn.hard_swish(y)
    return requantize_i8(y.reshape(Ho * Wo, C))


def _dsconv_int8_kernel(x_ref, xs_ref, dww_ref, dws_ref, dwb_ref,
                        pww_ref, pws_ref, pwb_ref, o_ref,
                        x_scratch, dwq_scratch, sdw_scratch, *, stride: int,
                        act: bool):
    j = pl.program_id(1)
    H, W = x_ref.shape[1], x_ref.shape[2]
    Ho, Wo = H // stride, W // stride

    @pl.when(j == 0)
    def _dw_requant():
        # the DW output stays int8 in scratch
        dq, s_dw = _int8_dw(x_ref, xs_ref, dww_ref, dws_ref, dwb_ref,
                            x_scratch, stride=stride, act=act)
        sdw_scratch[...] = s_dw
        dwq_scratch[...] = dq

    # MXU stage: int8 pointwise conv over the requantized scratch
    acc2 = int8_dot(dwq_scratch[...], pww_ref[...])
    out = acc2.astype(jnp.float32) * (sdw_scratch[...] * pws_ref[...]) \
        + pwb_ref[...]
    o_ref[0] = out.reshape(Ho, Wo, -1)


def dsconv_fused_int8(x_q, x_scale, dw_q, dw_s, dw_b, pw_q, pw_s, pw_b, *,
                      stride: int = 1, act: bool = True, block_f: int = 128,
                      interpret: bool | None = None):
    """FIX8 DSConv.  x_q: (B, H, W, C) int8 quantized with per-tensor
    ``x_scale``; dw_q: (3, 3, C) int8; pw_q: (C, F) int8; per-output-
    channel weight scales, BN-folded biases.  Returns (B, Ho, Wo, F) fp32.

    The depthwise output is requantized in-kernel (dynamic per batch
    element; exact vs the reference ``conv2d_int8`` chain at batch 1) and
    only ever exists as int8 VMEM scratch.
    """
    from repro.kernels.autotune import pad_to_multiple

    interpret = default_interpret(interpret)
    B, H, W, C = x_q.shape
    F = pw_q.shape[1]
    assert x_q.dtype == jnp.int8 and pw_q.dtype == jnp.int8
    assert H % stride == 0 and W % stride == 0
    Ho, Wo = H // stride, W // stride
    bf = min(block_f, F)
    pw_q, _ = pad_to_multiple(pw_q, 1, bf)
    pw_sp, _ = pad_to_multiple(pw_s.reshape(1, F), 1, bf)
    pw_bp, _ = pad_to_multiple(pw_b.reshape(1, F), 1, bf)
    Fp = pw_q.shape[1]
    nf = Fp // bf
    xs = xs_per_batch(x_scale, B)

    out = pl.pallas_call(
        functools.partial(_dsconv_int8_kernel, stride=stride, act=act),
        grid=(B, nf),
        in_specs=[
            pl.BlockSpec((1, H, W, C), lambda b, j: (b, 0, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((3, 3, C), lambda b, j: (0, 0, 0)),
            pl.BlockSpec((1, C), lambda b, j: (0, 0)),
            pl.BlockSpec((1, C), lambda b, j: (0, 0)),
            pl.BlockSpec((C, bf), lambda b, j: (0, j)),
            pl.BlockSpec((1, bf), lambda b, j: (0, j)),
            pl.BlockSpec((1, bf), lambda b, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, Ho, Wo, bf), lambda b, j: (b, 0, 0, j)),
        out_shape=jax.ShapeDtypeStruct((B, Ho, Wo, Fp), jnp.float32),
        scratch_shapes=[
            tap_scratch(H + 2, W + 2, C, jnp.int32),
            pltpu.VMEM((Ho * Wo, C), jnp.int8),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x_q, xs, dw_q, dw_s.reshape(1, C), dw_b.reshape(1, C), pw_q, pw_sp,
      pw_bp)
    return out[..., :F]


# ---------------------------------------------------------------------------
# FIX8 producer-epilogue variant: the kernel emits the int8 activation
# ---------------------------------------------------------------------------

def _dsconv_int8_emit_kernel(x_ref, xs_ref, dww_ref, dws_ref, dwb_ref,
                             pww_ref, pws_ref, pwb_ref, *refs,
                             stride: int, act: bool, keep_fp: bool):
    oq_ref, os_ref = refs[0], refs[1]
    ofp_ref = refs[2] if keep_fp else None
    x_scratch = refs[-1]
    H, W = x_ref.shape[1], x_ref.shape[2]
    Ho, Wo = H // stride, W // stride

    # VPU stage + in-kernel requant: identical arithmetic to
    # _dsconv_int8_kernel's j == 0 branch
    dq, s_dw = _int8_dw(x_ref, xs_ref, dww_ref, dws_ref, dwb_ref, x_scratch,
                        stride=stride, act=act)

    # MXU stage over the FULL c_out extent, then the act-quant epilogue
    acc2 = int8_dot(dq, pww_ref[...])
    out = acc2.astype(jnp.float32) * (s_dw * pws_ref[...]) + pwb_ref[...]
    if keep_fp:
        ofp_ref[0] = out.reshape(Ho, Wo, -1)
    q, s_out = requantize_i8(out)
    oq_ref[0] = q.reshape(Ho, Wo, -1)
    os_ref[0] = s_out


def dsconv_fused_int8_emit(x_q, x_scale, dw_q, dw_s, dw_b, pw_q, pw_s, pw_b,
                           *, stride: int = 1, act: bool = True,
                           keep_fp: bool = False,
                           interpret: bool | None = None):
    """FIX8 DSConv with the producer-side act-quant epilogue fused in.

    Same inputs as ``dsconv_fused_int8``; returns ``(q, scales)`` — q:
    (B, Ho, Wo, F) int8, scales: (B,) per-batch-element — or
    ``(q, scales, out_fp)`` when ``keep_fp``.  Bit-identical to
    quantizing ``dsconv_fused_int8``'s output per batch element: the
    epilogue quantizes the same fp32 projection in-kernel before it
    leaves VMEM.
    """
    interpret = default_interpret(interpret)
    B, H, W, C = x_q.shape
    F = pw_q.shape[1]
    assert x_q.dtype == jnp.int8 and pw_q.dtype == jnp.int8
    assert H % stride == 0 and W % stride == 0
    Ho, Wo = H // stride, W // stride
    xs = xs_per_batch(x_scale, B)

    out_shape = [jax.ShapeDtypeStruct((B, Ho, Wo, F), jnp.int8),
                 jax.ShapeDtypeStruct((B, 1, 1), jnp.float32)]
    out_specs = [pl.BlockSpec((1, Ho, Wo, F), lambda b: (b, 0, 0, 0)),
                 pl.BlockSpec((1, 1, 1), lambda b: (b, 0, 0))]
    if keep_fp:
        out_shape.append(jax.ShapeDtypeStruct((B, Ho, Wo, F), jnp.float32))
        out_specs.append(pl.BlockSpec((1, Ho, Wo, F), lambda b: (b, 0, 0, 0)))

    outs = pl.pallas_call(
        functools.partial(_dsconv_int8_emit_kernel, stride=stride, act=act,
                          keep_fp=keep_fp),
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, W, C), lambda b: (b, 0, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda b: (b, 0, 0)),
            pl.BlockSpec((3, 3, C), lambda b: (0, 0, 0)),
            pl.BlockSpec((1, C), lambda b: (0, 0)),
            pl.BlockSpec((1, C), lambda b: (0, 0)),
            pl.BlockSpec((C, F), lambda b: (0, 0)),
            pl.BlockSpec((1, F), lambda b: (0, 0)),
            pl.BlockSpec((1, F), lambda b: (0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[tap_scratch(H + 2, W + 2, C, jnp.int32)],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x_q, xs, dw_q, dw_s.reshape(1, C), dw_b.reshape(1, C), pw_q,
      pw_s.reshape(1, F), pw_b.reshape(1, F))
    if keep_fp:
        return outs[0], outs[1].reshape(B), outs[2]
    return outs[0], outs[1].reshape(B)
