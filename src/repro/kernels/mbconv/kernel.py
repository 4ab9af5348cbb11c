"""Pallas TPU megakernel: fused MBConv (PWConv -> DWConv -> PWConv).

TPU translation of the paper's TMP *inter-layer* fusion (Fig. 5) applied
to the whole MBConv block.  The expanded ``mid = c_in * expand_ratio``
tensor is the largest intermediate in the network (~75% of MBConv
activation traffic); on the FPGA it streams RPE -> aux buffer -> MAT
engine and never reaches DRAM.  Here both intermediates (the PW1
expansion and the DW output) live only in VMEM scratch:

  MXU stage 1: mid = act(x @ w1 + b1)          (1x1 expansion)
  VPU stage  : dw  = act(DW3x3(mid) + b_dw)    (9 shifted MACs)
  MXU stage 2: out = dw @ w2 + b2              (1x1 projection)

``act`` is static: Hardswish for the B series, tanh-form GELU for the
L series (``core.efficientvit.ACTIVATIONS``); the FIX8 variants run
Hardswish only.

Grid: (batch, c_out tiles).  Stages 1-2 run once per batch element
(c_out tile 0) into scratch; the remaining c_out tiles reuse the scratch
— the paper's time-multiplexing become scratch reuse, exactly as in
kernels/dsconv.  x is read from HBM once per batch element and only the
final projection is written back.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.efficientvit import activation
from repro.kernels.autotune import pad_to_multiple
from repro.kernels.compat import default_interpret, tpu_compiler_params
from repro.kernels.quant import int8_dot, requantize_i8, xs_per_batch
from repro.kernels.taps import dw_taps, fill, tap_scratch


def _mbconv_kernel(x_ref, w1_ref, b1_ref, dww_ref, dwb_ref, w2_ref, b2_ref,
                   o_ref, mid_scratch, dw_scratch, *, stride: int,
                   act: str):
    j = pl.program_id(1)
    f = activation(act)
    H, W, C = x_ref.shape[1], x_ref.shape[2], x_ref.shape[3]
    M = w1_ref.shape[1]
    Ho, Wo = H // stride, W // stride

    @pl.when(j == 0)
    def _expand_and_dw():
        # MXU stage 1: 1x1 expansion into the padded VMEM scratch
        x = x_ref[0].astype(jnp.float32).reshape(H * W, C)
        mid = jnp.dot(x, w1_ref[...].astype(jnp.float32),
                      preferred_element_type=jnp.float32)
        mid = f(mid + b1_ref[...])
        fill(mid_scratch, mid.reshape(H, W, M), row0=1, col0=1)

        # VPU stage: depthwise 3x3 (SAME, anchored at stride-1) over the
        # scratch, read strided so only the kept outputs are computed
        acc = dw_taps(mid_scratch,
                      lambda dy, dx, lo, hi: dww_ref[dy, dx, lo:hi],
                      rows=Ho, cols=Wo, stride=stride,
                      row0=stride - 1, col0=stride - 1)
        acc += dwb_ref[...][None]
        dw_scratch[...] = f(acc).reshape(Ho * Wo, M)

    # MXU stage 2: 1x1 projection of the VMEM-resident DW output
    out = jnp.dot(dw_scratch[...], w2_ref[...].astype(jnp.float32),
                  preferred_element_type=jnp.float32)
    out += b2_ref[...]
    o_ref[0] = out.reshape(Ho, Wo, -1)


def mbconv_fused(x, w1, b1, dw_w, dw_b, w2, b2, *, stride: int = 1,
                 block_f: int = 128, act: str = "hswish",
                 interpret: bool | None = None):
    """x: (B, H, W, C); w1: (C, M); dw_w: (3, 3, M); w2: (M, F).

    Returns (B, Ho, Wo, F) fp32, Ho = H // stride.  The c_out axis is
    tiled by ``block_f`` with zero-padded ragged tails (no full-tensor
    fallback); both intermediates stay in VMEM scratch.
    """
    interpret = default_interpret(interpret)
    B, H, W, C = x.shape
    M = w1.shape[1]
    F = w2.shape[1]
    assert H % stride == 0 and W % stride == 0
    Ho, Wo = H // stride, W // stride
    bf = min(block_f, F)
    w2p, _ = pad_to_multiple(w2, 1, bf)
    b2p, _ = pad_to_multiple(b2, 0, bf)
    Fp = w2p.shape[1]
    nf = Fp // bf

    out = pl.pallas_call(
        functools.partial(_mbconv_kernel, stride=stride, act=act),
        grid=(B, nf),
        in_specs=[
            pl.BlockSpec((1, H, W, C), lambda b, j: (b, 0, 0, 0)),
            pl.BlockSpec((C, M), lambda b, j: (0, 0)),
            pl.BlockSpec((1, M), lambda b, j: (0, 0)),
            pl.BlockSpec((3, 3, M), lambda b, j: (0, 0, 0)),
            pl.BlockSpec((1, M), lambda b, j: (0, 0)),
            pl.BlockSpec((M, bf), lambda b, j: (0, j)),
            pl.BlockSpec((1, bf), lambda b, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, Ho, Wo, bf), lambda b, j: (b, 0, 0, j)),
        out_shape=jax.ShapeDtypeStruct((B, Ho, Wo, Fp), jnp.float32),
        scratch_shapes=[
            tap_scratch(H + 2, W + 2, M),
            pltpu.VMEM((Ho * Wo, M), jnp.float32),
        ],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, w1, b1.reshape(1, M), dw_w, dw_b.reshape(1, M), w2p,
      b2p.reshape(1, Fp))
    return out[..., :F]


# ---------------------------------------------------------------------------
# FIX8 variant: int8 weights, int32 MXU accumulation, in-kernel requant
# ---------------------------------------------------------------------------

def _int8_expand_dw(x_ref, xs_ref, w1_ref, s1_ref, b1_ref, dww_ref,
                    dws_ref, dwb_ref, mid_scratch, *, stride: int):
    """Stages 1 + 2 of the FIX8 block for one batch element: int8 1x1
    expansion, in-kernel requant, int32 depthwise 3x3, requant.  Returns
    the int8 DW output (Ho*Wo, M) and its scale."""
    H, W, C = x_ref.shape[1], x_ref.shape[2], x_ref.shape[3]
    M = w1_ref.shape[1]
    Ho, Wo = H // stride, W // stride
    # MXU stage 1: int8 x int8 -> int32 expansion, fp32 dequant epilogue
    xq = x_ref[0].reshape(H * W, C)
    acc = int8_dot(xq, w1_ref[...])
    mid = acc.astype(jnp.float32) * (xs_ref[0] * s1_ref[...]) + b1_ref[...]
    mid = jax.nn.hard_swish(mid)
    # in-kernel requantization: the 4x-expanded mid tensor is int8 (held
    # widened to int32 in VMEM scratch for the strided tap reads)
    mq, s_mid = requantize_i8(mid)
    fill(mid_scratch, mq.reshape(H, W, M), row0=1, col0=1)
    # VPU stage: depthwise 3x3 in int32, strided to the kept outputs
    acc2 = dw_taps(mid_scratch,
                   lambda dy, dx, lo, hi:
                       dww_ref[dy, dx, lo:hi].astype(jnp.int32),
                   rows=Ho, cols=Wo, stride=stride,
                   row0=stride - 1, col0=stride - 1)
    dw = acc2.astype(jnp.float32) * (s_mid * dws_ref[...])[None] \
        + dwb_ref[...][None]
    dw = jax.nn.hard_swish(dw)
    return requantize_i8(dw.reshape(Ho * Wo, M))


def _mbconv_int8_kernel(x_ref, xs_ref, w1_ref, s1_ref, b1_ref,
                        dww_ref, dws_ref, dwb_ref, w2_ref, s2_ref, b2_ref,
                        o_ref, mid_scratch, dwq_scratch, sdw_scratch,
                        *, stride: int):
    j = pl.program_id(1)
    H, W = x_ref.shape[1], x_ref.shape[2]
    Ho, Wo = H // stride, W // stride

    @pl.when(j == 0)
    def _expand_dw_requant():
        dq, s_dw = _int8_expand_dw(x_ref, xs_ref, w1_ref, s1_ref, b1_ref,
                                   dww_ref, dws_ref, dwb_ref, mid_scratch,
                                   stride=stride)
        sdw_scratch[...] = s_dw
        dwq_scratch[...] = dq

    # MXU stage 2: int8 projection of the VMEM-resident requantized DW out
    acc3 = int8_dot(dwq_scratch[...], w2_ref[...])
    out = acc3.astype(jnp.float32) * (sdw_scratch[...] * s2_ref[...]) \
        + b2_ref[...]
    o_ref[0] = out.reshape(Ho, Wo, -1)


def mbconv_fused_int8(x_q, x_scale, w1_q, s1, b1, dw_q, s_dw, dw_b,
                      w2_q, s2, b2, *, stride: int = 1, block_f: int = 128,
                      interpret: bool | None = None):
    """FIX8 MBConv megakernel.  x_q: (B, H, W, C) int8 (activations already
    quantized with per-tensor — or per-batch-element, when emitted by a
    producer epilogue — ``x_scale``); w1_q: (C, M) int8; dw_q:
    (3, 3, M) int8; w2_q: (M, F) int8; s*: per-output-channel fp32 weight
    scales; b*: fp32 biases (BN folded).

    Returns (B, Ho, Wo, F) fp32.  Both intermediates are requantized
    in-kernel and never leave VMEM (the DW output as int8 scratch; the
    expanded mid map widened to int32 for the strided tap reads, which
    Mosaic only supports on 32-bit data).  The inter-stage activation
    scales are dynamic per batch element — identical to the reference
    FIX8 path (``core.quantization.conv2d_int8`` chain) at batch 1, and
    within quantization noise of it for larger batches.
    """
    interpret = default_interpret(interpret)
    B, H, W, C = x_q.shape
    M = w1_q.shape[1]
    F = w2_q.shape[1]
    assert x_q.dtype == jnp.int8 and w1_q.dtype == jnp.int8
    assert H % stride == 0 and W % stride == 0
    Ho, Wo = H // stride, W // stride
    bf = min(block_f, F)
    w2p, _ = pad_to_multiple(w2_q, 1, bf)
    s2p, _ = pad_to_multiple(s2.reshape(1, F), 1, bf)
    b2p, _ = pad_to_multiple(b2.reshape(1, F), 1, bf)
    Fp = w2p.shape[1]
    nf = Fp // bf
    xs = xs_per_batch(x_scale, B)

    out = pl.pallas_call(
        functools.partial(_mbconv_int8_kernel, stride=stride),
        grid=(B, nf),
        in_specs=[
            pl.BlockSpec((1, H, W, C), lambda b, j: (b, 0, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((C, M), lambda b, j: (0, 0)),
            pl.BlockSpec((1, M), lambda b, j: (0, 0)),
            pl.BlockSpec((1, M), lambda b, j: (0, 0)),
            pl.BlockSpec((3, 3, M), lambda b, j: (0, 0, 0)),
            pl.BlockSpec((1, M), lambda b, j: (0, 0)),
            pl.BlockSpec((1, M), lambda b, j: (0, 0)),
            pl.BlockSpec((M, bf), lambda b, j: (0, j)),
            pl.BlockSpec((1, bf), lambda b, j: (0, j)),
            pl.BlockSpec((1, bf), lambda b, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, Ho, Wo, bf), lambda b, j: (b, 0, 0, j)),
        out_shape=jax.ShapeDtypeStruct((B, Ho, Wo, Fp), jnp.float32),
        scratch_shapes=[
            tap_scratch(H + 2, W + 2, M, jnp.int32),
            pltpu.VMEM((Ho * Wo, M), jnp.int8),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x_q, xs, w1_q, s1.reshape(1, M), b1.reshape(1, M), dw_q,
      s_dw.reshape(1, M), dw_b.reshape(1, M), w2p, s2p, b2p)
    return out[..., :F]


# ---------------------------------------------------------------------------
# FIX8 producer-epilogue variant: the kernel emits the int8 activation
# ---------------------------------------------------------------------------

def _mbconv_int8_emit_kernel(x_ref, xs_ref, w1_ref, s1_ref, b1_ref,
                             dww_ref, dws_ref, dwb_ref, w2_ref, s2_ref,
                             b2_ref, *refs, stride: int, keep_fp: bool):
    oq_ref, os_ref = refs[0], refs[1]
    ofp_ref = refs[2] if keep_fp else None
    mid_scratch = refs[-1]
    H, W = x_ref.shape[1], x_ref.shape[2]
    Ho, Wo = H // stride, W // stride

    # MXU stage 1 + VPU stage + in-kernel requant: identical arithmetic
    # to _mbconv_int8_kernel's j == 0 branch
    dq, s_dw = _int8_expand_dw(x_ref, xs_ref, w1_ref, s1_ref, b1_ref,
                               dww_ref, dws_ref, dwb_ref, mid_scratch,
                               stride=stride)

    # MXU stage 2 over the FULL c_out extent (the epilogue's per-batch
    # absmax needs the whole projection before anything is written)
    acc3 = int8_dot(dq, w2_ref[...])
    out = acc3.astype(jnp.float32) * (s_dw * s2_ref[...]) + b2_ref[...]
    if keep_fp:
        ofp_ref[0] = out.reshape(Ho, Wo, -1)
    # the act-quant epilogue: exactly what the consumer used to run in
    # XLA after a round-trip through HBM, now fused into the producer
    q, s_out = requantize_i8(out)
    oq_ref[0] = q.reshape(Ho, Wo, -1)
    os_ref[0] = s_out


def mbconv_fused_int8_emit(x_q, x_scale, w1_q, s1, b1, dw_q, s_dw, dw_b,
                           w2_q, s2, b2, *, stride: int = 1,
                           keep_fp: bool = False,
                           interpret: bool | None = None):
    """FIX8 MBConv with the producer-side act-quant epilogue fused in.

    Same inputs as ``mbconv_fused_int8``; returns ``(q, scales)`` —
    q: (B, Ho, Wo, F) int8, scales: (B,) fp32 per-batch-element — or
    ``(q, scales, out_fp)`` when ``keep_fp`` (the epilogue's "keep-fp"
    residual policy: the consumer's residual add needs the fp tensor
    alongside).  The quantized output is bit-identical to running
    ``mbconv_fused_int8`` and quantizing its result per batch element,
    because the epilogue quantizes the very same fp32 projection —
    in-kernel, over the full c_out extent, before it ever leaves VMEM.
    """
    interpret = default_interpret(interpret)
    B, H, W, C = x_q.shape
    M = w1_q.shape[1]
    F = w2_q.shape[1]
    assert x_q.dtype == jnp.int8 and w1_q.dtype == jnp.int8
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
        functools.partial(_mbconv_int8_emit_kernel, stride=stride,
                          keep_fp=keep_fp),
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, W, C), lambda b: (b, 0, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda b: (b, 0, 0)),
            pl.BlockSpec((C, M), lambda b: (0, 0)),
            pl.BlockSpec((1, M), lambda b: (0, 0)),
            pl.BlockSpec((1, M), lambda b: (0, 0)),
            pl.BlockSpec((3, 3, M), lambda b: (0, 0, 0)),
            pl.BlockSpec((1, M), lambda b: (0, 0)),
            pl.BlockSpec((1, M), lambda b: (0, 0)),
            pl.BlockSpec((M, F), lambda b: (0, 0)),
            pl.BlockSpec((1, F), lambda b: (0, 0)),
            pl.BlockSpec((1, F), lambda b: (0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[tap_scratch(H + 2, W + 2, M, jnp.int32)],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x_q, xs, w1_q, s1.reshape(1, M), b1.reshape(1, M), dw_q,
      s_dw.reshape(1, M), dw_b.reshape(1, M), w2_q, s2.reshape(1, F),
      b2.reshape(1, F))
    if keep_fp:
        return outs[0], outs[1].reshape(B), outs[2]
    return outs[0], outs[1].reshape(B)
