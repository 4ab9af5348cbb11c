"""Pallas TPU kernel: fused FIX8 MSA multi-scale aggregation branch.

The paper's Fig. 6 calls out the MSA "group Convs" (depthwise s x s over
the stacked QKV + grouped 1x1 with ``3 * heads`` groups) as the ops
whose low input-channel parallelism starves a generic engine; the
accelerator runs them on the RPE in DW mode.  The TPU translation fuses
ONE aggregation branch into one launch:

  VPU stage : depthwise s x s in int32 over the int8 QKV block
  requant   : the intermediate stays int8 in-register (per batch elem)
  MXU stage : the grouped 1x1 as a dense block-diagonal int8 matmul —
              zero off-block weights contribute nothing to the int32
              accumulation, so one MXU dot replaces ``3 * heads`` tiny
              (d x d) GEMMs

Grid: (batch,).  Quantized MSA modules used to fall back to the
reference ``core.quantization.conv2d_int8`` for these convs — this
kernel (registered as ``("group_agg", "int8")`` in
``kernels/group_conv/ops.py``) closes that ROADMAP item.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.compat import default_interpret, tpu_compiler_params
from repro.kernels.quant import int8_dot, requantize_i8, xs_per_batch


def _group_agg_int8_kernel(x_ref, xs_ref, dww_ref, dws_ref, dwb_ref,
                           pww_ref, pws_ref, pwb_ref, o_ref, *, s: int):
    p = s // 2
    Hp, Wp, C = x_ref.shape[1], x_ref.shape[2], x_ref.shape[3]
    H, W = Hp - 2 * p, Wp - 2 * p

    # VPU stage: depthwise s x s in int32 over the padded int8 block
    xp = x_ref[0].astype(jnp.int32)
    acc = jnp.zeros((H, W, C), jnp.int32)
    for dy in range(s):
        for dx in range(s):
            acc += xp[dy:dy + H, dx:dx + W, :] \
                * dww_ref[dy, dx].astype(jnp.int32)[None, None, :]
    y = acc.astype(jnp.float32) * (xs_ref[0] * dws_ref[...])[None] \
        + dwb_ref[...][None]
    # in-kernel requantization (dynamic per batch element, same
    # arithmetic as the reference conv2d_int8 chain at batch 1)
    yq, sy = requantize_i8(y.reshape(H * W, C))

    # MXU stage: grouped 1x1 as one dense block-diagonal int8 matmul
    acc2 = int8_dot(yq, pww_ref[...])
    out = acc2.astype(jnp.float32) * (sy * pws_ref[...]) + pwb_ref[...]
    o_ref[0] = out.reshape(H, W, -1)


def group_agg_int8(x_q, x_scale, dw_q, dw_s, dw_b, pw_dense_q, pw_s, pw_b,
                   *, interpret: bool | None = None):
    """One fused MSA aggregation branch.  x_q: (B, H, W, C) int8 QKV
    (C = 3 * heads * head_dim), quantized with per-tensor or per-batch
    ``x_scale``; dw_q: (s, s, C) int8 depthwise taps; pw_dense_q:
    (C, C) int8 block-diagonal grouped-1x1 weights (see
    ``ops._block_diag``); per-output-channel fp32 scales, fp32 biases.

    Returns (B, H, W, C) fp32 — bit-identical at batch 1 to the
    reference ``conv2d_int8(dw) -> conv2d_int8(pw)`` chain.
    """
    interpret = default_interpret(interpret)
    B, H, W, C = x_q.shape
    s = dw_q.shape[0]
    assert s % 2 == 1, f"aggregation scale must be odd, got {s}"
    assert x_q.dtype == jnp.int8 and pw_dense_q.dtype == jnp.int8
    p = s // 2
    xp = jnp.pad(x_q, ((0, 0), (p, p), (p, p), (0, 0)))
    xs = xs_per_batch(x_scale, B)

    out = pl.pallas_call(
        functools.partial(_group_agg_int8_kernel, s=s),
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H + 2 * p, W + 2 * p, C),
                         lambda b: (b, 0, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda b: (b, 0, 0)),
            pl.BlockSpec((s, s, C), lambda b: (0, 0, 0)),
            pl.BlockSpec((1, C), lambda b: (0, 0)),
            pl.BlockSpec((1, C), lambda b: (0, 0)),
            pl.BlockSpec((C, C), lambda b: (0, 0)),
            pl.BlockSpec((1, C), lambda b: (0, 0)),
            pl.BlockSpec((1, C), lambda b: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, W, C), lambda b: (b, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, W, C), jnp.float32),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(xp, xs, dw_q, dw_s.reshape(1, C), dw_b.reshape(1, C), pw_dense_q,
      pw_s.reshape(1, C), pw_b.reshape(1, C))
    return out


def group_agg_int8_ref(x_q, x_scale, dw_q, dw_s, dw_b, pw_dense_q, pw_s,
                       pw_b):
    """Pure-jnp oracle (same args, vmapped over batch) — also the
    fallback when a shape exceeds the VMEM budget."""
    from repro.core.quantization import quantize_tensor
    from repro.kernels.quant import xs_per_batch_vec

    s = dw_q.shape[0]
    p = s // 2
    sx_b = xs_per_batch_vec(x_scale, x_q.shape[0])

    def one(xi, sx):                                 # (H, W, C) int8
        H, W, C = xi.shape
        xp = jnp.pad(xi, ((p, p), (p, p), (0, 0))).astype(jnp.int32)
        acc = jnp.zeros((H, W, C), jnp.int32)
        for dy in range(s):
            for dx in range(s):
                acc += xp[dy:dy + H, dx:dx + W, :] \
                    * dw_q[dy, dx].astype(jnp.int32)[None, None, :]
        y = acc.astype(jnp.float32) * (sx * dw_s)[None, None, :] \
            + dw_b[None, None, :]
        yq, sy = quantize_tensor(y)
        acc2 = jnp.einsum("hwc,cf->hwf", yq.astype(jnp.int32),
                          pw_dense_q.astype(jnp.int32))
        return acc2.astype(jnp.float32) * (sy * pw_s)[None, None, :] \
            + pw_b[None, None, :]

    return jax.vmap(one)(x_q, sx_b)
