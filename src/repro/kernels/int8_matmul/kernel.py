"""Pallas TPU kernel: W8A8 int8 GEMM with int32 accumulation.

TPU translation of the paper's FIX8 datapath (§IV-A): the FPGA packs two
8x8-bit multiplies per DSP slice (WP486) to double multiplier density;
the TPU MXU natively runs int8 x int8 -> int32 at ~2x the bf16 rate on
v5e — the same economics, delivered architecturally.  Per-output-channel
scales are applied in the epilogue, exactly like the accelerator's
post-processing stage.

Grid: (M/bm, N/bn, K/bk) with the K dimension sequential; the int32
accumulator lives in VMEM scratch across K steps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.compat import default_interpret, tpu_compiler_params
from repro.kernels.quant import int8_dot, requantize_i8, xs_per_batch


def _int8_mm_kernel(x_ref, w_ref, xs_ref, ws_ref, o_ref, acc_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += int8_dot(x_ref[...], w_ref[...])

    @pl.when(k == pl.num_programs(2) - 1)
    def _epilogue():
        # per-row activation scales: a scalar per-tensor scale arrives
        # broadcast, producer-epilogue QTensors arrive per-batch-element
        o_ref[...] = (acc_ref[...].astype(jnp.float32)
                      * xs_ref[...] * ws_ref[0][None, :])


def int8_matmul(x_q, w_q, x_scale, w_scale, *, block_m: int = 256,
                block_n: int = 256, block_k: int = 256,
                interpret: bool | None = None):
    """x_q: (M, K) int8; w_q: (K, N) int8 -> (M, N) fp32.

    ``x_scale`` is the per-tensor activation scale, or per-ROW (M,)
    scales when the rows carry different quantization granules (e.g. a
    producer epilogue's per-batch-element scales flattened over H*W).
    Ragged M/N/K are zero-padded to the block boundary (exact for int32
    accumulation) instead of collapsing to one full-tensor block.
    """
    from repro.kernels.autotune import pad_to_multiple

    interpret = default_interpret(interpret)
    M, K = x_q.shape
    K2, N = w_q.shape
    assert K == K2
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    x_q, _ = pad_to_multiple(x_q, 0, bm)
    x_q, _ = pad_to_multiple(x_q, 1, bk)
    w_q, _ = pad_to_multiple(w_q, 0, bk)
    w_q, _ = pad_to_multiple(w_q, 1, bn)
    Mp, Kp = x_q.shape
    Np = w_q.shape[1]
    xs = jnp.broadcast_to(                # per-ROW scale column here
        jnp.asarray(x_scale, jnp.float32).reshape(-1, 1), (M, 1))
    xs, _ = pad_to_multiple(xs, 0, bm)
    ws, _ = pad_to_multiple(
        jnp.asarray(w_scale, jnp.float32).reshape(1, N), 1, bn)

    out = pl.pallas_call(
        _int8_mm_kernel,
        grid=(Mp // bm, Np // bn, Kp // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x_q, w_q, xs, ws)
    return out[:M, :N]


# ---------------------------------------------------------------------------
# producer-epilogue variant: the GEMM emits the int8 activation
# ---------------------------------------------------------------------------

def _int8_mm_emit_kernel(x_ref, w_ref, xs_ref, ws_ref, b_ref, *refs,
                         keep_fp: bool):
    oq_ref, os_ref = refs[0], refs[1]
    ofp_ref = refs[2] if keep_fp else None
    acc_ref = refs[-1]
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += int8_dot(x_ref[0], w_ref[...])

    @pl.when(k == pl.num_programs(1) - 1)
    def _epilogue():
        o = acc_ref[...].astype(jnp.float32) * xs_ref[0] * ws_ref[...]
        o = o + b_ref[...]
        if keep_fp:
            ofp_ref[0] = o
        # act-quant epilogue: the whole row group (= one batch element's
        # tokens) is this grid step's block, so its per-batch absmax is
        # local — quantized before the activation ever leaves VMEM
        q, s = requantize_i8(o)
        oq_ref[0] = q
        os_ref[0] = s


def int8_matmul_emit(x_q, w_q, x_scale, w_scale, *, rows_per_group: int,
                     bias=None, keep_fp: bool = False, block_k: int = 256,
                     interpret: bool | None = None):
    """W8A8 GEMM with the producer-side act-quant epilogue fused in.

    ``rows_per_group`` partitions the M axis into contiguous groups
    sharing one dynamic activation scale (one batch element's H*W rows
    for a 1x1 conv); the grid runs one step per group with the FULL N
    extent resident, so the group absmax is computed in-kernel at the
    last K step.  Returns ``(q (M, N) int8, scales (M // rows_per_group,)
    fp32)``, plus the fp output when ``keep_fp``.  ``bias`` (N,) is
    added before quantization (it is part of the activation).  The rows
    are blocked as ``(groups, rows_per_group, K)`` so a group of any
    size (B1's 196 or 49 tokens) is a whole-dim block.
    """
    from repro.kernels.autotune import pad_to_multiple

    interpret = default_interpret(interpret)
    M, K = x_q.shape
    N = w_q.shape[1]
    assert M % rows_per_group == 0, (M, rows_per_group)
    G, R = M // rows_per_group, rows_per_group
    bk = min(block_k, K)
    x_q, _ = pad_to_multiple(x_q, 1, bk)
    w_q, _ = pad_to_multiple(w_q, 0, bk)
    Kp = x_q.shape[1]
    xs = xs_per_batch(x_scale, G)     # one scale per row group
    ws = jnp.asarray(w_scale, jnp.float32).reshape(1, N)
    b = (jnp.zeros((1, N), jnp.float32) if bias is None
         else jnp.asarray(bias, jnp.float32).reshape(1, N))

    out_shape = [jax.ShapeDtypeStruct((G, R, N), jnp.int8),
                 jax.ShapeDtypeStruct((G, 1, 1), jnp.float32)]
    out_specs = [pl.BlockSpec((1, R, N), lambda i, k: (i, 0, 0)),
                 pl.BlockSpec((1, 1, 1), lambda i, k: (i, 0, 0))]
    if keep_fp:
        out_shape.append(jax.ShapeDtypeStruct((G, R, N), jnp.float32))
        out_specs.append(pl.BlockSpec((1, R, N), lambda i, k: (i, 0, 0)))

    outs = pl.pallas_call(
        functools.partial(_int8_mm_emit_kernel, keep_fp=keep_fp),
        grid=(G, Kp // bk),
        in_specs=[
            pl.BlockSpec((1, R, bk), lambda i, k: (i, 0, k)),
            pl.BlockSpec((bk, N), lambda i, k: (k, 0)),
            pl.BlockSpec((1, 1, 1), lambda i, k: (i, 0, 0)),
            pl.BlockSpec((1, N), lambda i, k: (0, 0)),
            pl.BlockSpec((1, N), lambda i, k: (0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((R, N), jnp.int32)],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x_q.reshape(G, R, Kp), w_q, xs, ws, b)
    q, scales = outs[0].reshape(M, N), outs[1].reshape(G)
    if keep_fp:
        return q, scales, outs[2].reshape(M, N)
    return q, scales
