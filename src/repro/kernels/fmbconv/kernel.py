"""Pallas TPU kernel: fused FusedMBConv (dense 3x3 conv -> 1x1 conv).

The L series of EfficientViT opens S1/S2 with FusedMBConv blocks: a
dense 3x3 conv C_in -> mid at the block's stride (BN folded, then the
activation) and a 1x1 projection mid -> C_out (BN folded).  The mid
tensor is the block's largest (4-16x the input's channels); here it
lives only in VMEM and never reaches HBM:

  MXU stage 1: mid = act(sum over the 9 taps of x_tap @ w1[dy, dx] + b1)
  MXU stage 2: out = mid @ w2 + b2 (+ x when the block is residual)

Grid: (batch, mid tiles).  Each image's input block is read from HBM
once: the first mid tile stores it zero-padded into a VMEM scratch, and
every tile reads its taps there (strided for stride 2, through the
lane-chunked layout of ``kernels.taps``).  Each mid tile's share of the
projection accumulates in a VMEM scratch; the last tile adds the bias
and the residual and writes the output.  Weights stream per mid tile.

SAME padding at stride s: output row t reads padded rows s*t + (s-1) +
{0, 1, 2} (XLA's SAME pads 0 above and 1 below at stride 2), the
anchor the MBConv kernel uses too.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.efficientvit import activation
from repro.kernels.compat import default_interpret, tpu_compiler_params
from repro.kernels.taps import fill, tap_scratch


def _fmbconv_kernel(x_ref, w1_ref, b1_ref, w2_ref, b2_ref, o_ref,
                    x_scratch, acc_scratch, *, stride: int, act: str,
                    residual: bool):
    j = pl.program_id(1)
    H, W, C = x_ref.shape[1], x_ref.shape[2], x_ref.shape[3]
    Ho, Wo = H // stride, W // stride
    n, lanes = x_scratch.shape[0], x_scratch.shape[-1]

    @pl.when(j == 0)
    def _load():
        fill(x_scratch, x_ref[0].astype(jnp.float32), row0=1, col0=1)
        acc_scratch[...] = jnp.zeros(acc_scratch.shape, jnp.float32)

    # MXU stage 1: the dense 3x3 conv of this mid tile, tap by tap
    mid = None
    for dy in range(3):
        for dx in range(3):
            for k in range(n):
                tap = x_scratch[k, pl.ds(stride - 1 + dy, Ho, stride=stride),
                                pl.ds(stride - 1 + dx, Wo, stride=stride), :]
                part = jnp.dot(tap.reshape(Ho * Wo, lanes),
                               w1_ref[dy, dx, k * lanes:(k + 1) * lanes, :],
                               preferred_element_type=jnp.float32)
                mid = part if mid is None else mid + part
    mid = activation(act)(mid + b1_ref[...])

    # MXU stage 2: this tile's share of the 1x1 projection
    acc_scratch[...] += jnp.dot(mid, w2_ref[...],
                                preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(1) - 1)
    def _store():
        out = acc_scratch[...] + b2_ref[...]
        if residual:                             # stride 1, C_out == C_in
            out += x_ref[0].astype(jnp.float32).reshape(H * W, C)
        o_ref[0] = out.reshape(Ho, Wo, -1)


def fmbconv_fused(x, w1, b1, w2, b2, *, stride: int = 1, block_m: int = 256,
                  act: str = "gelu_tanh", residual: bool = False,
                  interpret: bool | None = None):
    """x: (B, H, W, C); w1: (3, 3, C, M); w2: (M, F).

    Returns (B, Ho, Wo, F) fp32, Ho = H // stride, plus x itself when
    ``residual``.  The mid axis is tiled by ``block_m`` (zero-padded:
    a padded mid channel has zero weights on both sides, so it adds
    nothing); the mid tensor stays in VMEM.
    """
    from repro.kernels.autotune import pad_to_multiple

    interpret = default_interpret(interpret)
    B, H, W, C = x.shape
    M, F = w1.shape[-1], w2.shape[-1]
    assert H % stride == 0 and W % stride == 0
    assert not residual or (stride == 1 and F == C)
    Ho, Wo = H // stride, W // stride
    bm = min(block_m, M)
    w1p, _ = pad_to_multiple(w1, 3, bm)
    b1p, _ = pad_to_multiple(b1, 0, bm)
    w2p, _ = pad_to_multiple(w2, 0, bm)
    nm = w1p.shape[-1] // bm

    out = pl.pallas_call(
        functools.partial(_fmbconv_kernel, stride=stride, act=act,
                          residual=residual),
        grid=(B, nm),
        in_specs=[
            pl.BlockSpec((1, H, W, C), lambda b, j: (b, 0, 0, 0)),
            pl.BlockSpec((3, 3, C, bm), lambda b, j: (0, 0, 0, j)),
            pl.BlockSpec((1, bm), lambda b, j: (0, j)),
            pl.BlockSpec((bm, F), lambda b, j: (j, 0)),
            pl.BlockSpec((1, F), lambda b, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Ho, Wo, F), lambda b, j: (b, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Ho, Wo, F), jnp.float32),
        scratch_shapes=[
            tap_scratch(H + 2, W + 2, C),
            pltpu.VMEM((Ho * Wo, F), jnp.float32),
        ],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="fmbconv_op",
    )(x, w1p, b1p.reshape(1, -1), w2p, b2.reshape(1, F))
    return out
