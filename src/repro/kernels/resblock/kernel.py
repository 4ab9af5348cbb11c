"""Pallas TPU kernel: the L series' ResBlock stem, both 3x3 convs in one
launch.

The stem of EfficientViT-L runs a ResBlock at half resolution:
conv1 3x3 C -> C (BN folded, then the activation), conv2 3x3 C -> C (BN
folded), plus the input.  Here the mid map lives only in VMEM:

  conv1: mid = act(conv3x3(x, w1) + b1)      into a padded VMEM scratch
  conv2: out = conv3x3(mid, w2) + b2 + x

At C = 32 a channel-last map fills a quarter of the 128 lanes, and a
3x3 conv run tap by tap contracts over K = 32 into N = 32: 1/16 of the
128x128 MXU.  So the maps are folded: ``P = 128 // C`` neighbouring
pixels of a row share one lane row, ``(H, W, C) -> (H, W // P, 128)``
(a reshape of the row-major map, which XLA lays out anew around the
kernel), and each conv is nine full-width
``(rows * G, 128) @ (128, 128)`` dots.  Output lane group ``g`` (pixels
``P*g .. P*g + P-1``) reads its 3x3 neighbourhood from input groups
``g - 1``, ``g`` and ``g + 1`` of rows ``r - 1 .. r + 1``; tap
``(dy, j)`` is the padded scratch's rows ``r + dy`` and groups
``g + j``, and its banded ``(128, 128)`` weight (``fold_taps``) holds
``w[dy, dx]`` in the block of each (input pixel, output pixel) pair
that ``dx`` joins, zero elsewhere.  A zero block adds exact zeros, so
the sums are the conv's own products in another order.

Grid: (batch,).  Each image's map is stored once into a scratch padded
by one row above and below and one lane group left and right (the SAME
padding), and conv1 writes its output into a second such scratch; both
convs run in bands of ``gcd(H, BAND_ROWS)`` output rows.  The computed
group count is rounded up to a multiple of 8 so every dot's rows reshape
for free; the extra groups read only zeros and are never stored.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.efficientvit import activation
from repro.kernels.compat import default_interpret, tpu_compiler_params

LANES = 128
BAND_ROWS = 16


def folded_groups(w: int, c: int) -> tuple[int, int]:
    """(lane groups per row, groups computed per row: rounded up to 8)."""
    g = w // (LANES // c)
    return g, -(-g // 8) * 8


def fold_taps(w):
    """(3, 3, C, C) conv weights -> (3, 3, 128, 128) banded weights:
    ``[dy, j]`` maps input group ``g - 1 + j`` to output group ``g``.
    Input pixel ``p`` of that group reaches output pixel ``q`` through
    tap ``dx = (j - 1) * P + p - q + 1`` when it lies in 0..2."""
    c = w.shape[2]
    p_ = LANES // c
    j, p, q = np.meshgrid(np.arange(3), np.arange(p_), np.arange(p_),
                          indexing="ij")
    dx = (j - 1) * p_ + p - q + 1
    joined = ((dx >= 0) & (dx <= 2)).astype(np.float32)
    blocks = w[:, np.clip(dx, 0, 2)] * joined[None, ..., None, None]
    # [dy, j, p, q, ci, co] -> rows (p, ci), columns (q, co)
    return blocks.transpose(0, 1, 2, 4, 3, 5).reshape(3, 3, LANES, LANES)


def _resblock_kernel(x_ref, w1_ref, b1_ref, w2_ref, b2_ref, o_ref,
                     x_pad, mid_pad, *, act: str, rows: int):
    H, G = x_ref.shape[1], x_ref.shape[2]
    gc = x_pad.shape[1] - 2
    x_pad[...] = jnp.zeros(x_pad.shape, jnp.float32)
    mid_pad[...] = jnp.zeros(mid_pad.shape, jnp.float32)
    x_pad[1:H + 1, 1:G + 1, :] = x_ref[0]

    def conv(src, w_ref, r0):
        acc = None
        for dy in range(3):
            for j in range(3):
                tap = src[pl.ds(r0 + dy, rows), j:j + gc, :]
                part = jnp.dot(tap.reshape(rows * gc, LANES), w_ref[dy, j],
                               preferred_element_type=jnp.float32)
                acc = part if acc is None else acc + part
        return acc.reshape(rows, gc, LANES)[:, :G, :]

    def conv1(i, carry):
        r0 = pl.multiple_of(i * rows, rows)
        mid = activation(act)(conv(x_pad, w1_ref, r0) + b1_ref[...])
        mid_pad[pl.ds(r0 + 1, rows), 1:G + 1, :] = mid
        return carry

    def conv2(i, carry):
        r0 = pl.multiple_of(i * rows, rows)
        o_ref[0, pl.ds(r0, rows)] = (conv(mid_pad, w2_ref, r0) + b2_ref[...]
                                     + x_ref[0, pl.ds(r0, rows)])
        return carry

    jax.lax.fori_loop(0, H // rows, conv1, 0)
    jax.lax.fori_loop(0, H // rows, conv2, 0)


def resblock_fused(x, w1, b1, w2, b2, *, act: str = "gelu_tanh",
                   interpret: bool | None = None):
    """x: (B, H, W, C); w1, w2: (3, 3, C, C); b1, b2: (C,).

    Returns x + conv2(act(conv1(x))) in fp32, SAME padding, stride 1.
    C must divide 128 and W must be a multiple of 128 // C.
    """
    interpret = default_interpret(interpret)
    B, H, W, C = x.shape
    assert LANES % C == 0 and W % (LANES // C) == 0, x.shape
    assert w1.shape == w2.shape == (3, 3, C, C), (w1.shape, w2.shape)
    p = LANES // C
    G, gc = folded_groups(W, C)
    rows = math.gcd(H, BAND_ROWS)
    out = pl.pallas_call(
        functools.partial(_resblock_kernel, act=act, rows=rows),
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, G, LANES), lambda b: (b, 0, 0, 0)),
            pl.BlockSpec((3, 3, LANES, LANES), lambda b: (0, 0, 0, 0)),
            pl.BlockSpec((1, LANES), lambda b: (0, 0)),
            pl.BlockSpec((3, 3, LANES, LANES), lambda b: (0, 0, 0, 0)),
            pl.BlockSpec((1, LANES), lambda b: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, G, LANES), lambda b: (b, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, G, LANES), jnp.float32),
        scratch_shapes=[pltpu.VMEM((H + 2, gc + 2, LANES), jnp.float32),
                        pltpu.VMEM((H + 2, gc + 2, LANES), jnp.float32)],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="resblock_op",
    )(x.astype(jnp.float32).reshape(B, H, G, LANES),
      fold_taps(w1.astype(jnp.float32)), jnp.tile(b1, p).reshape(1, LANES),
      fold_taps(w2.astype(jnp.float32)), jnp.tile(b2, p).reshape(1, LANES))
    return out.reshape(B, H, W, C)
