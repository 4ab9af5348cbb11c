"""Depthwise 3x3 taps read from a lane-chunked VMEM scratch.

Every fused conv kernel (mbconv, dsconv, supersite) runs its depthwise
stage the same way: the zero-padded input map is stored into a VMEM
scratch and each tap is a (possibly strided) read of that scratch.
Mosaic, the TPU Pallas compiler, forces both halves of this design:

* a strided slice of an in-register value (``a[s-1::s]``) lowers to a
  gather it refuses, while a strided read of a VMEM ref
  (``pl.ds(start, n, stride=s)``) compiles;
* a strided read needs 32-bit data in a buffer whose last dim is at
  most one lane tile (128) wide, so the scratch is laid out
  ``(C // lanes, Hp, Wp, lanes)`` and the taps run per lane chunk.

Output ``(t, u)`` of a stride-``s`` conv sums
``scr[row0 + s*t + dy, col0 + s*u + dx] * w[dy, dx]`` over the taps in
(dy, dx) order, starting from zero — element for element the arithmetic
of a stride-1 conv followed by the ``[row0::s, col0::s]`` subsample, so
the strided read changes no numerics.  ``row0``/``col0`` carry the SAME
anchor of each kernel family (``s - 1`` for mbconv and the int8 dsconv,
``0`` for the fp dsconv).
"""
from __future__ import annotations

import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def lane_split(c: int) -> tuple[int, int]:
    """(number of chunks, chunk width) covering ``c`` channels: the
    widest chunk of at most one lane tile that divides ``c`` (128 for
    every B1 width above 128)."""
    lanes = max(d for d in range(1, min(c, LANES) + 1) if c % d == 0)
    return c // lanes, lanes


def tap_scratch(h: int, w: int, c: int, dtype=jnp.float32):
    """VMEM scratch for one padded ``(h, w, c)`` map (``dtype`` must be
    32-bit: int8 maps are widened to int32 before they are stored)."""
    n, lanes = lane_split(c)
    return pltpu.VMEM((n, h, w, lanes), dtype)


def fill(scr, val, *, row0: int = 0, col0: int = 0) -> None:
    """Zero ``scr`` and store ``val`` (h, w, c) at ``[row0:, col0:]``:
    the zero border is the conv's SAME padding."""
    h, w, c = val.shape
    n, lanes = scr.shape[0], scr.shape[-1]
    scr[...] = jnp.zeros(scr.shape, scr.dtype)
    for k in range(n):
        scr[k, row0:row0 + h, col0:col0 + w, :] = \
            val[:, :, k * lanes:(k + 1) * lanes].astype(scr.dtype)


def dw_taps(scr, weight, *, rows: int, cols: int, stride: int = 1,
            row0: int = 0, col0: int = 0, ksize: int = 3):
    """Depthwise conv over the map in ``scr``: ``(rows, cols, c)`` in the
    scratch dtype.  ``weight(dy, dx, lo, hi)`` returns the tap's
    ``(hi - lo,)`` channel weights in that dtype."""
    n, lanes = scr.shape[0], scr.shape[-1]
    outs = []
    for k in range(n):
        lo, hi = k * lanes, (k + 1) * lanes
        acc = jnp.zeros((rows, cols, lanes), scr.dtype)
        for dy in range(ksize):
            for dx in range(ksize):
                tap = scr[k, pl.ds(row0 + dy, rows, stride=stride),
                          pl.ds(col0 + dx, cols, stride=stride), :]
                acc += tap * weight(dy, dx, lo, hi)[None, None, :]
        outs.append(acc)
    return outs[0] if n == 1 else jnp.concatenate(outs, axis=-1)
