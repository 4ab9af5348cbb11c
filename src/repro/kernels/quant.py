"""Shared in-kernel FIX8 arithmetic: requantization and the int8 GEMM.

One definition for every megakernel's inter-stage requant step (mbconv,
dsconv): ``requantize_i8`` delegates to ``core.quantization.
quantize_tensor`` (jnp-only, Pallas-traceable), so the kernels and the
reference ``conv2d_int8`` chain share the exact scale/clip/round
arithmetic and cannot drift apart.  Inside the kernels the quantized
block is one batch element, which makes the fused path bit-identical to
the reference chain at batch 1.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.quantization import quantize_tensor


def requantize_i8(x, bits: int = 8):
    """x fp32 -> (int8 values, fp32 scale), symmetric per-block.  The
    scale keeps ``x``'s rank as size-1 dims (``(1, 1)`` for a 2-D block):
    Mosaic keeps it a vector, which broadcasts against per-channel rows
    and stores into a ``(1, 1, 1)`` output block."""
    return quantize_tensor(x, axis=None, bits=bits, keepdims=True)


def int8_dot(a, b):
    """(M, K) int8 @ (K, N) int8 -> (M, N) int32, exact.  The precision
    is pinned to DEFAULT: a caller's ``jax.default_matmul_precision``
    ("highest" for an fp32 check) would otherwise ask Mosaic for an fp32
    contraction of integer operands, which it refuses."""
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               precision=jax.lax.Precision.DEFAULT,
                               preferred_element_type=jnp.int32)


def xs_per_batch(x_scale, batch: int):
    """The producer-epilogue activation-scale convention, one definition
    for every consumer kernel: a per-tensor scalar or per-batch-element
    (B,) scales -> a (B, 1, 1) fp32 array feeding a ``(1, 1, 1)``
    per-batch BlockSpec (scalars broadcast, so both conventions share one
    kernel).  The block's last two dims equal the array's, the form
    Mosaic accepts for a per-grid-step scalar."""
    xs = jnp.asarray(x_scale, jnp.float32).reshape(-1, 1, 1)
    return jnp.broadcast_to(xs, (batch, 1, 1))


def xs_per_batch_vec(x_scale, batch: int):
    """Same convention as a (B,) vector — the vmap axis the jnp oracles
    consume."""
    xs = jnp.asarray(x_scale, jnp.float32).reshape(-1)
    return jnp.broadcast_to(xs, (batch,))
