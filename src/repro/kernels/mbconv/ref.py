"""Pure-jnp oracle for the fused MBConv megakernel.

Semantics match ``core.efficientvit.mbconv`` with BN already folded into
each conv: PWConv(c_in->mid) + bias + Hardswish, depthwise 3x3 (SAME
padding, stride 1 or 2) + bias + Hardswish, PWConv(mid->c_out) + bias,
no activation after the projection (paper §II).

SAME for a 3x3 stride-s conv equals the stride-1 conv over a (1,1)-padded
input sampled at offset s-1 with step s (for even H, W) — the form both
this oracle and the Pallas kernel use so they agree with
``lax.conv_general_dilated(padding="SAME")`` bit-for-bit in fp32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def mbconv_ref(x, w1, b1, dw_w, dw_b, w2, b2, *, stride: int = 1,
               act: str = "hswish"):
    """x: (B, H, W, C); w1: (C, M); dw_w: (3, 3, M); w2: (M, F).

    Returns (B, Ho, Wo, F) fp32 with Ho = H // stride; ``act`` names the
    activation after both expansion stages (Hardswish in the B series).
    """
    from repro.core.efficientvit import activation
    f = activation(act)
    B, H, W, C = x.shape
    xf = x.astype(jnp.float32)
    mid = jnp.einsum("bhwc,cm->bhwm", xf, w1.astype(jnp.float32))
    mid = f(mid + b1[None, None, None, :])
    mp = jnp.pad(mid, ((0, 0), (1, 1), (1, 1), (0, 0)))
    acc = jnp.zeros_like(mid)
    for dy in range(3):
        for dx in range(3):
            acc = acc + mp[:, dy:dy + H, dx:dx + W, :] \
                * dw_w[dy, dx][None, None, None, :]
    acc = acc + dw_b[None, None, None, :]
    if stride > 1:
        acc = acc[:, stride - 1::stride, stride - 1::stride, :]
    acc = f(acc)
    out = jnp.einsum("bhwm,mf->bhwf", acc, w2.astype(jnp.float32))
    return out + b2[None, None, None, :]


def mbconv_int8_ref(x_q, x_scale, w1_q, s1, b1, dw_q, dw_s, dw_b, w2_q, s2,
                    b2, *, stride: int = 1):
    """Pure-jnp oracle for the FIX8 megakernel (same argument convention).

    Mirrors the reference quantized chain (``core.quantization.
    conv2d_int8`` per stage: int32 accumulation, fp32 dequant, Hardswish,
    dynamic symmetric requantization) with the kernel's per-batch-element
    inter-stage activation scales, via vmap over the batch.  ``x_scale``
    may be a per-tensor scalar or per-batch (B,) scales (the producer-
    epilogue convention).
    """
    from repro.core.quantization import quantize_tensor
    from repro.kernels.quant import xs_per_batch_vec

    sx_b = xs_per_batch_vec(x_scale, x_q.shape[0])

    def one(xi, x_scale):                            # (H, W, C) int8
        H, W, C = xi.shape
        M = w1_q.shape[1]
        acc = jnp.einsum("hwc,cm->hwm", xi.astype(jnp.int32),
                         w1_q.astype(jnp.int32))
        mid = acc.astype(jnp.float32) * (x_scale * s1)[None, None, :] \
            + b1[None, None, :]
        mid = jax.nn.hard_swish(mid)
        mq, s_mid = quantize_tensor(mid)
        mp = jnp.pad(mq, ((1, 1), (1, 1), (0, 0))).astype(jnp.int32)
        acc2 = jnp.zeros((H, W, M), jnp.int32)
        for dy in range(3):
            for dx in range(3):
                acc2 += mp[dy:dy + H, dx:dx + W, :] \
                    * dw_q[dy, dx].astype(jnp.int32)[None, None, :]
        dw = acc2.astype(jnp.float32) * (s_mid * dw_s)[None, None, :] \
            + dw_b[None, None, :]
        if stride > 1:
            dw = dw[stride - 1::stride, stride - 1::stride, :]
        dw = jax.nn.hard_swish(dw)
        dq, s_dw = quantize_tensor(dw)
        acc3 = jnp.einsum("hwm,mf->hwf", dq.astype(jnp.int32),
                          w2_q.astype(jnp.int32))
        return acc3.astype(jnp.float32) * (s_dw * s2)[None, None, :] \
            + b2[None, None, :]

    return jax.vmap(one)(x_q, sx_b)
