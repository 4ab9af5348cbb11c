"""Pure-jnp oracle for the fused FusedMBConv kernel.

Semantics match ``core.efficientvit.fmbconv`` with BN already folded
into both convs: dense 3x3 conv (SAME, stride 1 or 2) + bias + the
activation, 1x1 conv + bias, plus the input when ``residual``.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def fmbconv_ref(x, w1, b1, w2, b2, *, stride: int = 1,
                act: str = "gelu_tanh", residual: bool = False):
    """x: (B, H, W, C); w1: (3, 3, C, M); w2: (M, F) -> (B, Ho, Wo, F)."""
    from repro.core.efficientvit import activation
    xf = x.astype(jnp.float32)
    mid = lax.conv_general_dilated(
        xf, w1.astype(jnp.float32), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    mid = activation(act)(mid + b1)
    out = jnp.einsum("bhwm,mf->bhwf", mid, w2.astype(jnp.float32)) + b2
    return out + xf if residual else out
