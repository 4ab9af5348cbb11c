"""Pure-jnp oracle for the fused ResBlock kernel.

Semantics match ``core.efficientvit.resblock`` plus the block's residual,
with BN already folded into both convs: dense 3x3 conv (SAME, stride 1)
+ bias + the activation, dense 3x3 conv + bias, plus the input.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def resblock_ref(x, w1, b1, w2, b2, *, act: str = "gelu_tanh"):
    """x: (B, H, W, C); w1, w2: (3, 3, C, C) -> (B, H, W, C)."""
    from repro.core.efficientvit import activation
    xf = x.astype(jnp.float32)
    dn = ("NHWC", "HWIO", "NHWC")
    mid = lax.conv_general_dilated(xf, w1.astype(jnp.float32), (1, 1),
                                   "SAME", dimension_numbers=dn)
    mid = activation(act)(mid + b1)
    out = lax.conv_general_dilated(mid, w2.astype(jnp.float32), (1, 1),
                                   "SAME", dimension_numbers=dn)
    return out + b2 + xf
