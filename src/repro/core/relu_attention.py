"""EfficientViT's Lightweight Multi-Scale Attention (MSA / LiteMLA).

Faithful to Cai et al. (ICCV'23) + the accelerator paper's Fig. 2(b):

  1. 1x1 conv projects input to Q/K/V (``3 * total_dim`` channels).
  2. Multi-scale token aggregation: per scale, a depthwise k x k conv +
     grouped 1x1 conv over the stacked QKV (the "group Convs" whose low
     input-channel parallelism Fig. 6 calls out).
  3. ReLU-based global attention per scale:
         out = (ReLU(Q) @ (ReLU(K)^T V)) / (ReLU(Q) @ rowsum(ReLU(K)^T))
     — Softmax-free, linear in token count via associativity.  The
     divisor path is the K-adder-tree + divider pipeline of §III-D.
  4. Concat scales, 1x1 projection (+BN).

``msa`` is the plain jnp reference; the fused Pallas module
(kernels/relu_attn) is what a plan routes a fused site to.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.layers.conv import conv2d, init_conv2d, init_pwconv, pwconv
from repro.layers.norms import batchnorm, init_batchnorm


@dataclasses.dataclass(frozen=True)
class MSAConfig:
    channels: int
    head_dim: int = 16
    scales: Sequence[int] = (5,)
    dtype: jnp.dtype = jnp.float32

    @property
    def n_heads(self) -> int:
        return self.channels // self.head_dim

    @property
    def total_dim(self) -> int:
        return self.n_heads * self.head_dim


def init_msa(key, cfg: MSAConfig):
    keys = jax.random.split(key, 3 + 2 * len(cfg.scales))
    qkv_dim = 3 * cfg.total_dim
    p = {
        "qkv": init_pwconv(keys[0], cfg.channels, qkv_dim, bias=False,
                           dtype=cfg.dtype),
        "aggreg": [],
        "proj": init_pwconv(keys[1], (1 + len(cfg.scales)) * cfg.total_dim,
                            cfg.channels, bias=False, dtype=cfg.dtype),
        "proj_bn": init_batchnorm(cfg.channels, cfg.dtype),
    }
    for i, s in enumerate(cfg.scales):
        kd, kp = keys[3 + 2 * i], keys[4 + 2 * i]
        p["aggreg"].append({
            # depthwise s x s over stacked QKV
            "dw": init_conv2d(kd, s, qkv_dim, qkv_dim, groups=qkv_dim,
                              bias=False, dtype=cfg.dtype),
            # grouped 1x1 (groups = 3 * heads)
            "pw": init_conv2d(kp, 1, qkv_dim, qkv_dim, groups=3 * cfg.n_heads,
                              bias=False, dtype=cfg.dtype),
        })
    return p


def relu_global_attention(q, k, v, eps: float = 1e-6):
    """Fig. 2(b): ReLU(Q) [ReLU(K)^T V] with rowsum divisor.

    q, k, v: (B, N, h, d) multi-head token layout, non-causal.
    Computed KV-first: O(N * d^2) instead of O(N^2 * d).
    """
    pq = jax.nn.relu(q.astype(jnp.float32))
    pk = jax.nn.relu(k.astype(jnp.float32))
    vf = v.astype(jnp.float32)
    kv = jnp.einsum("bnhd,bnhe->bhde", pk, vf)       # ReLU(K)^T V
    ksum = jnp.sum(pk, axis=1)                        # rowsum (K-adder-tree)
    num = jnp.einsum("bnhd,bhde->bnhe", pq, kv)
    den = jnp.einsum("bnhd,bhd->bnh", pq, ksum)[..., None]
    return (num / jnp.maximum(den, eps)).astype(q.dtype)


def _conv_any(p, x, *, groups=1):
    """fp32 or FIX8 conv depending on whether the weight was quantized."""
    if "qconv" in p:
        from repro.core.quantization import conv2d_int8
        return conv2d_int8(p["qconv"], x, groups=groups)
    return conv2d(p, x, groups=groups)


def msa(params, x, cfg: MSAConfig, *, attention_fn=relu_global_attention):
    """x: (B, H, W, C) -> (B, H, W, C).

    The reference MSA module: a Python loop over the ``1 + len(scales)``
    branches, each through ``attention_fn``.  A FIX8 tree
    (``qconv`` leaves) runs its convs through ``conv2d_int8``.  The
    fused module (all branches and heads in ONE attention launch) is the
    kernel registry's ``msa`` kind, which ``core.program.execute``
    dispatches to when the plan fuses the site.
    """
    B, H, W, C = x.shape
    qkv = _conv_any(params["qkv"], x)                 # (B,H,W,3*total)
    multi = [qkv]
    for i, s in enumerate(cfg.scales):
        agg = _conv_any(params["aggreg"][i]["dw"], qkv, groups=qkv.shape[-1])
        agg = _conv_any(params["aggreg"][i]["pw"], agg, groups=3 * cfg.n_heads)
        multi.append(agg)
    outs = []
    for branch in multi:
        t = branch.reshape(B, H * W, 3, cfg.n_heads, cfg.head_dim)
        q, k, v = t[:, :, 0], t[:, :, 1], t[:, :, 2]
        o = attention_fn(q, k, v)
        outs.append(o.reshape(B, H, W, cfg.total_dim))
    out = jnp.concatenate(outs, axis=-1)
    if "qconv" in params["proj"]:
        return _conv_any(params["proj"], out)  # BN folded by quantization
    out = pwconv(params["proj"], out)
    return batchnorm(params["proj_bn"], out)
