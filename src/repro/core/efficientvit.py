"""EfficientViT backbone (Cai et al., ICCV'23) — the paper's workload.

Macro architecture (paper Fig. 1): input stem (generic Conv + DSConv),
then four stages: S1/S2 stack MBConvs, S3/S4 stack EfficientViT Modules
(MSA + MBConv).  Every conv is followed by BN (foldable) and Hardswish
except block-final projections, matching §II.

The same walk serves the L series (``efficientvit_backbone_l*``): a
ResBlock stem, FusedMBConv stages S1/S2, an MBConv stage S3, an
EfficientViT-Module stage S4, GELU everywhere and a LayerNorm in the
head — chosen by ``EfficientViTConfig``'s ``stage_blocks``,
``expand_ratios``, ``down_expand``, ``act`` and ``head_norm``, whose
defaults give B1.  ``stage_layout`` lists the blocks of either pattern.

This module owns the *building blocks* (param init + reference block
forwards).  The network-level walk lives in ONE place —
``core.program.lower`` — and ``efficientvit()`` below is the reference
forward over that IR (``lower`` then ``execute`` without a plan), so
the forward, the fusion plan, the accelerator cycle model and the
fig6/table2 benchmarks all trace to the same lowering.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.core.relu_attention import MSAConfig, init_msa
from repro.layers.conv import conv2d, dwconv2d, init_conv2d, init_dwconv2d, init_pwconv, pwconv
from repro.layers.norms import batchnorm, init_batchnorm, init_layernorm


@dataclasses.dataclass(frozen=True)
class EfficientViTConfig:
    """One EfficientViT architecture; the defaults are B1.

    ``stage_blocks`` names the block kind of the stem (stage 0) and of
    S1..S4: ``"ds"`` DSConv or ``"res"`` ResBlock for the stem, then
    ``"mb"`` MBConv, ``"fmb"`` FusedMBConv or ``"att"`` EfficientViT
    Module (MSA + MBConv).  The stem's kind picks how ``depths`` counts:

    * B pattern (``"ds"`` stem, B1 ``("ds", "mb", "mb", "att", "att")``):
      an ``"mb"`` stage's depth INCLUDES its stride-2 first block (B1's
      S1/S2, a flat list of blocks); an ``"att"`` stage has one
      downsampling MBConv before its ``depths`` modules.
    * L pattern (``"res"`` stem, L2 ``("res", "fmb", "fmb", "mb",
      "att")``): every stage 1-4 opens with a stride-2 downsampling
      block (FusedMBConv in an ``"fmb"`` stage, MBConv otherwise) that
      ``depths`` does NOT count, then ``depths`` residual blocks.

    ``expand_ratios`` gives each stage's expansion (None: ``expand_ratio``
    everywhere); a downsampling block expands by ``down_expand`` times
    its stage's ratio (1 for B1, 4 for the L series).  ``act`` is every
    activation: ``"hswish"`` or ``"gelu_tanh"`` (PyTorch's
    ``GELU(approximate="tanh")``).  ``head_norm="ln"`` puts a LayerNorm
    between fc1 and its activation and gives fc2 a bias, as the L
    series' classifier head has them; ``"none"`` is B1's head.
    """
    name: str = "efficientvit-b1"
    widths: Sequence[int] = (16, 32, 64, 128, 256)
    depths: Sequence[int] = (1, 2, 3, 3, 4)
    head_dim: int = 16
    msa_scales: Sequence[int] = (5,)
    expand_ratio: int = 4
    head_widths: Sequence[int] = (1536, 1600)
    num_classes: int = 1000
    image_size: int = 224
    dtype: jnp.dtype = jnp.float32
    stage_blocks: Sequence[str] = ("ds", "mb", "mb", "att", "att")
    expand_ratios: Sequence[int] | None = None
    down_expand: int = 1
    act: str = "hswish"
    head_norm: str = "none"


B1 = EfficientViTConfig()
B1_SMOKE = EfficientViTConfig(
    name="efficientvit-b1-smoke", widths=(8, 16, 24, 32, 48),
    depths=(1, 1, 1, 1, 1), head_widths=(64, 64), num_classes=10,
    image_size=64)
# EfficientViT-L2 (upstream efficientvit_backbone_l2 + ClsHead(512,
# [3072, 3200])): qkv dim 32 gives S4 16 heads of 32
L2 = EfficientViTConfig(
    name="efficientvit-l2", widths=(32, 64, 128, 256, 512),
    depths=(1, 2, 2, 8, 8), head_dim=32, head_widths=(3072, 3200),
    stage_blocks=("res", "fmb", "fmb", "mb", "att"),
    expand_ratios=(1, 4, 4, 4, 6), down_expand=4, act="gelu_tanh",
    head_norm="ln")
# the L pattern at a size the CPU runs in seconds
L_SMOKE = dataclasses.replace(
    L2, name="efficientvit-l-smoke", widths=(16, 32, 32, 64, 64),
    depths=(1, 1, 1, 1, 1), head_widths=(64, 64), num_classes=10,
    image_size=64)


def gelu_tanh(x):
    """GELU in its tanh form, PyTorch's ``GELU(approximate="tanh")``."""
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * (x * x * x))))


ACTIVATIONS = {"hswish": jax.nn.hard_swish, "gelu_tanh": gelu_tanh}


def activation(name: str):
    """The activation function named by a config's or a site's ``act``."""
    try:
        return ACTIVATIONS[name]
    except KeyError:
        raise ValueError(f"activation {name!r}; have "
                         f"{sorted(ACTIVATIONS)}") from None


@dataclasses.dataclass(frozen=True)
class Block:
    """One block of ``stage_layout``: its kind (``stage_blocks``'
    vocabulary), site-local name, param path, channels, stride, expansion
    (mid = c_in * expand) and whether it adds its input back."""
    stage: str
    kind: str
    name: str
    path: tuple
    c_in: int
    c_out: int
    stride: int
    expand: int
    residual: bool


STEM_KEYS = {"ds": "stem_ds", "res": "stem_res"}


def stage_layout(cfg: EfficientViTConfig) -> list[Block]:
    """Every block after the stem conv, in order (see the config's
    docstring for how ``depths`` counts under each pattern)."""
    w, d = tuple(cfg.widths), tuple(cfg.depths)
    kinds = tuple(cfg.stage_blocks)
    ex = tuple(cfg.expand_ratios or (cfg.expand_ratio,) * 5)
    if len(kinds) != 5 or kinds[0] not in STEM_KEYS or any(
            k not in ("mb", "fmb", "att") for k in kinds[1:]):
        raise ValueError(f"stage_blocks={kinds}: the stem is one of "
                         f"{sorted(STEM_KEYS)}, S1..S4 'mb', 'fmb' or 'att'")
    out = [Block("stem", kinds[0], f"{kinds[0]}{i}",
                 (STEM_KEYS[kinds[0]], i), w[0], w[0], 1, ex[0], True)
           for i in range(d[0])]
    b_pattern = kinds[0] == "ds"
    for si in range(1, 5):
        kind, st, c = kinds[si], f"S{si}", w[si]
        if b_pattern and kind == "mb":        # depth counts the down block
            out += [Block(st, "mb", f"mb{bi}", (f"stage{si}", bi),
                          w[si - 1] if bi == 0 else c, c,
                          2 if bi == 0 else 1,
                          ex[si] * (cfg.down_expand if bi == 0 else 1),
                          bi > 0) for bi in range(d[si])]
            continue
        out.append(Block(st, "fmb" if kind == "fmb" else "mb", "down",
                         (f"stage{si}", "down"), w[si - 1], c, 2,
                         ex[si] * cfg.down_expand, False))
        name = "evit" if kind == "att" else kind
        out += [Block(st, kind, f"{name}{bi}", (f"stage{si}", "blocks", bi),
                      c, c, 1, ex[si], True) for bi in range(d[si])]
    return out


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------

def init_conv_bn(key, k, c_in, c_out, dtype, *, groups=1):
    return {
        "conv": init_conv2d(key, k, c_in, c_out, groups=groups, bias=False,
                            dtype=dtype),
        "bn": init_batchnorm(c_out, dtype),
    }


def conv_bn_act(p, x, *, stride=1, groups=1, act="hswish"):
    """fp32 conv+BN, or the FIX8 folded path when the block was quantized
    by core.quantization.quantize_efficientvit; then the activation
    ``act`` names (none when it is empty)."""
    if "qconv" in p:
        from repro.core.quantization import conv2d_int8
        y = conv2d_int8(p["qconv"], x, stride=stride, groups=groups)
    else:
        y = conv2d(p["conv"], x, stride=stride, groups=groups)
        y = batchnorm(p["bn"], y)
    return activation(act)(y) if act else y


def init_dsconv(key, c_in, c_out, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "dw": init_conv_bn(k1, 3, c_in, c_in, dtype, groups=c_in),
        "pw": init_conv_bn(k2, 1, c_in, c_out, dtype),
    }


def dsconv(p, x, *, stride=1, act="hswish"):
    y = conv_bn_act(p["dw"], x, stride=stride, groups=x.shape[-1], act=act)
    return conv_bn_act(p["pw"], y, act="")


def init_resblock(key, c, expand, dtype):
    k1, k2 = jax.random.split(key)
    return {"conv1": init_conv_bn(k1, 3, c, c * expand, dtype),
            "conv2": init_conv_bn(k2, 3, c * expand, c, dtype)}


def resblock(p, x, *, act="gelu_tanh"):
    """ResBlock body (the L series' stem): 3x3 conv+BN+act, 3x3 conv+BN;
    the residual add is the caller's."""
    return conv_bn_act(p["conv2"], conv_bn_act(p["conv1"], x, act=act),
                       act="")


def init_fmbconv(key, c_in, c_out, expand, dtype):
    k1, k2 = jax.random.split(key)
    return {"spatial": init_conv_bn(k1, 3, c_in, c_in * expand, dtype),
            "point": init_conv_bn(k2, 1, c_in * expand, c_out, dtype)}


def fmbconv(p, x, *, stride=1, act="gelu_tanh"):
    """FusedMBConv: a dense 3x3 conv C_in -> mid (stride ``stride``) with
    BN and the activation, then a 1x1 conv mid -> C_out with BN."""
    y = conv_bn_act(p["spatial"], x, stride=stride, act=act)
    return conv_bn_act(p["point"], y, act="")


def init_mbconv(key, c_in, c_out, expand, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    mid = c_in * expand
    return {
        "pw1": init_conv_bn(k1, 1, c_in, mid, dtype),
        "dw": init_conv_bn(k2, 3, mid, mid, dtype, groups=mid),
        "pw2": init_conv_bn(k3, 1, mid, c_out, dtype),
    }


def mbconv(p, x, *, stride=1, act="hswish"):
    """PWConv -> DWConv -> PWConv, BN + activation on all but the last
    (§II)."""
    y = conv_bn_act(p["pw1"], x, act=act)
    y = conv_bn_act(p["dw"], y, stride=stride, groups=y.shape[-1], act=act)
    return conv_bn_act(p["pw2"], y, act="")


def init_evit_module(key, c, head_dim, scales, expand, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "msa": init_msa(k1, MSAConfig(c, head_dim, scales, dtype)),
        "mbconv": init_mbconv(k2, c, c, expand, dtype),
    }


# ---------------------------------------------------------------------------
# full model
# ---------------------------------------------------------------------------

def init_efficientvit(key, cfg: EfficientViTConfig = B1):
    keys = iter(jax.random.split(key, 64))
    w, dt = cfg.widths, cfg.dtype
    params = {"stem_conv": init_conv_bn(next(keys), 3, 3, w[0], dt)}

    def put(path, value):
        node = params
        for k, nxt in zip(path, path[1:]):
            if isinstance(node, dict) and k not in node:
                node[k] = [] if isinstance(nxt, int) else {}
            node = node[k]
        if isinstance(node, list):
            node.append(value)
        else:
            node[path[-1]] = value

    for b in stage_layout(cfg):
        k = next(keys)
        if b.kind == "ds":
            blk = init_dsconv(k, b.c_in, b.c_out, dt)
        elif b.kind == "res":
            blk = init_resblock(k, b.c_in, b.expand, dt)
        elif b.kind == "fmb":
            blk = init_fmbconv(k, b.c_in, b.c_out, b.expand, dt)
        elif b.kind == "mb":
            blk = init_mbconv(k, b.c_in, b.c_out, b.expand, dt)
        else:
            blk = init_evit_module(k, b.c_in, cfg.head_dim,
                                   tuple(cfg.msa_scales), b.expand, dt)
        put(b.path, blk)
    kh, k1, k2 = jax.random.split(next(keys), 3)
    hw1, hw2 = cfg.head_widths
    params["head"] = {
        "conv": init_conv_bn(kh, 1, w[4], hw1, dt),
        "fc1": {"w": (jax.random.normal(k1, (hw1, hw2), jnp.float32)
                      * hw1 ** -0.5).astype(dt)},
        "fc2": {"w": (jax.random.normal(k2, (hw2, cfg.num_classes),
                                        jnp.float32) * hw2 ** -0.5
                      ).astype(dt)},
    }
    if cfg.head_norm == "ln":
        params["head"]["fc1"]["ln"] = init_layernorm(hw2, dt)
        params["head"]["fc2"]["b"] = jnp.zeros((cfg.num_classes,), dt)
    return params


def efficientvit(params, x, cfg: EfficientViTConfig = B1):
    """x: (B, H, W, 3) image -> (B, num_classes) logits: the reference
    forward (``core.program.lower`` for the input's shape, then
    ``execute`` without a plan).  A plan-routed forward is
    ``execute(program, params, x, plan=plan_program(program, params))``.
    """
    from repro.core.program import execute, lower

    program = lower(cfg, batch=x.shape[0], image_size=x.shape[1])
    return execute(program, params, x)


# ---------------------------------------------------------------------------
# layer manifest (drives the accelerator cycle model + benchmarks)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class OpRecord:
    stage: str
    name: str
    kind: str          # conv | pw | dw | matmul | group_pw
    h: int             # output spatial height (or M rows for matmul)
    w: int             # output spatial width (or 1 for matmul)
    c_in: int          # reduction length (C_in * k * k for conv)
    c_out: int
    k: int = 1
    fused_with_prev: bool = False   # TMP inter-layer fusion target

    @property
    def macs(self) -> int:
        if self.kind == "dw":  # one input channel per output channel
            return self.h * self.w * self.c_out * self.k * self.k
        return self.h * self.w * self.c_out * self.c_in * (
            self.k * self.k if self.kind == "conv" else 1)

    @property
    def reduction(self) -> int:
        """Parallelizable reduction length per output element."""
        if self.kind == "dw":
            return self.k * self.k
        if self.kind == "conv":
            return self.c_in * self.k * self.k
        return self.c_in


def total_macs(cfg: EfficientViTConfig = B1) -> int:
    """MACs of one inference at ``cfg.image_size``, from the same
    program IR the forward executes."""
    from repro.core.program import lower, manifest
    return sum(op.macs for op in manifest(lower(cfg)))
