"""Plain reference of EfficientViT's L series (Cai et al., ICCV 2023,
arXiv:2205.14756; upstream ``efficientvit_backbone_l*`` and
``ClsHead``) for the benchmark's configurations, independent of the
program under test: it imports nothing from ``src/``.

The network, from a configuration file's ``model`` object (``widths``,
``depths``, ``head_dim``, ``msa_scales``, ``expand_ratios``,
``down_expand``, ``head_widths``, ``num_classes``) and its
``image_size``:

* stem: 3x3 stride-2 conv 3 -> w0 with BN and GELU, then ``depths[0]``
  ResBlocks (3x3 conv + BN + GELU, 3x3 conv + BN, residual);
* S1, S2: a stride-2 FusedMBConv (3x3 conv C -> C * e * down_expand
  with BN and GELU, 1x1 conv with BN), then ``depths[i]`` residual
  FusedMBConvs of expansion e;
* S3: the same with MBConvs (1x1 + BN + GELU, depthwise 3x3 + BN +
  GELU, 1x1 + BN);
* S4: a stride-2 MBConv, then ``depths[4]`` EfficientViT modules: LiteMLA
  (qkv 1x1, per scale a depthwise s x s and a grouped 1x1 over the
  stacked qkv, ReLU linear attention per head of ``head_dim``, 1x1
  projection + BN), then an MBConv, each with a residual;
* head: 1x1 conv + BN + GELU, global average pool, fc1 (no bias) +
  LayerNorm (eps 1e-5) + GELU, fc2 with a bias.

Every GELU is the tanh form (upstream's activation registry builds
``nn.GELU(approximate="tanh")`` for ``"gelu"``).  Departures from
upstream, none of which changes the work:

* a conv that upstream gives a bias and no norm (``fewer_norm`` in S3
  and S4: MBConv's first two convs) is drawn as conv + BN; an inference
  BN folds to the same (w, b), so the served arithmetic is the same;
* convolutions pad as XLA's ``SAME`` does (at stride 2: nothing above
  and left, one row and column below and right), PyTorch's pad one on
  every side;
* the attention divides by ``max(den, 1e-6)`` where upstream adds 1e-15.

``forward`` runs every product at ``Precision.HIGHEST``;
``products="bf16x3"`` replaces each by the three-pass bf16 product
(hi*hi + hi*lo + lo*hi), the precision just below fp32, the control of
the configuration's limit.  There is no int8 L series: ``quant_bits``
must be None.  ``macs_per_image`` counts the work; ``fmbconv_work``
the FusedMBConv sites' operations and HBM bytes for their roofline.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

BN_EPS = 1e-5
LN_EPS = 1e-5
HIGHEST = lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# the layout
# ---------------------------------------------------------------------------

def _blocks(m: dict):
    """(stage, kind, c_in, c_out, stride, expand) of every block after the
    stem conv: kind "res" | "fmb" | "mb" | "att"."""
    w, d = m["widths"], m["depths"]
    e, de = m["expand_ratios"], m["down_expand"]
    kinds = m["stage_blocks"]
    if kinds[0] != "res":
        raise ValueError(f"stage_blocks={kinds}: the L series' stem is 'res'")
    out = [(0, "res", w[0], w[0], 1, e[0]) for _ in range(d[0])]
    for si, kind in enumerate(kinds[1:], start=1):
        out.append((si, "fmb" if kind == "fmb" else "mb", w[si - 1], w[si],
                    2, e[si] * de))
        out += [(si, kind, w[si], w[si], 1, e[si]) for _ in range(d[si])]
    return out


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

class _Keys:
    """Deterministic key stream: the i-th draw is ``fold_in(key, i)``."""

    def __init__(self, key):
        self.key, self.i = key, 0

    def __call__(self):
        self.i += 1
        return jax.random.fold_in(self.key, self.i)


def _conv_w(keys, k, c_in, c_out, groups=1):
    fan_in = k * k * c_in // groups
    w = jax.random.normal(keys(), (k, k, c_in // groups, c_out), jnp.float32)
    return {"w": w * fan_in ** -0.5}


def _uniform(keys, c):
    return jax.random.uniform(keys(), (c,), jnp.float32)


def _bn(keys, c):
    """Inference BatchNorm with non-trivial statistics, so that folding
    it into the conv is exercised."""
    return {"scale": 0.75 + 0.5 * _uniform(keys, c),
            "bias": 0.1 * (_uniform(keys, c) - 0.5),
            "mean": 0.1 * (_uniform(keys, c) - 0.5),
            "var": 0.75 + 0.5 * _uniform(keys, c)}


def _conv_bn_w(keys, k, c_in, c_out, groups=1):
    return {"conv": _conv_w(keys, k, c_in, c_out, groups),
            "bn": _bn(keys, c_out)}


def _block(keys, kind, c_in, c_out, expand, m):
    mid = c_in * expand
    if kind == "res":
        return {"conv1": _conv_bn_w(keys, 3, c_in, mid),
                "conv2": _conv_bn_w(keys, 3, mid, c_out)}
    if kind == "fmb":
        return {"spatial": _conv_bn_w(keys, 3, c_in, mid),
                "point": _conv_bn_w(keys, 1, mid, c_out)}
    if kind == "mb":
        return {"pw1": _conv_bn_w(keys, 1, c_in, mid),
                "dw": _conv_bn_w(keys, 3, mid, mid, groups=mid),
                "pw2": _conv_bn_w(keys, 1, mid, c_out)}
    hd, heads = m["head_dim"], c_in // m["head_dim"]
    total = heads * hd
    return {"msa": {"qkv": _conv_w(keys, 1, c_in, 3 * total),
                    "aggreg": [{"dw": _conv_w(keys, s, 3 * total, 3 * total,
                                              groups=3 * total),
                                "pw": _conv_w(keys, 1, 3 * total, 3 * total,
                                              groups=3 * heads)}
                               for s in m["msa_scales"]],
                    "proj": _conv_w(keys, 1,
                                    (1 + len(m["msa_scales"])) * total,
                                    c_in),
                    "proj_bn": _bn(keys, c_in)},
            "mbconv": _block(keys, "mb", c_in, c_in, expand, m)}


def init_params(key, cfg: dict):
    """Random weights for ``cfg`` (fan-in scaled normals, BatchNorm and
    LayerNorm parameters drawn near identity), in the served tree:
    ``stem_res`` a list, ``stage{1..4}`` ``{"down", "blocks"}``."""
    keys = _Keys(key)
    m = cfg["model"]
    p = {"stem_conv": _conv_bn_w(keys, 3, 3, m["widths"][0]), "stem_res": []}
    for si, kind, c_in, c_out, stride, e in _blocks(m):
        blk = _block(keys, kind, c_in, c_out, e, m)
        if si == 0:
            p["stem_res"].append(blk)
        elif stride == 2:
            p[f"stage{si}"] = {"down": blk, "blocks": []}
        else:
            p[f"stage{si}"]["blocks"].append(blk)
    hw1, hw2 = m["head_widths"]
    n = m["num_classes"]
    p["head"] = {
        "conv": _conv_bn_w(keys, 1, m["widths"][4], hw1),
        "fc1": {"w": jax.random.normal(keys(), (hw1, hw2)) * hw1 ** -0.5,
                "ln": {"scale": 0.75 + 0.5 * _uniform(keys, hw2),
                       "bias": 0.1 * (_uniform(keys, hw2) - 0.5)}},
        "fc2": {"w": jax.random.normal(keys(), (hw2, n)) * hw2 ** -0.5,
                "b": 0.1 * (_uniform(keys, n) - 0.5)}}
    return p


def images(key, n: int, size: int):
    """``n`` seeded images, (n, size, size, 3) float32."""
    return jax.random.normal(key, (n, size, size, 3), jnp.float32)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _split_bf16(x):
    """x = hi + lo + (the rest), hi and lo bfloat16 values kept in
    float32.  ``reduce_precision`` and not a cast pair, which XLA may
    drop as excess precision."""
    hi = lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return hi, lax.reduce_precision(x - hi, exponent_bits=8, mantissa_bits=7)


class _Arith:
    """The products one forward runs with."""

    def __init__(self, products):
        if products not in (None, "fp32", "bf16x3"):
            raise ValueError(f"products={products!r}")
        self.products = products or "fp32"

    def product(self, op, a, b):
        if self.products == "fp32":
            return op(a, b)
        a_hi, a_lo = _split_bf16(a)
        b_hi, b_lo = _split_bf16(b)
        return op(a_hi, b_hi) + op(a_hi, b_lo) + op(a_lo, b_hi)

    def conv(self, x, w, stride=1, groups=1):
        op = lambda a, b: lax.conv_general_dilated(            # noqa: E731
            a, b, (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=groups, precision=HIGHEST)
        return self.product(op, x, w)

    def einsum(self, spec, a, b):
        return self.product(
            lambda u, v: jnp.einsum(spec, u, v, precision=HIGHEST), a, b)


def _gelu(x):
    return jax.nn.gelu(x, approximate=True)


def _conv_bn(a, p, x, stride=1, groups=1, act=True):
    y = a.conv(x, p["conv"]["w"], stride, groups)
    bn = p["bn"]
    y = ((y - bn["mean"]) * lax.rsqrt(bn["var"] + BN_EPS) * bn["scale"]
         + bn["bias"])
    return _gelu(y) if act else y


def _mbconv(a, p, x, stride=1):
    y = _conv_bn(a, p["pw1"], x)
    y = _conv_bn(a, p["dw"], y, stride, groups=y.shape[-1])
    return _conv_bn(a, p["pw2"], y, act=False)


def _relu_attention(a, q, k, v, eps=1e-6):
    """ReLU linear attention, KV first: (B, N, h, d) each."""
    q, k = jax.nn.relu(q), jax.nn.relu(k)
    kv = a.einsum("bnhd,bnhe->bhde", k, v)
    num = a.einsum("bnhd,bhde->bnhe", q, kv)
    den = a.einsum("bnhd,bhd->bnh", q, jnp.sum(k, axis=1))[..., None]
    return num / jnp.maximum(den, eps)


def _lite_mla(a, p, x, head_dim):
    B, H, W, C = x.shape
    heads = C // head_dim
    total = heads * head_dim
    qkv = a.conv(x, p["qkv"]["w"])
    branches = [qkv]
    for agg in p["aggreg"]:
        y = a.conv(qkv, agg["dw"]["w"], groups=qkv.shape[-1])
        branches.append(a.conv(y, agg["pw"]["w"], groups=3 * heads))
    outs = []
    for t in branches:
        t = t.reshape(B, H * W, 3, heads, head_dim)
        o = _relu_attention(a, t[:, :, 0], t[:, :, 1], t[:, :, 2])
        outs.append(o.reshape(B, H, W, total))
    return _conv_bn(a, {"conv": p["proj"], "bn": p["proj_bn"]},
                    jnp.concatenate(outs, axis=-1), act=False)


def _layernorm(p, x):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def forward(params, x, cfg: dict, *, quant_bits=None, products="fp32"):
    """(B, S, S, 3) float32 images -> (B, num_classes) float32 logits."""
    if quant_bits is not None:
        raise ValueError("the L series has no int8 reference")
    a = _Arith(products)
    m = cfg["model"]
    y = _conv_bn(a, params["stem_conv"], x, stride=2)
    for p in params["stem_res"]:
        y = y + _conv_bn(a, p["conv2"], _conv_bn(a, p["conv1"], y),
                         act=False)
    for si in (1, 2, 3, 4):
        st = params[f"stage{si}"]
        for i, p in enumerate([st["down"]] + st["blocks"]):
            stride = 2 if i == 0 else 1
            if "spatial" in p:
                z = _conv_bn(a, p["spatial"], y, stride)
                z = _conv_bn(a, p["point"], z, act=False)
            elif "msa" in p:
                y = y + _lite_mla(a, p["msa"], y, m["head_dim"])
                z = _mbconv(a, p["mbconv"], y)
            else:
                z = _mbconv(a, p, y, stride)
            y = z if i == 0 else y + z
    head = params["head"]
    y = jnp.mean(_conv_bn(a, head["conv"], y), axis=(1, 2))
    y = a.einsum("bc,cf->bf", y, head["fc1"]["w"])
    y = _gelu(_layernorm(head["fc1"]["ln"], y))
    return a.einsum("bc,cf->bf", y, head["fc2"]["w"]) + head["fc2"]["b"]


# ---------------------------------------------------------------------------
# work
# ---------------------------------------------------------------------------

def _block_macs(kind, r_in, c_in, c_out, stride, expand, m):
    """(MACs of one block, its output resolution)."""
    r = r_in // stride
    mid = c_in * expand
    if kind == "res":
        return r * r * 9 * (c_in * mid + mid * c_out), r
    if kind == "fmb":
        return r * r * (9 * c_in * mid + mid * c_out), r
    if kind == "mb":
        return (r_in * r_in * c_in * mid + r * r * mid * 9
                + r * r * mid * c_out), r
    hd, scales = m["head_dim"], m["msa_scales"]
    heads = c_in // hd
    total, n_br, tok = heads * hd, 1 + len(scales), r * r
    macs = tok * c_in * 3 * total                                # qkv
    for s in scales:
        macs += tok * 3 * total * s * s + tok * 3 * total * hd   # agg
    macs += n_br * heads * hd * tok * hd                         # K^T V
    macs += n_br * heads * tok * hd * (hd + 1)                   # Q [KV|ksum]
    macs += tok * n_br * total * c_in                            # proj
    return macs + _block_macs("mb", r, c_in, c_in, 1, expand, m)[0], r


def macs_per_image(cfg: dict) -> int:
    """Multiply-accumulates of one image: convolutions, attention
    products and fc layers (elementwise work is not counted)."""
    m = cfg["model"]
    w = m["widths"]
    r = cfg["image_size"] // 2
    macs = r * r * w[0] * 3 * 9                                  # stem conv
    for _, kind, c_in, c_out, stride, e in _blocks(m):
        n, r = _block_macs(kind, r, c_in, c_out, stride, e, m)
        macs += n
    hw1, hw2 = m["head_widths"]
    return macs + r * r * w[4] * hw1 + hw1 * hw2 + hw2 * m["num_classes"]


def fmbconv_work(cfg: dict, batch: int) -> tuple:
    """(FLOPs, HBM bytes) of the FusedMBConv sites of one forward of
    ``batch`` images: 2 x their MACs, and each site's fp32 input, its
    BN-folded weights and biases, and its output, each moved once."""
    m = cfg["model"]
    r = cfg["image_size"] // 2
    flops = nbytes = 0
    for _, kind, c_in, c_out, stride, e in _blocks(m):
        r_in = r
        n, r = _block_macs(kind, r_in, c_in, c_out, stride, e, m)
        if kind != "fmb":
            continue
        mid = c_in * e
        flops += 2 * batch * n
        nbytes += 4 * (batch * r_in * r_in * c_in
                       + 9 * c_in * mid + mid + mid * c_out + c_out
                       + batch * r * r * c_out)
    return flops, nbytes
