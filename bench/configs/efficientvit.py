"""Plain reference of EfficientViT (Cai et al., ICCV 2023, arXiv:2205.14756)
for the benchmark's configurations, written against the paper and
independent of the program under test: it imports nothing from ``src/``.

Three things live here, all driven by the architecture in a
configuration file's ``model`` object and its ``image_size``:

* ``init_params``: random weights from a key, in the layout the served
  engine takes (conv + BatchNorm pairs, bare MSA convs, two fc layers).
  The benchmark makes them in one jitted call; the program receives
  them, and the reference reads the same fp32 tree.
* ``forward``: the network in ``jax.numpy``, every product at
  ``Precision.HIGHEST``.  ``quant_bits`` runs the FIX8 arithmetic of the
  accelerator paper (arXiv:2403.20230): BatchNorm folded into the conv,
  weights symmetric per output channel, every conv and fc input
  quantized per image by its absmax, integer products accumulated in
  int32, dequantized in fp32, and the attention core in fp32.
  ``products="bf16x3"`` replaces every fp32 product by the three-pass
  bf16 product (hi*hi + hi*lo + lo*hi), the precision just below fp32.
  ``quant_bits=4`` and ``products="bf16x3"`` are the controls.
* ``macs_per_image``: the multiply-accumulates of one image, counted
  from the sizes; the benchmark's work count.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

BN_EPS = 1e-5
HIGHEST = lax.Precision.HIGHEST


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


def _bn(keys, c):
    """Inference BatchNorm with non-trivial statistics, so that folding
    it into the conv is exercised."""
    u = lambda: jax.random.uniform(keys(), (c,), jnp.float32)   # noqa: E731
    return {"scale": 0.75 + 0.5 * u(), "bias": 0.1 * (u() - 0.5),
            "mean": 0.1 * (u() - 0.5), "var": 0.75 + 0.5 * u()}


def _conv_bn(keys, k, c_in, c_out, groups=1):
    return {"conv": _conv_w(keys, k, c_in, c_out, groups),
            "bn": _bn(keys, c_out)}


def _mbconv(keys, c_in, c_out, expand):
    mid = c_in * expand
    return {"pw1": _conv_bn(keys, 1, c_in, mid),
            "dw": _conv_bn(keys, 3, mid, mid, groups=mid),
            "pw2": _conv_bn(keys, 1, mid, c_out)}


def _msa(keys, c, head_dim, scales):
    total = (c // head_dim) * head_dim
    heads = c // head_dim
    return {"qkv": _conv_w(keys, 1, c, 3 * total),
            "aggreg": [{"dw": _conv_w(keys, s, 3 * total, 3 * total,
                                      groups=3 * total),
                        "pw": _conv_w(keys, 1, 3 * total, 3 * total,
                                      groups=3 * heads)} for s in scales],
            "proj": _conv_w(keys, 1, (1 + len(scales)) * total, c),
            "proj_bn": _bn(keys, c)}


def init_params(key, cfg: dict):
    """Random weights for ``cfg`` (fan-in scaled normals, BatchNorm
    statistics drawn near identity)."""
    keys = _Keys(key)
    m = cfg["model"]
    w, d, e = m["widths"], m["depths"], m["expand_ratio"]
    p = {"stem_conv": _conv_bn(keys, 3, 3, w[0]),
         "stem_ds": [{"dw": _conv_bn(keys, 3, w[0], w[0], groups=w[0]),
                      "pw": _conv_bn(keys, 1, w[0], w[0])}
                     for _ in range(d[0])]}
    for si in (1, 2):
        p[f"stage{si}"] = [_mbconv(keys, w[si - 1] if bi == 0 else w[si],
                                   w[si], e) for bi in range(d[si])]
    for si in (3, 4):
        p[f"stage{si}"] = {
            "down": _mbconv(keys, w[si - 1], w[si], e),
            "blocks": [{"msa": _msa(keys, w[si], m["head_dim"],
                                    m["msa_scales"]),
                        "mbconv": _mbconv(keys, w[si], w[si], e)}
                       for _ in range(d[si])]}
    hw1, hw2 = m["head_widths"]
    p["head"] = {"conv": _conv_bn(keys, 1, w[4], hw1),
                 "fc1": {"w": jax.random.normal(keys(), (hw1, hw2))
                         * hw1 ** -0.5},
                 "fc2": {"w": jax.random.normal(keys(), (hw2,
                                                        m["num_classes"]))
                         * hw2 ** -0.5}}
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
    """The products and quantization one forward runs with."""

    def __init__(self, quant_bits, products):
        if products not in ("fp32", "bf16x3"):
            raise ValueError(f"products={products!r}")
        if quant_bits not in (None, 8, 4):
            raise ValueError(f"quant_bits={quant_bits!r}")
        self.bits, self.products = quant_bits, products

    # fp32 products ------------------------------------------------------
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

    # FIX8 ---------------------------------------------------------------
    @property
    def qmax(self):
        return 2 ** (self.bits - 1) - 1

    def quant(self, x, axes):
        absmax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
        scale = jnp.maximum(absmax, 1e-8) / self.qmax
        q = jnp.clip(jnp.round(x / scale), -self.qmax - 1, self.qmax)
        return q.astype(jnp.int8), scale

    def qconv(self, x, w, bias, stride=1, groups=1):
        """int conv of per-image-quantized ``x`` with per-channel ``w``."""
        wq, sw = self.quant(w, (0, 1, 2))
        xq, sx = self.quant(x, (1, 2, 3))
        acc = lax.conv_general_dilated(
            xq, wq, (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=groups, preferred_element_type=jnp.int32)
        return acc.astype(jnp.float32) * (sx * sw.reshape(-1)) + bias

    def qfc(self, h, w):
        wq, sw = self.quant(w, (0,))
        hq, sh = self.quant(h, (1,))
        acc = jnp.einsum("bc,cf->bf", hq, wq,
                         preferred_element_type=jnp.int32)
        return acc.astype(jnp.float32) * (sh * sw.reshape(-1))


def _fold(bn):
    gamma = bn["scale"] * lax.rsqrt(bn["var"] + BN_EPS)
    return gamma, bn["bias"] - bn["mean"] * gamma


def _conv_bn_act(a, p, x, stride=1, groups=1, act=True):
    if a.bits is None:
        y = a.conv(x, p["conv"]["w"], stride, groups)
        bn = p["bn"]
        y = ((y - bn["mean"]) * lax.rsqrt(bn["var"] + BN_EPS)
             * bn["scale"] + bn["bias"])
    else:
        gamma, beta = _fold(p["bn"])
        y = a.qconv(x, p["conv"]["w"] * gamma, beta, stride, groups)
    return jax.nn.hard_swish(y) if act else y


def _bare_conv(a, p, x, groups=1):
    if a.bits is None:
        return a.conv(x, p["w"], 1, groups)
    return a.qconv(x, p["w"], 0.0, 1, groups)


def _mbconv_fwd(a, p, x, stride=1):
    y = _conv_bn_act(a, p["pw1"], x)
    y = _conv_bn_act(a, p["dw"], y, stride, groups=y.shape[-1])
    return _conv_bn_act(a, p["pw2"], y, act=False)


def _relu_attention(a, q, k, v, eps=1e-6):
    """ReLU linear attention, KV first: (B, N, h, d) each."""
    q, k = jax.nn.relu(q), jax.nn.relu(k)
    kv = a.einsum("bnhd,bnhe->bhde", k, v)
    num = a.einsum("bnhd,bhde->bnhe", q, kv)
    den = a.einsum("bnhd,bhd->bnh", q, jnp.sum(k, axis=1))[..., None]
    return num / jnp.maximum(den, eps)


def _msa_fwd(a, p, x, head_dim):
    B, H, W, C = x.shape
    heads = C // head_dim
    total = heads * head_dim
    qkv = _bare_conv(a, p["qkv"], x)
    branches = [qkv]
    for agg in p["aggreg"]:
        y = _bare_conv(a, agg["dw"], qkv, groups=qkv.shape[-1])
        branches.append(_bare_conv(a, agg["pw"], y, groups=3 * heads))
    outs = []
    for t in branches:
        t = t.reshape(B, H * W, 3, heads, head_dim)
        o = _relu_attention(a, t[:, :, 0], t[:, :, 1], t[:, :, 2])
        outs.append(o.reshape(B, H, W, total))
    out = jnp.concatenate(outs, axis=-1)
    return _conv_bn_act(a, {"conv": p["proj"], "bn": p["proj_bn"]}, out,
                        act=False)


def forward(params, x, cfg: dict, *, quant_bits=None, products="fp32"):
    """(B, S, S, 3) float32 images -> (B, num_classes) float32 logits."""
    a = _Arith(quant_bits, products)
    y = _conv_bn_act(a, params["stem_conv"], x, stride=2)
    for p in params["stem_ds"]:
        z = _conv_bn_act(a, p["dw"], y, groups=y.shape[-1])
        y = y + _conv_bn_act(a, p["pw"], z, act=False)
    for si in (1, 2):
        for bi, p in enumerate(params[f"stage{si}"]):
            z = _mbconv_fwd(a, p, y, stride=2 if bi == 0 else 1)
            y = z if bi == 0 else y + z
    for si in (3, 4):
        st = params[f"stage{si}"]
        y = _mbconv_fwd(a, st["down"], y, stride=2)
        for blk in st["blocks"]:
            y = y + _msa_fwd(a, blk["msa"], y, cfg["model"]["head_dim"])
            y = y + _mbconv_fwd(a, blk["mbconv"], y)
    head = params["head"]
    y = jnp.mean(_conv_bn_act(a, head["conv"], y), axis=(1, 2))
    fc = a.qfc if a.bits is not None else (
        lambda h, w: a.einsum("bc,cf->bf", h, w))
    y = jax.nn.hard_swish(fc(y, head["fc1"]["w"]))
    return fc(y, head["fc2"]["w"])


# ---------------------------------------------------------------------------
# work count
# ---------------------------------------------------------------------------

def macs_per_image(cfg: dict) -> int:
    """Multiply-accumulates of one image: convolutions, attention
    products and fc layers (elementwise work is not counted)."""
    m = cfg["model"]
    w, d, e = m["widths"], m["depths"], m["expand_ratio"]
    hd, scales = m["head_dim"], m["msa_scales"]
    r = cfg["image_size"] // 2
    macs = r * r * w[0] * 3 * 9                                  # stem conv
    macs += d[0] * (r * r * w[0] * 9 + r * r * w[0] * w[0])       # DSConvs

    def mbconv(r_in, r_out, c_in, c_out):
        mid = c_in * e
        return (r_in * r_in * c_in * mid + r_out * r_out * mid * 9
                + r_out * r_out * mid * c_out)

    for si in (1, 2):
        c_in = w[si - 1]
        for bi in range(d[si]):
            r_out = r // 2 if bi == 0 else r
            macs += mbconv(r, r_out, c_in, w[si])
            r, c_in = r_out, w[si]
    for si in (3, 4):
        c = w[si]
        macs += mbconv(r, r // 2, w[si - 1], c)
        r //= 2
        heads = c // hd
        total, n_br, tok = heads * hd, 1 + len(scales), r * r
        for _ in range(d[si]):
            macs += tok * c * 3 * total                          # qkv
            for s in scales:
                macs += tok * 3 * total * s * s                  # agg dw
                macs += tok * 3 * total * hd                     # agg pw
            macs += n_br * heads * hd * tok * hd                 # K^T V
            macs += n_br * heads * tok * hd * (hd + 1)           # Q [KV|ksum]
            macs += tok * n_br * total * c                       # proj
            macs += mbconv(r, r, c, c)
    hw1, hw2 = m["head_widths"]
    macs += r * r * w[4] * hw1 + hw1 * hw2 + hw2 * m["num_classes"]
    return macs
