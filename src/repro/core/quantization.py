"""FIX8 (int8) post-training quantization — the paper's arithmetic.

The accelerator computes 8x8-bit fixed-point multiplies (two per DSP via
WP486 packing).  The TPU analogue is the MXU's native int8 path (int8 x
int8 -> int32 accumulate), giving the same ~2x-over-bf16 economics.

Scheme, matching the paper + [18]:
  * BN folded into the preceding conv first ("BN can be implemented via
    1x1 convolutions, integrated into preceding convolutions", paper §II)
  * weights: symmetric per-output-channel int8
  * activations: symmetric per-tensor int8, dynamic (absmax) or calibrated
  * accumulation: int32, dequantized by (s_act * s_w) per channel

`quantize_efficientvit` rewrites an EfficientViT param tree in place-form:
every conv+BN pair becomes a folded+quantized `qconv`, and the shared
forward (`core.efficientvit.conv_bn_act`) dispatches on its presence, so
the fp32 and FIX8 networks share one code path.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.layers.norms import bn_fold_scale_bias


class QTensor(NamedTuple):
    """A quantized activation crossing a producer->consumer site boundary.

    The carrier of the int8 dataflow (``core.program.Epilogue``): the
    producer's epilogue emits ``q`` (int8) with its per-batch-element
    symmetric ``scale`` so the consumer kernel never re-reads the fp32
    activation from HBM to quantize it.  ``fp`` is the fp activation and
    is only populated when the epilogue's residual-policy demands it
    (the consumer's residual add must run in full precision, or the
    producer's own residual add already produced it).
    """
    q: jax.Array                      # int8, same shape as the activation
    scale: jax.Array                  # fp32 () or (B,) per-batch scales
    fp: Optional[jax.Array] = None    # fp activation (residual policy)

    @property
    def shape(self):
        return self.q.shape

    def scale_col(self):
        """Scale broadcastable against the leading batch axis: (B, 1...)."""
        s = jnp.asarray(self.scale, jnp.float32).reshape(-1)
        return jnp.broadcast_to(s, (self.q.shape[0],))


def act_fp(y):
    """The fp view of an activation: QTensor -> its kept fp tensor."""
    if isinstance(y, QTensor):
        if y.fp is None:
            raise ValueError(
                "QTensor without a kept fp activation reached a consumer "
                "that needs full precision — epilogue assignment bug")
        return y.fp
    return y


def quantize_act(x, *, keep_fp: bool = False, bits: int = 8) -> QTensor:
    """Producer-side activation quantization: per-batch-element symmetric
    absmax (identical to ``quantize_tensor``'s per-tensor scheme at
    batch 1, which is what keeps the fused int8 chain bit-exact vs the
    reference there).  ``keep_fp`` carries the fp tensor alongside for a
    downstream residual add."""
    qmax = 2 ** (bits - 1) - 1
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=tuple(range(1, x.ndim)))
    scale = jnp.maximum(absmax, 1e-8) / qmax          # (B,)
    col = scale.reshape((-1,) + (1,) * (x.ndim - 1))
    q = jnp.clip(jnp.round(xf / col), -qmax - 1, qmax).astype(jnp.int8)
    return QTensor(q, scale, x if keep_fp else None)


def quantize_tensor(x, axis=None, bits: int = 8, keepdims: bool = False):
    """Symmetric quantization.  axis=None -> per-tensor scale (a scalar,
    or size-1 dims of ``x``'s rank with ``keepdims``)."""
    qmax = 2 ** (bits - 1) - 1
    xf = x.astype(jnp.float32)
    if axis is None:
        absmax = jnp.max(jnp.abs(xf), keepdims=keepdims)
    else:
        red = tuple(i for i in range(x.ndim) if i != axis % x.ndim)
        absmax = jnp.max(jnp.abs(xf), axis=red, keepdims=True)
    scale = jnp.maximum(absmax, 1e-8) / qmax
    q = jnp.clip(jnp.round(xf / scale), -qmax - 1, qmax).astype(jnp.int8)
    return q, scale


def quantize_with_scale(x, scale, bits: int = 8):
    """Symmetric quantization against a precomputed (calibrated) scale.

    Skips the absmax reduction ``quantize_tensor`` runs on every call —
    the serving-time fast path for static activation ranges.
    """
    qmax = 2 ** (bits - 1) - 1
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -qmax - 1, qmax)
    return q.astype(jnp.int8)


def calibrate_act_scale(samples, bits: int = 8):
    """Static per-tensor activation scale from calibration batches.

    ``samples``: an array or an iterable of arrays of representative
    activations.  Returns the symmetric scale covering their joint
    absmax, for use as ``x_scale`` in ``kernels.int8_matmul.ops.
    linear_w8a8`` (and anywhere else a static range beats a per-call
    reduction).
    """
    qmax = 2 ** (bits - 1) - 1
    if hasattr(samples, "ndim"):
        samples = [samples]
    absmax = jnp.zeros((), jnp.float32)
    for s in samples:
        absmax = jnp.maximum(absmax,
                             jnp.max(jnp.abs(s.astype(jnp.float32))))
    return jnp.maximum(absmax, 1e-8) / qmax


def dequantize(q, scale):
    return q.astype(jnp.float32) * scale


def fold_bn_into_conv(conv_p, bn_p, eps: float = 1e-5):
    """(conv, BN) -> folded (w', b') with BN absorbed per output channel."""
    gamma, beta = bn_fold_scale_bias(bn_p, eps)
    w = conv_p["w"].astype(jnp.float32) * gamma[None, None, None, :]
    b = conv_p.get("b")
    b = beta if b is None else beta + b.astype(jnp.float32) * gamma
    return w, b


def quantize_conv_bn(p, eps: float = 1e-5):
    """{'conv','bn'} block -> {'qconv': {q, scale, bias, groups-compatible}}."""
    w, b = fold_bn_into_conv(p["conv"], p["bn"], eps)
    q, scale = quantize_tensor(w, axis=-1)  # per-output-channel (HWIO)
    return {"qconv": {"q": q, "scale": scale[0, 0, 0, :], "bias": b}}


def conv2d_int8(qp, x, *, stride: int = 1, groups: int = 1, padding="SAME"):
    """FIX8 conv: dynamic per-batch-element act quant, int8 conv, int32
    accumulate, fp32 dequant + bias.  Mirrors layers.conv.conv2d
    semantics.

    The dynamic activation scale is per batch element (``quantize_act``'s
    scheme — identical to the old per-tensor scale at batch 1, where the
    bit-exactness gates run): one request's numerics never depend on its
    batch-mates, so bucketed batch formation and batch-axis sharding
    (``serving.sharding``) are bit-transparent to results.

    ``x`` may be a ``QTensor`` emitted by the producer's epilogue — the
    activation quantization is then skipped entirely (its per-batch
    scales broadcast through the dequant), which is the int8-dataflow
    route for structural quantized convs (e.g. ``head.conv``)."""
    if isinstance(x, QTensor):
        xq = x.q
        sx = x.scale_col().reshape(-1, 1, 1, 1)
        out_dtype = x.fp.dtype if x.fp is not None else jnp.float32
    else:
        qt = quantize_act(x)
        xq, sx = qt.q, qt.scale.reshape(-1, 1, 1, 1)
        out_dtype = x.dtype
    acc = lax.conv_general_dilated(
        xq, qp["q"],
        window_strides=(stride, stride), padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups,
        preferred_element_type=jnp.int32,
    )
    y = acc.astype(jnp.float32) * (sx * qp["scale"][None, None, None, :])
    return (y + qp["bias"][None, None, None, :]).astype(out_dtype)


def matmul_int8(x, qw, w_scale):
    """(..., d) x int8 (d, f): int8 GEMM with int32 accumulation.

    Dynamic activation scale per leading (batch) element, like
    ``quantize_act`` — batch-composition-invariant, so sharded and
    bucketed serving deliver bit-identical logits per request."""
    qmax = 127
    xf = x.astype(jnp.float32)
    if x.ndim <= 1:
        absmax = jnp.max(jnp.abs(xf))
    else:
        absmax = jnp.max(jnp.abs(xf), axis=tuple(range(1, x.ndim)),
                         keepdims=True)
    sx = jnp.maximum(absmax, 1e-8) / qmax
    xq = jnp.clip(jnp.round(xf / sx), -qmax - 1, qmax).astype(jnp.int8)
    acc = jnp.einsum("...d,df->...f", xq, qw,
                     preferred_element_type=jnp.int32)
    return (acc.astype(jnp.float32) * (sx * w_scale)).astype(x.dtype)


def quantize_linear(p):
    q, scale = quantize_tensor(p["w"], axis=-1)
    out = {"qw": q, "scale": scale[0, :]}
    if "b" in p:
        out["bias"] = p["b"].astype(jnp.float32)
    return out


# ---------------------------------------------------------------------------
# EfficientViT end-to-end quantization
# ---------------------------------------------------------------------------

def _is_conv_bn(node) -> bool:
    return isinstance(node, dict) and set(node) == {"conv", "bn"}


# param-tree blocks FIX8 has no int8 path for: their keys -> block kind
_NO_INT8 = {frozenset({"conv1", "conv2"}): "resblock",
            frozenset({"spatial", "point"}): "fmbconv"}


def quantize_efficientvit(params, cfg=None):
    """Recursively fold+quantize every conv+BN block of an EfficientViT
    param tree; bare convs (MSA qkv/aggreg/proj) get weight+act int8 too.

    FIX8 covers the B series only.  A tree holding an L-series block
    (ResBlock, FusedMBConv, a LayerNorm head) — or a ``cfg`` whose
    activation is not Hardswish — raises ``ValueError`` naming it rather
    than quantizing part of the tree."""
    if cfg is not None and (cfg.act != "hswish"
                            or cfg.head_norm != "none"):
        raise ValueError(
            f"quantize_efficientvit: FIX8 runs Hardswish networks with a "
            f"plain head; {cfg.name} has act={cfg.act!r}, "
            f"head_norm={cfg.head_norm!r}")

    found: dict[str, str] = {}          # block kind -> first path

    def check(node, path="params"):
        if isinstance(node, dict):
            kind = _NO_INT8.get(frozenset(node)) or (
                "layernorm head" if "ln" in node else None)
            if kind is not None:
                found.setdefault(kind, path)
            for k, v in node.items():
                check(v, f"{path}.{k}")
        elif isinstance(node, list):
            for i, v in enumerate(node):
                check(v, f"{path}[{i}]")

    def walk(node):
        if _is_conv_bn(node):
            return quantize_conv_bn(node)
        if isinstance(node, dict):
            if "proj" in node and "proj_bn" in node:  # MSA tail: fold BN
                out = {k: walk(v) for k, v in node.items()
                       if k not in ("proj", "proj_bn")}
                out["proj"] = quantize_conv_bn(
                    {"conv": node["proj"], "bn": node["proj_bn"]})
                return out
            if set(node) == {"w"} and node["w"].ndim == 4:  # bare conv
                q, scale = quantize_tensor(node["w"], axis=-1)
                return {"qconv": {"q": q, "scale": scale[0, 0, 0, :],
                                  "bias": jnp.zeros(node["w"].shape[-1])}}
            if set(node) == {"w"} and node["w"].ndim == 2:  # fc
                return quantize_linear(node)
            return {k: walk(v) for k, v in node.items()}
        if isinstance(node, list):
            return [walk(v) for v in node]
        return node

    check(params)
    if found:
        raise ValueError("quantize_efficientvit: FIX8 has no int8 path for "
                         + ", ".join(f"the {k!r} block at {p}"
                                     for k, p in sorted(found.items())))
    return walk(params)


def quantization_error(x_fp, x_q):
    """Relative L2 error — the acceptance metric for FIX8 parity tests."""
    num = jnp.linalg.norm((x_fp - x_q).astype(jnp.float32).ravel())
    den = jnp.maximum(jnp.linalg.norm(x_fp.astype(jnp.float32).ravel()), 1e-9)
    return num / den


# ---------------------------------------------------------------------------
# LM weight-only int8 (W8) — the FIX8 datapath as a serving feature
# ---------------------------------------------------------------------------

_W8_SKIP = ("norm", "ln1", "ln2", "ln3", "final_norm", "enc_norm", "router",
            "conv_w", "conv_b", "A_log", "dt_bias", "D", "proj_bn", "bn")


def _q_per_out_channel(w):
    """int8 per-(stack..., out-channel): scale reduces the in dim only,
    so scan-stacked weights (L, in, out) / (L, E, D, F) quantize
    per-layer-per-channel and slice correctly inside the layer scan."""
    wf = w.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(wf), axis=-2, keepdims=True)
    scale = jnp.maximum(absmax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(wf / scale), -128, 127).astype(jnp.int8)
    return q, scale


def quantize_lm_params(params):
    """Weight-only int8 transform of an LM param tree.

    Matmul weights ({'w': (..., in, out)}) become {'qw' int8, 'scale'
    (..., 1, out)}; embedding tables become {'qt' int8, 'scale' (V, 1)};
    MoE expert tensors (stacked or not) become {'q' int8, 'scale'}.
    Norms, biases, routers and SSM scalars stay fp.  ``layers.linear`` /
    ``layers.moe`` dequantize on use, so the HBM-resident (and
    ZeRO-gathered) bytes drop ~2x — the lever for weight-read/-gather-
    bound decode (EXPERIMENTS.md §Perf H3b).
    """

    def walk(node, path=""):
        if isinstance(node, dict):
            if any(s in path.rsplit("/", 1)[-1] for s in _W8_SKIP):
                return node
            if "table" in node and node["table"].ndim == 2:
                q, scale = quantize_tensor(node["table"], axis=0)
                return {"qt": q, "scale": scale.astype(jnp.float32)}
            if "w" in node and node["w"].ndim >= 2 \
                    and not any(s in path for s in _W8_SKIP):
                q, scale = _q_per_out_channel(node["w"])
                out = {"qw": q, "scale": scale}
                if "b" in node:
                    out["b"] = node["b"]
                return out
            return {k: walk(v, f"{path}/{k}") for k, v in node.items()}
        if hasattr(node, "ndim") and node.ndim >= 3 and \
                path.rsplit("/", 1)[-1] in ("w_in", "w_gate", "w_out"):
            q, scale = _q_per_out_channel(node)
            return {"q": q, "scale": scale}
        return node

    return walk(params)
