"""Jitted public wrappers for the fused ReLU linear attention kernels.

Accepts the framework's multi-head layouts, folds (batch, heads) into one
grid axis, pads ragged token counts to the tile boundary, and dispatches
to the Pallas kernels (interpret=True on CPU; compiled on TPU).

``msa_batched_attention`` additionally folds the MSA module's multi-scale
*branches* into the same grid axis, so one EfficientViT module issues ONE
attention launch instead of a Python loop of ``1 + len(scales)`` calls
(each of which used to be two launches before the single-pass rewrite).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.autotune import autotune, shape_key
from repro.kernels.compat import default_interpret
from repro.kernels.registry import KernelBase, register
from repro.kernels.relu_attn.kernel import relu_attn_causal, relu_attn_noncausal

BLOCK_N_CANDIDATES = ({"block_n": 256}, {"block_n": 128}, {"block_n": 64},
                      {"block_n": 512})
MSA_DEFAULT_BLOCK_N = 256   # token tile when no plan/autotune choice exists


def _fold_heads(x):
    """(B, N, H, D) -> (B*H, N, D)"""
    B, N, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, N, D)


def _unfold_heads(x, B, H):
    BH, N, D = x.shape
    return x.reshape(B, H, N, D).transpose(0, 2, 1, 3)


def tune_block_n(bh: int, n: int, d: int, *, allow_sweep: bool = True,
                 interpret: bool | None = None) -> int:
    """Autotuned token tile for a (BH, N, D) attention shape (disk-cached).

    The cache key carries the folded grid batch ``bh`` (branches x image
    batch x heads) and the token count ``n`` (= H*W) explicitly, so two
    serving buckets differing only in batch or resolution tune and cache
    independently; the backend tag keeps interpreter timings away from
    compiled runs.  The attention core always accumulates fp32, hence
    the fixed dtype tag.
    """
    interpret = default_interpret(interpret)
    backend = "interp" if interpret else "compiled"
    key = shape_key(batch=bh, spatial=(n,), d=d, dtype="f32",
                    backend=backend)

    def bench(cand):
        z = jnp.zeros((bh, n, d), jnp.float32)
        return relu_attn_noncausal(z, z, z, block_n=cand["block_n"],
                                   interpret=interpret)

    choice = autotune("relu_attn", key, BLOCK_N_CANDIDATES,
                      bench if allow_sweep else None,
                      interpret=interpret)
    return choice["block_n"]


@functools.partial(jax.jit, static_argnames=("causal", "block_n", "interpret"))
def relu_linear_attention(q, k, v, *, causal: bool = False,
                          block_n: int = 256, interpret: bool | None = None):
    """Fused ReLU linear attention.  q, k, v: (B, N, H, D).

    Returns (B, N, H, D) in fp32.  The non-causal form is EfficientViT's
    MSA core; the causal form is the LM backend.
    """
    B, N, H, D = q.shape
    qf, kf, vf = _fold_heads(q), _fold_heads(k), _fold_heads(v)
    if causal:
        out = relu_attn_causal(qf, kf, vf, chunk=block_n, interpret=interpret)
    else:
        out = relu_attn_noncausal(qf, kf, vf, block_n=block_n,
                                  interpret=interpret)
    return _unfold_heads(out, B, H)


def msa_attention_fn(q, k, v):
    """Drop-in ``attention_fn`` for core.relu_attention.msa (B, N, h, d)."""
    return relu_linear_attention(q, k, v, causal=False).astype(q.dtype)


def msa_batched_attention(qkv, n_heads: int, head_dim: int, *,
                          block_n: int = 256, interpret: bool | None = None):
    """All MSA branches + heads in one launch.

    qkv: (S, B, N, 3 * n_heads * head_dim) — the S multi-scale aggregation
    branches stacked.  Returns (S, B, N, n_heads * head_dim) fp32.  The
    (scale, batch, head) axes fold into the kernel's single parallel grid
    axis, so the whole module is one ``pallas_call``.
    """
    S, B, N, _ = qkv.shape
    t = qkv.reshape(S * B, N, 3, n_heads, head_dim)
    q, k, v = t[:, :, 0], t[:, :, 1], t[:, :, 2]
    out = relu_linear_attention(q, k, v, causal=False, block_n=block_n,
                                interpret=interpret)
    return out.reshape(S, B, N, n_heads * head_dim)


# ---------------------------------------------------------------------------
# fused MSA module (registry impl for core.program / core.fusion)
# ---------------------------------------------------------------------------

def msa_fused_apply(params, x, n_heads: int, head_dim: int, *,
                    block_n: int = MSA_DEFAULT_BLOCK_N,
                    interpret: bool | None = None,
                    int8_proj: bool = False, epilogue=None):
    """One EfficientViT MSA module, attention core fused to ONE launch.

    params: the module's {'qkv','aggreg','proj','proj_bn'} tree (fp32 or
    ``quantize_efficientvit`` qconv subtrees).  ``int8_proj`` routes the
    QKV/output projections through the Pallas W8A8 GEMM — only honored
    when both projections are actually quantized, so a mixed tree keeps
    its projections on the reference conv path.

    The int8 dataflow runs through here at FIX8: ``x`` may be a
    producer-emitted ``QTensor`` (consumed directly by the QKV GEMM),
    the multi-scale aggregation branches run the grouped int8 Pallas
    kernel (one launch per scale — no more reference ``conv2d_int8``
    fallback), and an int8 ``epilogue`` makes the output projection
    GEMM emit the quantized module output itself.
    """
    from repro.core.quantization import QTensor, act_fp, quantize_act
    from repro.core.relu_attention import _conv_any
    from repro.layers.conv import pwconv
    from repro.layers.norms import batchnorm

    qt = isinstance(x, QTensor)
    B, H, W, _ = (x.q if qt else x).shape
    dtype = (x.fp.dtype if qt and x.fp is not None
             else jnp.float32 if qt else x.dtype)
    int8 = (int8_proj and "qconv" in params["qkv"]
            and "qconv" in params["proj"])
    if int8:
        from repro.kernels.int8_matmul.ops import conv1x1_w8a8
        qkv = conv1x1_w8a8(params["qkv"]["qconv"], x, interpret=interpret)
    else:
        qkv = _conv_any(params["qkv"],
                        act_fp(x) if qt else x)        # (B,H,W,3*total)
    agg_int8 = int8 and all("qconv" in a["dw"] and "qconv" in a["pw"]
                            for a in params["aggreg"])
    multi = [qkv]
    if agg_int8 and params["aggreg"]:
        from repro.kernels.group_conv.ops import group_agg_apply_int8
        qkv_qt = quantize_act(qkv)         # ONE quantize feeds every scale
        for agg in params["aggreg"]:
            multi.append(group_agg_apply_int8(agg, qkv_qt,
                                              interpret=interpret))
    else:
        for agg in params["aggreg"]:
            a = _conv_any(agg["dw"], qkv, groups=qkv.shape[-1])
            multi.append(_conv_any(agg["pw"], a, groups=3 * n_heads))
    stack = jnp.stack(multi)                          # (S,B,H,W,3*total)
    S = stack.shape[0]
    total = n_heads * head_dim
    o = msa_batched_attention(
        stack.reshape(S, B, H * W, 3 * total), n_heads, head_dim,
        block_n=block_n, interpret=interpret)         # one launch
    out = jnp.moveaxis(o.reshape(S, B, H, W, total), 0, -2)
    out = out.reshape(B, H, W, S * total).astype(dtype)
    if int8:
        return conv1x1_w8a8(params["proj"]["qconv"], out,
                            interpret=interpret, epilogue=epilogue)
    if "qconv" in params["proj"]:
        return _conv_any(params["proj"], out)  # BN folded by quantization
    return batchnorm(params["proj_bn"], pwconv(params["proj"], out))


@register
class MsaKernel(KernelBase):
    """(msa, fp): whole-module fusion — all branches and heads fold into
    one attention launch; projections stay on the reference conv path."""
    kind, precision, dtype = "msa", "fp", "f32"
    int8_proj = False

    def site_precision(self, params):
        # Both projections must be quantized for the W8A8 route; the
        # attention core itself is precision-agnostic (fp accumulation).
        return ("int8" if "qconv" in params["qkv"]
                and "qconv" in params["proj"] else "fp")

    def resolve_precision(self, site_prec, requested):
        # Never a fallback: a precision mismatch just keeps the
        # projections on the reference path (precision "fp") while the
        # attention core fuses either way.
        if requested in ("auto", site_prec):
            return site_prec, None
        return "fp", None

    def tune(self, site, *, autotune=True, interpret=None):
        B, H, W, _ = site.in_shape
        bh = site.attrs["n_branches"] * B * site.attrs["heads"]
        bn = tune_block_n(bh, H * W, site.attrs["head_dim"],
                          allow_sweep=autotune, interpret=interpret)
        return {"block_n": bn}

    def candidates(self, site):
        return BLOCK_N_CANDIDATES

    def block_work(self, site, blocks):
        from repro.kernels.autotune import tile_work
        _, H, W, _ = site.in_shape
        return tile_work(H * W, blocks["block_n"])

    def apply(self, params, x, site, decision=None, *, interpret=None,
              epilogue=None):
        blocks = decision.blocks if decision is not None else {}
        return msa_fused_apply(params, x, site.attrs["heads"],
                               site.attrs["head_dim"],
                               block_n=blocks.get("block_n",
                                                  MSA_DEFAULT_BLOCK_N),
                               interpret=interpret,
                               int8_proj=self.int8_proj,
                               epilogue=epilogue)

    def ref(self, params, x, site, *, epilogue=None, **kw):
        from repro.core.quantization import quantize_act
        from repro.core.relu_attention import MSAConfig, msa
        mcfg = MSAConfig(x.shape[-1], site.attrs["head_dim"],
                         site.attrs["scales"])
        out = msa(params, x, mcfg)
        if epilogue is not None and epilogue.emits_q:
            return quantize_act(out, keep_fp=epilogue.residual == "keep-fp")
        return out
