"""Jitted wrapper + registry impl for the grouped int8 aggregation kernel.

``group_agg_apply_int8(agg_params, x)`` consumes one entry of an MSA
module's quantized ``aggreg`` list ({'dw','pw'} each holding a ``qconv``
from ``core.quantization.quantize_efficientvit``) and runs the fused
Pallas branch kernel — the FIX8 MSA module
(``kernels.relu_attn.ops.msa_fused_apply``) calls it instead of falling
back to the reference ``conv2d_int8``: one aggregation launch per scale
next to the single attention core.

This package is also the registry's worked "new kind" example
(``("group_agg", "int8")``, an int8-only registration): a custom IR
that emits ``Site(kind="group_agg")`` nodes plans and executes it with
no planner/executor changes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.quantization import QTensor, quantize_act
from repro.kernels.group_conv.kernel import group_agg_int8, group_agg_int8_ref
from repro.kernels.compat import VMEM_BUDGET_BYTES
from repro.kernels.registry import KernelBase, register


def _block_diag(pw_q):
    """Grouped-1x1 HWIO weights (1, 1, d, C) -> dense (C, C) int8
    block-diagonal: column (= output channel) ``oc`` keeps its group's
    ``d`` input rows, everything off-block is zero (exact for int32
    accumulation)."""
    d, C = pw_q.shape[2], pw_q.shape[3]
    w = pw_q[0, 0]                                   # (d, C)
    col = jnp.arange(C)
    row_idx = (col // d)[None, :] * d + jnp.arange(d)[:, None]   # (d, C)
    return jnp.zeros((C, C), jnp.int8).at[row_idx, col[None, :]].set(w)


def group_agg_vmem_bytes(h: int, w: int, c: int, s: int) -> int:
    """Analytic per-grid-step VMEM: padded int8 input block, int32
    depthwise accumulator, int8 requantized intermediate, fp32 output
    block, and the dense block-diagonal weights."""
    p = s // 2
    return ((h + 2 * p) * (w + 2 * p) * c      # int8 input block
            + 4 * h * w * c                    # int32 DW accumulator
            + h * w * c                        # int8 requant intermediate
            + 4 * h * w * c                    # fp32 output block
            + 2 * c * c)                       # int8 weights + slack


@functools.partial(jax.jit, static_argnames=("interpret",))
def _group_agg_op(x_q, x_scale, dw_q, dw_s, dw_b, pw_dense, pw_s, pw_b, *,
                  interpret: bool | None = None):
    B, H, W, C = x_q.shape
    s = dw_q.shape[0]
    if group_agg_vmem_bytes(H, W, C, s) > VMEM_BUDGET_BYTES:
        return group_agg_int8_ref(x_q, x_scale, dw_q, dw_s, dw_b, pw_dense,
                                  pw_s, pw_b)
    return group_agg_int8(x_q, x_scale, dw_q, dw_s, dw_b, pw_dense, pw_s,
                          pw_b, interpret=interpret)


def group_agg_apply_int8(agg_params, x, *, interpret: bool | None = None):
    """One quantized MSA aggregation branch ({'dw','pw'} ``qconv`` pair)
    -> fused Pallas launch.  ``x`` is the fp QKV tensor (quantized here
    per batch element) or an int8 ``QTensor``; returns (B, H, W, C)
    fp32 — bit-identical to the reference ``conv2d_int8`` chain at
    batch 1."""
    qd = agg_params["dw"]["qconv"]
    qp = agg_params["pw"]["qconv"]
    dw_q = qd["q"][:, :, 0, :]            # (s,s,1,C) -> (s,s,C)
    dense = _block_diag(qp["q"])
    if isinstance(x, QTensor):
        x_q, x_scale = x.q, x.scale
    else:
        qt = quantize_act(x)
        x_q, x_scale = qt.q, qt.scale
    return _group_agg_op(x_q, x_scale, dw_q, qd["scale"], qd["bias"],
                         dense, qp["scale"], qp["bias"],
                         interpret=interpret)


@register
class GroupAggInt8Kernel(KernelBase):
    """(group_agg, int8): the registry face of the aggregation kernel —
    an int8-only kind (``get_probe`` resolves it without an fp twin)."""
    kind, precision, dtype = "group_agg", "int8", "i8"
    vmem_budget = VMEM_BUDGET_BYTES
    takes_q = True

    def site_precision(self, params):
        return ("int8" if "qconv" in params.get("dw", {})
                and "qconv" in params.get("pw", {}) else "fp")

    def vmem_bytes(self, site, dtype=None):
        _, H, W, C = site.in_shape
        return group_agg_vmem_bytes(H, W, C, site.attrs.get("scale", 5))

    def apply(self, params, x, site, decision=None, *, interpret=None,
              epilogue=None):
        return group_agg_apply_int8(params, x, interpret=interpret)

    def ref(self, params, x, site, **kw):
        from repro.core.quantization import conv2d_int8
        C = x.shape[-1]
        groups_pw = C // params["pw"]["qconv"]["q"].shape[2]
        y = conv2d_int8(params["dw"]["qconv"], x, groups=C)
        return conv2d_int8(params["pw"]["qconv"], y, groups=groups_pw)
