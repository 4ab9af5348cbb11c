"""Jitted wrappers + registry impls for the super-site chain kernels.

``supersite_apply(params, x, supersite, ...)`` runs an fp chain banded
over output rows; ``supersite_apply_int8`` runs the FIX8 chain whole-map
per batch element.  Both pack the members' weights into one resident
block (``pack.pack_weights``) and hand the kernels a static
``SupersiteGeom`` so jit caches one program per chain shape.

The planner-facing half (``supersite_vmem_bytes`` /
``supersite_vmem_bytes_int8`` / ``choose_block_rows``) is pure host
arithmetic over ``Site`` shapes: ``core.fusion.plan_program``'s grouping
pass calls it to decide, before any params exist, whether a candidate
chain fits the per-launch VMEM budget — fp by shrinking the band height
until it fits, int8 by a whole-map check (spatial tiling would break the
per-batch-element requant numerics, so int8 chains that don't fit
whole simply stay ungrouped).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.quantization import QTensor, act_fp, quantize_act
from repro.kernels.compat import VMEM_BUDGET_BYTES
from repro.kernels.registry import KernelBase, register
from repro.kernels.supersite.kernel import (
    MemberGeom, SupersiteGeom, band_geometry, supersite_fused,
    supersite_fused_int8)
from repro.kernels.supersite.pack import pack_weights

# fp band heights, largest first — choose_block_rows picks the first
# fit, and the offline search (repro.search) sweeps them per group
BLOCK_ROWS_CANDIDATES = (
    {"block_rows": 64}, {"block_rows": 32}, {"block_rows": 16},
    {"block_rows": 8}, {"block_rows": 4})


def _member_specs(supersite, fp_offsets=None, q_offsets=None):
    """Base ``MemberGeom`` per member (windows unfilled)."""
    k = len(supersite.sites)
    fp_offsets = fp_offsets or ((),) * k
    q_offsets = q_offsets or ((),) * k
    out = []
    for site, fo, qo in zip(supersite.sites, fp_offsets, q_offsets):
        _, h, w, c = site.in_shape
        out.append(MemberGeom(site.kind, site.stride, site.residual,
                              h, w, c, site.attrs.get("mid", 0),
                              site.out_shape[-1], fp_offs=fo, q_offs=qo,
                              act=site.act or "hswish"))
    return tuple(out)


def make_fp_geom(supersite, pack, block_rows: int) -> SupersiteGeom:
    _, ho, wo, f = supersite.out_shape
    n_bands, members = band_geometry(
        _member_specs(supersite, pack.fp_offsets, pack.q_offsets),
        block_rows, ho)
    return SupersiteGeom(members, ho, wo, f, block_rows, n_bands)


def make_int8_geom(supersite, pack) -> SupersiteGeom:
    _, ho, wo, f = supersite.out_shape
    return SupersiteGeom(
        _member_specs(supersite, pack.fp_offsets, pack.q_offsets),
        ho, wo, f)


# ---------------------------------------------------------------------------
# analytic VMEM models (planner-facing, no params required)
# ---------------------------------------------------------------------------

def _weight_counts(supersite):
    """(fp32 scalars, int8 scalars) of the chain's resident pack."""
    n_fp = n_q = 0
    for s in supersite.sites:
        c, f = s.in_shape[-1], s.out_shape[-1]
        if s.kind == "mbconv":
            m = s.attrs["mid"]
            n_q += c * m + 9 * m + m * f
            n_fp += 4 * m + 2 * f                # s1,b1,dws,dwb + s2,b2
        else:
            n_q += 9 * c + c * f
            n_fp += 2 * c + 2 * f
    return n_fp, n_q


def fp_weight_bytes(supersite) -> int:
    """fp pack bytes: every weight AND scale/bias slot at fp32."""
    n_fp, n_q = _weight_counts(supersite)
    return 4 * (n_fp + n_q)


def supersite_vmem_bytes(supersite, block_rows: int) -> int:
    """fp banded chain, per grid step: input slab + each member's
    col-padded intermediate + band output, plus the resident pack."""
    _, ho, _, _ = supersite.out_shape
    _, members = band_geometry(_member_specs(supersite), block_rows, ho)
    m0 = members[0]
    total = m0.length * m0.w_in * m0.c_in        # input slab
    for m in members:
        wo = m.w_in // m.stride
        if m.kind == "mbconv":
            total += m.length * (m.w_in + 2) * m.mid \
                + m.n_out * wo * m.mid + m.n_out * wo * m.f_out
        else:
            total += m.length * (m.w_in + 2) * m.c_in \
                + m.n_out * wo * m.c_in + m.n_out * wo * m.f_out
    return 4 * total + fp_weight_bytes(supersite)


def supersite_vmem_bytes_int8(supersite, *, keep_fp: bool = False) -> int:
    """FIX8 whole-map chain, per grid step (one batch element): int8
    buffers per member plus the emit epilogue's fp32/int8 output blocks
    (the same convention as the per-site emit kernels' fit check) and
    the resident pack."""
    total = 0
    for s in supersite.sites:
        _, h, w, c = s.in_shape
        ho, wo = h // s.stride, w // s.stride
        if s.kind == "mbconv":
            m = s.attrs["mid"]
            total += h * w * c + (h + 2) * (w + 2) * m + ho * wo * m
        else:
            total += (h + 2) * (w + 2) * c + ho * wo * c
    _, ho, wo, f = supersite.out_shape
    total += ho * wo * f * (5 + (4 if keep_fp else 0))
    n_fp, n_q = _weight_counts(supersite)
    return total + 4 * n_fp + n_q


def choose_block_rows(supersite,
                      budget: int = VMEM_BUDGET_BYTES) -> int | None:
    """Largest band height that fits the budget (None: nothing fits).

    Deterministic and analytic — no device sweep — so plans, search
    artifacts and the drift gates agree on the same choice everywhere.
    """
    _, ho, _, _ = supersite.out_shape
    rows = [c["block_rows"] for c in BLOCK_ROWS_CANDIDATES if
            c["block_rows"] <= ho]
    if ho not in rows:
        rows.append(ho)
    for r in sorted(rows, reverse=True):
        if supersite_vmem_bytes(supersite, r) <= budget:
            return r
    return None


# ---------------------------------------------------------------------------
# jitted ops + apply wrappers
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("geom", "interpret"))
def supersite_op(x, w_flat, *, geom, interpret=None):
    return supersite_fused(x, w_flat, geom=geom, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("geom", "exit_emit",
                                             "keep_fp", "interpret"))
def supersite_op_int8(x_q, x_scale, wq_flat, wf_flat, x_fp=None, *,
                      geom, exit_emit=False, keep_fp=False,
                      interpret=None):
    return supersite_fused_int8(x_q, x_scale, wq_flat, wf_flat, geom=geom,
                                x_fp=x_fp, exit_emit=exit_emit,
                                keep_fp=keep_fp, interpret=interpret)


def supersite_apply(params, x, supersite, blocks=None, *,
                    interpret=None, epilogue=None):
    """fp chain.  ``params`` is the ROOT param tree (members resolve
    their own subtrees via ``Site.param_path``).  ``epilogue`` is
    accepted for interface parity and ignored, mirroring the per-site
    fp impls (fp producers never emit int8 in-kernel)."""
    x = act_fp(x)
    pack = pack_weights(params, supersite, "fp")
    rows = (blocks or {}).get("block_rows") or choose_block_rows(supersite)
    if rows is None:
        raise ValueError(f"super-site {supersite.name} fits no band "
                         f"height; the planner should not have grouped it")
    out = supersite_op(x, pack.fp, geom=make_fp_geom(supersite, pack, rows),
                       interpret=interpret)
    return out.astype(x.dtype)


def supersite_apply_int8(params, x, supersite, *, interpret=None,
                         epilogue=None):
    """FIX8 chain.  ``x`` is a producer-emitted ``QTensor`` or an fp
    activation (entry-quantized here per batch element, same as the
    per-site consumers).  The exit follows the last member's epilogue:
    int8 emission returns a ``QTensor`` (fp alongside when the residual
    policy keeps it); otherwise the fp32 output."""
    pack = pack_weights(params, supersite, "int8")
    geom = make_int8_geom(supersite, pack)
    first_residual = supersite.sites[0].residual
    if isinstance(x, QTensor):
        x_q, x_scale, x_fp = x.q, x.scale, x.fp
        out_dtype = x.fp.dtype if x.fp is not None else jnp.float32
    else:
        qt = quantize_act(x, keep_fp=first_residual)
        x_q, x_scale, x_fp = qt.q, qt.scale, qt.fp
        out_dtype = x.dtype
    exit_emit = epilogue is not None and epilogue.emits_q
    keep_fp = exit_emit and epilogue.residual != "none"
    outs = supersite_op_int8(
        x_q, x_scale, pack.q, pack.fp,
        x_fp if first_residual else None,
        geom=geom, exit_emit=exit_emit, keep_fp=keep_fp,
        interpret=interpret)
    if exit_emit:
        fp = outs[2].astype(out_dtype) if keep_fp else None
        return QTensor(outs[0], outs[1], fp)
    return outs.astype(out_dtype)


# ---------------------------------------------------------------------------
# registry impls (consumed by core.fusion.plan_program / core.program)
# ---------------------------------------------------------------------------

@register
class SupersiteKernel(KernelBase):
    """(supersite, fp): the banded inter-layer chain kernel.  ``site``
    throughout is a ``core.program.SuperSite``."""
    kind, precision, dtype = "supersite", "fp", "f32"
    vmem_budget = VMEM_BUDGET_BYTES

    def vmem_bytes(self, site, dtype=None):
        rows = choose_block_rows(site)
        return supersite_vmem_bytes(site, rows or 4)

    def tune(self, site, *, autotune=True, interpret=None):
        rows = choose_block_rows(site)
        return {} if rows is None else {"block_rows": rows}

    def candidates(self, site):
        _, ho, _, _ = site.out_shape
        return tuple(c for c in BLOCK_ROWS_CANDIDATES
                     if c["block_rows"] <= ho)

    def block_work(self, site, blocks):
        from repro.kernels.autotune import tile_work
        return tile_work(site.out_shape[1], blocks["block_rows"])

    def apply(self, params, x, site, decision=None, *, interpret=None,
              epilogue=None):
        blocks = getattr(decision, "blocks", None) or {}
        return supersite_apply(params, x, site, blocks,
                               interpret=interpret, epilogue=epilogue)

    def ref(self, params, x, site, *, epilogue=None, **kw):
        """Member-by-member reference chain (the parity oracle)."""
        from repro.core.efficientvit import dsconv, mbconv
        from repro.core.program import params_at
        y = act_fp(x)
        for s in site.sites:
            p = params_at(params, s.param_path)
            fn = dsconv if s.kind == "dsconv" else mbconv
            out = fn(p, y, stride=s.stride, act=s.act or "hswish")
            y = y + out if s.residual else out
        if epilogue is not None and epilogue.emits_q:
            return quantize_act(y, keep_fp=epilogue.residual != "none")
        return y


@register
class SupersiteInt8Kernel(SupersiteKernel):
    """(supersite, int8): FIX8 chain — whole-map per batch element,
    bit-exact vs the ungrouped int8 site sequence."""
    precision, dtype = "int8", "i8"
    takes_q = True
    emits_q = True

    def vmem_bytes(self, site, dtype=None):
        return supersite_vmem_bytes_int8(site)

    def tune(self, site, *, autotune=True, interpret=None):
        return {}

    def candidates(self, site):
        return ()

    def block_work(self, site, blocks):
        return 1.0

    def apply(self, params, x, site, decision=None, *, interpret=None,
              epilogue=None):
        return supersite_apply_int8(params, x, site, interpret=interpret,
                                    epilogue=epilogue)
