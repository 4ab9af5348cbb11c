"""Pallas kernels: an inter-layer super-site chain in ONE launch.

The paper's TMP dataflow fuses across layer boundaries (Fig. 5); the
per-site megakernels (kernels/mbconv, kernels/dsconv) already fuse
*within* a block.  This module fuses the next level up: a whole chain of
consecutive conv sites (``core.program.SuperSite``) runs as a single
``pallas_call`` — member boundary activations exist only as in-register
values / VMEM temporaries, never in HBM, and every member's weights come
from one packed resident block (``pack.py``) whose BlockSpec index map
is constant, so the weights are read from HBM once per launch no matter
how many grid steps run.

Two variants, mirroring the per-site kernel split:

* ``supersite_fused`` (fp32) — grid ``(batch, row-bands)``: the grid
  walks spatial tiles of the STAGE OUTPUT.  Each band recomputes the
  overlapping input halo (``band_geometry`` walks the chain backwards to
  size each member's input window), which is what lets a stage whose
  whole feature map would blow the VMEM budget run fused anyway — this
  retires the B1@384 fp ``"vmem"`` demotions.
* ``supersite_fused_int8`` (FIX8) — grid ``(batch,)``, whole feature
  map per step: the int8 dataflow's per-batch-element absmax
  requantization at every member boundary needs the full map, so
  spatial tiling would change the numerics.  Arithmetic per member is
  identical to the per-site emit kernels plus ``execute``'s fp residual
  adds, which keeps the chain bit-exact vs the ungrouped int8 path.

Band geometry (fp).  Member output row ``t`` at stride ``s`` reads
input rows ``s*t + off + {0,1,2}`` with ``off = s-2`` for mbconv
(reference subsamples ``[s-1::s]``) and ``off = -1`` for dsconv
(reference subsamples ``[::s]``).  Walking the chain backwards from an
output window of ``R`` rows gives each member an affine input window
``start(j) = c0 + c1*j`` of static length ``L = s*(n-1) + 3``; rows of
the window that fall outside the real feature map are masked to zero
in-kernel (zero-padding the *input* is not enough for mbconv — the
reference zero-pads the expanded ``mid`` tensor, and
``hardswish(b1) != 0`` on zeroed input rows).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.efficientvit import activation
from repro.kernels.compat import default_interpret, tpu_compiler_params
from repro.kernels.quant import int8_dot, requantize_i8
from repro.kernels.taps import dw_taps, fill, tap_scratch


class MemberGeom(NamedTuple):
    """Static geometry + resident-pack offsets of one chain member."""
    kind: str                  # "mbconv" | "dsconv"
    stride: int
    residual: bool
    h_in: int                  # valid (unpadded) input rows
    w_in: int
    c_in: int
    mid: int                   # mbconv expansion width (0 for dsconv)
    f_out: int
    c0: int = 0                # input window start: c0 + c1 * band
    c1: int = 0
    length: int = 0            # input window rows (static)
    n_out: int = 0             # output rows produced per band
    fp_offs: Tuple[int, ...] = ()
    q_offs: Tuple[int, ...] = ()
    act: str = "hswish"        # the member's activation (static)


class SupersiteGeom(NamedTuple):
    """Static launch geometry of one super-site (hashable: jit key)."""
    members: Tuple[MemberGeom, ...]
    h_out: int
    w_out: int
    f_out: int
    block_rows: int = 0        # fp band height R (0: whole-map int8)
    n_bands: int = 0


def band_geometry(members: Tuple[MemberGeom, ...], block_rows: int,
                  h_out: int) -> Tuple[int, Tuple[MemberGeom, ...]]:
    """Walk the chain backwards, sizing each member's input window.

    Returns ``(n_bands, members)`` with every member's affine window
    ``(c0, c1, length)`` and per-band output rows ``n_out`` filled in.
    The window covering output rows ``[o0, o0+n)`` at stride ``s`` is
    ``[s*o0 + off, s*o0 + off + s*(n-1) + 3)``.
    """
    n_bands = -(-h_out // block_rows)
    out = []
    win = (0, block_rows, block_rows)            # (c0, c1, rows)
    for m in reversed(members):
        s = m.stride
        off = (s - 2) if m.kind == "mbconv" else -1
        n_out = win[2]
        win = (s * win[0] + off, s * win[1], s * (win[2] - 1) + 3)
        out.append(m._replace(c0=win[0], c1=win[1], length=win[2],
                              n_out=n_out))
    return n_bands, tuple(reversed(out))


def _take(w_ref, off: int, rows: int, cols: int):
    """Static ``(rows, cols)`` block of the resident weight pack: every
    tensor starts on an aligned row, at lane 0 (``pack.pack_weights``)."""
    return w_ref[off:off + rows, :cols]


def _tap_weights(w_ref, off: int, cast=None):
    """Depthwise tap rows of a pack (the (3, 3, C) kernel stored as 9
    rows) as the ``weight`` callable of ``taps.dw_taps``."""
    def weight(dy, dx, lo, hi):
        w = w_ref[off + 3 * dy + dx, lo:hi]
        return w if cast is None else w.astype(cast)
    return weight


def member_scratch(m: MemberGeom, rows: int, dtype):
    """The tap scratch of one chain member: its input window (``rows``
    tall) plus the SAME column padding, at the depthwise width."""
    width = m.mid if m.kind == "mbconv" else m.c_in
    return tap_scratch(rows, m.w_in + 2, width, dtype)


# ---------------------------------------------------------------------------
# fp32: spatially-banded chain
# ---------------------------------------------------------------------------

def _fp_member(cur, j, m: MemberGeom, w_ref, scr):
    """One fp chain member on a band: cur (length, W, C) -> (n, Wo, F).

    Arithmetic is element-for-element the per-site megakernel's
    (kernels/mbconv, kernels/dsconv): same tap order, same bias /
    subsample / activation ordering, so the fused chain tracks the
    site-by-site path to accumulation roundoff only.
    """
    f = activation(m.act)
    L, W, C = m.length, m.w_in, m.c_in
    s, n = m.stride, m.n_out
    Wo = W // s
    # global input-row validity of this band's window (halo masking)
    rows = (m.c0 + m.c1 * j) \
        + jax.lax.broadcasted_iota(jnp.int32, (L, 1, 1), 0)
    valid = (rows >= 0) & (rows < m.h_in)
    o = m.fp_offs

    if m.kind == "mbconv":
        M, F = m.mid, m.f_out
        w1 = _take(w_ref, o[0], C, M)
        b1 = _take(w_ref, o[1], 1, M)
        dwb = _take(w_ref, o[3], 1, M)
        w2 = _take(w_ref, o[4], M, F)
        b2 = _take(w_ref, o[5], 1, F)
        mid = jnp.dot(cur.reshape(L * W, C), w1,
                      preferred_element_type=jnp.float32)
        mid = f(mid + b1).reshape(L, W, M)
        # the reference zero-pads MID: rows outside the feature map must
        # contribute zero to the DW taps, and act(b1) != 0
        fill(scr, jnp.where(valid, mid, 0.0), col0=1)
        acc = dw_taps(scr, _tap_weights(w_ref, o[2]), rows=n, cols=Wo,
                      stride=s, col0=s - 1)
        dw = f(acc + dwb[None])
        out = jnp.dot(dw.reshape(n * Wo, M), w2,
                      preferred_element_type=jnp.float32)
        out = (out + b2).reshape(n, Wo, F)
    else:                                        # dsconv (act always on)
        F = m.f_out
        dwb = _take(w_ref, o[1], 1, C)
        pww = _take(w_ref, o[2], C, F)
        pwb = _take(w_ref, o[3], 1, F)
        fill(scr, jnp.where(valid, cur, 0.0), col0=1)
        acc = dw_taps(scr, _tap_weights(w_ref, o[0]), rows=n, cols=Wo,
                      stride=s)
        dw = f(acc + dwb[None])
        out = jnp.dot(dw.reshape(n * Wo, C), pww,
                      preferred_element_type=jnp.float32)
        out = (out + pwb).reshape(n, Wo, F)

    if m.residual:                               # s == 1, F == C
        out = out + cur[1:1 + n]
    return out


def _supersite_kernel(x_ref, w_ref, o_ref, *scratch, geom: SupersiteGeom):
    j = pl.program_id(1)
    cur = x_ref[0, 0].astype(jnp.float32)        # (L0, W0, C0) slab
    for m, scr in zip(geom.members, scratch):
        cur = _fp_member(cur, j, m, w_ref, scr)
    o_ref[0] = cur                               # (R, W_out, F_out)


def supersite_fused(x, w_flat, *, geom: SupersiteGeom,
                    interpret: bool | None = None):
    """Run an fp super-site chain.  x: (B, H, W, C) member-0 input;
    ``w_flat``: the (rows, lanes) resident pack (``pack.pack_weights``);
    ``geom``: ``SupersiteGeom`` with band windows filled in
    (``ops.make_fp_geom``).  Returns (B, H_out, W_out, F_out) fp32.

    The host gathers the per-band overlapping input slabs (static
    slices of the zero-padded input) so each grid step reads exactly
    its window; the weight block's index map is constant — loaded once,
    resident across all ``B * n_bands`` steps.
    """
    interpret = default_interpret(interpret)
    B, H, W, C = x.shape
    R, nb = geom.block_rows, geom.n_bands
    m0 = geom.members[0]
    c0, c1, L = m0.c0, m0.c1, m0.length
    pad_top = max(0, -c0)
    pad_bot = max(0, c0 + c1 * (nb - 1) + L - H)
    xpad = jnp.pad(x.astype(jnp.float32),
                   ((0, 0), (pad_top, pad_bot), (0, 0), (0, 0)))
    slabs = jnp.stack(
        [xpad[:, c0 + pad_top + c1 * j: c0 + pad_top + c1 * j + L]
         for j in range(nb)], axis=1)            # (B, nb, L, W, C)

    out = pl.pallas_call(
        functools.partial(_supersite_kernel, geom=geom),
        grid=(B, nb),
        in_specs=[
            pl.BlockSpec((1, 1, L, W, C), lambda b, j: (b, j, 0, 0, 0)),
            pl.BlockSpec(w_flat.shape, lambda b, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, R, geom.w_out, geom.f_out),
                               lambda b, j: (b, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, nb * R, geom.w_out, geom.f_out),
                                       jnp.float32),
        scratch_shapes=[member_scratch(m, m.length, jnp.float32)
                        for m in geom.members],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(slabs, w_flat)
    return out[:, :geom.h_out]


# ---------------------------------------------------------------------------
# FIX8: whole-map chain, per-batch-element grid
# ---------------------------------------------------------------------------

def _int8_member(cur_q, cur_s, m: MemberGeom, wq_ref, wf_ref, scr):
    """One FIX8 chain member: (int8 map, (1, 1) scale) -> fp32 output map.

    Identical arithmetic to the per-site int8 emit kernels
    (``_mbconv_int8_emit_kernel`` / ``_dsconv_int8_emit_kernel``) up to
    — but not including — the exit requantization, which the chain
    driver applies per boundary policy.
    """
    H, W, C = m.h_in, m.w_in, m.c_in
    s = m.stride
    Ho, Wo = H // s, W // s
    qo, fo = m.q_offs, m.fp_offs
    f = activation(m.act)
    i32 = jnp.int32
    if m.kind == "mbconv":
        M, F = m.mid, m.f_out
        w1q = _take(wq_ref, qo[0], C, M)
        w2q = _take(wq_ref, qo[2], M, F)
        s1 = _take(wf_ref, fo[0], 1, M)
        b1 = _take(wf_ref, fo[1], 1, M)
        dws = _take(wf_ref, fo[2], 1, M)
        dwb = _take(wf_ref, fo[3], 1, M)
        s2 = _take(wf_ref, fo[4], 1, F)
        b2 = _take(wf_ref, fo[5], 1, F)
        xq = cur_q.reshape(H * W, C)
        acc = int8_dot(xq, w1q)
        mid = acc.astype(jnp.float32) * (cur_s * s1) + b1
        mid = f(mid)
        mq, s_mid = requantize_i8(mid)
        fill(scr, mq.reshape(H, W, M), row0=1, col0=1)
        acc2 = dw_taps(scr, _tap_weights(wq_ref, qo[1], i32), rows=Ho,
                       cols=Wo, stride=s, row0=s - 1, col0=s - 1)
        dw = acc2.astype(jnp.float32) * (s_mid * dws)[None] + dwb[None]
        dw = f(dw)
        dq, s_dw = requantize_i8(dw.reshape(Ho * Wo, M))
        acc3 = int8_dot(dq, w2q)
        out = acc3.astype(jnp.float32) * (s_dw * s2) + b2
    else:                                        # dsconv (act always on)
        F = m.f_out
        pwq = _take(wq_ref, qo[1], C, F)
        dws = _take(wf_ref, fo[0], 1, C)
        dwb = _take(wf_ref, fo[1], 1, C)
        pws = _take(wf_ref, fo[2], 1, F)
        pwb = _take(wf_ref, fo[3], 1, F)
        fill(scr, cur_q, row0=1, col0=1)
        acc = dw_taps(scr, _tap_weights(wq_ref, qo[0], i32), rows=Ho,
                      cols=Wo, stride=s, row0=s - 1, col0=s - 1)
        y = acc.astype(jnp.float32) * (cur_s * dws)[None] + dwb[None]
        y = f(y)
        dq, s_dw = requantize_i8(y.reshape(Ho * Wo, C))
        acc2 = int8_dot(dq, pwq)
        out = acc2.astype(jnp.float32) * (s_dw * pws) + pwb
    return out.reshape(Ho, Wo, -1)


def _requant_map(x):
    """Per-batch-element requant of a (H, W, C) map -> (int8 map, (1, 1)
    scale): the absmax runs over the whole map either way."""
    q, s = requantize_i8(x.reshape(x.shape[0] * x.shape[1], -1))
    return q.reshape(x.shape), s


def _supersite_int8_kernel(x_ref, xs_ref, wq_ref, wf_ref, *refs,
                           geom: SupersiteGeom, has_xfp: bool,
                           exit_emit: bool, keep_fp: bool):
    n_members = len(geom.members)
    scratch, refs = refs[-n_members:], refs[:-n_members]
    if has_xfp:
        xfp_ref, refs = refs[0], refs[1:]
    if exit_emit:
        oq_ref, os_ref = refs[0], refs[1]
        ofp_ref = refs[2] if keep_fp else None
    else:
        ofp_ref = refs[0]

    cur_q = x_ref[0]                             # (H, W, C) int8
    cur_s = xs_ref[0]                            # (1, 1)
    cur_fp = xfp_ref[0] if has_xfp else None
    for k, (m, scr) in enumerate(zip(geom.members, scratch)):
        out = _int8_member(cur_q, cur_s, m, wq_ref, wf_ref, scr)
        last = k == n_members - 1
        if m.residual:
            # execute()'s fp residual add + post-add quantize, per batch
            # element (one element's map == the reference quantize_act)
            out = cur_fp + out
        if not last or exit_emit:
            # post-add quantize, or the per-site emit kernel's epilogue
            cur_q, cur_s = _requant_map(out)
        cur_fp = out
    if exit_emit:
        oq_ref[0] = cur_q
        os_ref[0] = cur_s
        if keep_fp:
            ofp_ref[0] = cur_fp
    else:
        ofp_ref[0] = cur_fp


def supersite_fused_int8(x_q, x_scale, wq_flat, wf_flat, *,
                         geom: SupersiteGeom, x_fp=None,
                         exit_emit: bool = False, keep_fp: bool = False,
                         interpret: bool | None = None):
    """Run a FIX8 super-site chain.  x_q: (B, H, W, C) int8 with
    per-batch-element (or scalar) ``x_scale``; ``wq_flat``/``wf_flat``:
    the int8 + fp32 resident pack halves (``pack.pack_weights``); ``x_fp``: the
    kept-fp entry activation (required iff member 0 is residual).

    Exit mirrors the site epilogue contract: ``exit_emit`` returns
    ``(q, scales)`` — plus the fp map when ``keep_fp`` — otherwise the
    fp32 output alone.  Every member boundary requantizes in-kernel per
    batch element, so the chain is bit-exact vs running the member
    sites one launch at a time (any batch).
    """
    from repro.kernels.quant import xs_per_batch

    interpret = default_interpret(interpret)
    B, H, W, C = x_q.shape
    assert x_q.dtype == jnp.int8
    Ho, Wo, F = geom.h_out, geom.w_out, geom.f_out
    xs = xs_per_batch(x_scale, B)
    has_xfp = x_fp is not None

    in_specs = [
        pl.BlockSpec((1, H, W, C), lambda b: (b, 0, 0, 0)),
        pl.BlockSpec((1, 1, 1), lambda b: (b, 0, 0)),
        pl.BlockSpec(wq_flat.shape, lambda b: (0, 0)),
        pl.BlockSpec(wf_flat.shape, lambda b: (0, 0)),
    ]
    args = [x_q, xs, wq_flat, wf_flat]
    if has_xfp:
        in_specs.append(pl.BlockSpec((1, H, W, C), lambda b: (b, 0, 0, 0)))
        args.append(x_fp.astype(jnp.float32))
    if exit_emit:
        out_shape = [jax.ShapeDtypeStruct((B, Ho, Wo, F), jnp.int8),
                     jax.ShapeDtypeStruct((B, 1, 1), jnp.float32)]
        out_specs = [pl.BlockSpec((1, Ho, Wo, F), lambda b: (b, 0, 0, 0)),
                     pl.BlockSpec((1, 1, 1), lambda b: (b, 0, 0))]
        if keep_fp:
            out_shape.append(
                jax.ShapeDtypeStruct((B, Ho, Wo, F), jnp.float32))
            out_specs.append(
                pl.BlockSpec((1, Ho, Wo, F), lambda b: (b, 0, 0, 0)))
    else:
        out_shape = [jax.ShapeDtypeStruct((B, Ho, Wo, F), jnp.float32)]
        out_specs = [pl.BlockSpec((1, Ho, Wo, F), lambda b: (b, 0, 0, 0))]

    outs = pl.pallas_call(
        functools.partial(_supersite_int8_kernel, geom=geom,
                          has_xfp=has_xfp, exit_emit=exit_emit,
                          keep_fp=keep_fp),
        grid=(B,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[member_scratch(m, m.h_in + 2, jnp.int32)
                        for m in geom.members],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(*args)
    if exit_emit:
        if keep_fp:
            return outs[0], outs[1].reshape(B), outs[2]
        return outs[0], outs[1].reshape(B)
    return outs[0]
