"""Single-load weight residency: pack a super-site's weights into ONE block.

ME-ViT's (arXiv 2402.09709) single-load strategy, software-side: all
member-site weights of a ``core.program.SuperSite`` are stacked into
one resident block — a single fp32 matrix for the fp chain, an int8
matrix + an fp32 scale/bias matrix for the FIX8 chain — that the
supersite kernel maps with a constant-index BlockSpec, so the grid
re-reads nothing from HBM between spatial tiles.  Each tensor occupies
its own rows of the block, starting on a row aligned to the dtype's
sublane tile and at lane 0, which is the form in which Mosaic slices a
VMEM block without relayout.

The pack is built from the params on every call of the forward: the
served executors jit ``execute`` with the params as arguments, so the
gather is part of each compiled forward (an XLA copy of the chain's
weights per call), not a host-side cache.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax.numpy as jnp

from repro.core.program import params_at
from repro.core.quantization import fold_bn_into_conv

__all__ = ["WeightPack", "pack_weights"]


class WeightPack(NamedTuple):
    """One super-site's resident weights.

    ``fp``: (rows, lanes) fp32 — weights+biases for an fp chain;
    scales+biases for an int8 chain.  ``q``: (rows, lanes) int8 weight
    values (int8 chains only).  Every tensor is stored as a matrix (a
    vector as one row, a (3, 3, C) depthwise kernel as 9 rows).
    ``fp_offsets``/``q_offsets``: per-member tuples of static starting
    rows, in the fixed per-kind order the kernel unpacks
    (mbconv fp: w1,b1,dw,dwb,w2,b2; dsconv fp: dw,dwb,pw,pwb; int8 q:
    mbconv w1,dw,w2 / dsconv dw,pw; int8 fp: mbconv s1,b1,dws,dwb,s2,b2
    / dsconv dws,dwb,pws,pwb).  ``nbytes`` is the delivered-HBM cost of
    loading the pack once.
    """
    fp: jnp.ndarray
    q: Optional[jnp.ndarray]
    fp_offsets: Tuple[Tuple[int, ...], ...]
    q_offsets: Tuple[Tuple[int, ...], ...]
    nbytes: int


def _member_fp_tensors(p, kind):
    """Folded fp tensors of one member, in kernel unpack order."""
    if kind == "mbconv":
        w1_4, b1 = fold_bn_into_conv(p["pw1"]["conv"], p["pw1"]["bn"])
        dw_4, dwb = fold_bn_into_conv(p["dw"]["conv"], p["dw"]["bn"])
        w2_4, b2 = fold_bn_into_conv(p["pw2"]["conv"], p["pw2"]["bn"])
        return (w1_4[0, 0], b1, dw_4[:, :, 0, :], dwb, w2_4[0, 0], b2)
    dw_4, dwb = fold_bn_into_conv(p["dw"]["conv"], p["dw"]["bn"])
    pw_4, pwb = fold_bn_into_conv(p["pw"]["conv"], p["pw"]["bn"])
    return (dw_4[:, :, 0, :], dwb, pw_4[0, 0], pwb)


def _member_int8_tensors(p, kind):
    """(int8 weight tensors, fp scale/bias tensors) of one member."""
    if kind == "mbconv":
        q1, qd, q2 = p["pw1"]["qconv"], p["dw"]["qconv"], p["pw2"]["qconv"]
        qs = (q1["q"][0, 0], qd["q"][:, :, 0, :], q2["q"][0, 0])
        fs = (q1["scale"], q1["bias"], qd["scale"], qd["bias"],
              q2["scale"], q2["bias"])
        return qs, fs
    qd, qp = p["dw"]["qconv"], p["pw"]["qconv"]
    qs = (qd["q"][:, :, 0, :], qp["q"][0, 0])
    fs = (qd["scale"], qd["bias"], qp["scale"], qp["bias"])
    return qs, fs


# row alignment of each tensor in a pack: the sublane tile of the dtype
_ROW_ALIGN = {jnp.dtype(jnp.float32): 8, jnp.dtype(jnp.int8): 32}
_LANES = 128


def _stack_rows(tensors, dtype):
    """Stack tensors as row blocks of one (rows, lanes) matrix ->
    (matrix, per-tensor starting rows).  Rows start on the dtype's
    sublane tile; lanes are padded to a multiple of 128."""
    align = _ROW_ALIGN[jnp.dtype(dtype)]
    mats = [jnp.asarray(t, dtype).reshape(-1, t.shape[-1]) for t in tensors]
    if not mats:
        return jnp.zeros((align, _LANES), dtype), ()
    lanes = -(-max(m.shape[1] for m in mats) // _LANES) * _LANES
    offs, blocks, n = [], [], 0
    for m in mats:
        rows = -(-m.shape[0] // align) * align
        offs.append(n)
        blocks.append(jnp.pad(m, ((0, rows - m.shape[0]),
                                  (0, lanes - m.shape[1]))))
        n += rows
    return jnp.concatenate(blocks), tuple(offs)


def pack_weights(params, supersite, precision: str) -> WeightPack:
    """Pack every member's weights into the resident block(s)."""
    fp_all, q_all = [], []
    fp_counts, q_counts = [], []
    for site in supersite.sites:
        p = params_at(params, site.param_path)
        if precision == "int8":
            qs, fs = _member_int8_tensors(p, site.kind)
        else:
            qs, fs = (), _member_fp_tensors(p, site.kind)
        fp_all.extend(fs)
        q_all.extend(qs)
        fp_counts.append(len(fs))
        q_counts.append(len(qs))
    fp_flat, fp_offs = _stack_rows(fp_all, jnp.float32)
    q_flat, q_offs = (_stack_rows(q_all, jnp.int8) if q_all
                      else (None, ()))

    def _split(offs, counts):
        out, i = [], 0
        for c in counts:
            out.append(tuple(offs[i:i + c]))
            i += c
        return tuple(out)

    nbytes = int(fp_flat.size) * 4 + (int(q_flat.size) if q_flat is not None
                                      else 0)
    return WeightPack(fp_flat, q_flat, _split(fp_offs, fp_counts),
                      _split(q_offs, q_counts), nbytes)

