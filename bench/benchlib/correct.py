"""The comparison that decides ``correct``.

Every answer the served path delivered is compared with the
configuration's plain reference run on the same pool image: the number
is the largest, over all of them, of ``max|served - ref| / max|ref|``
per request (``logit_err``).  The reference is computed after the
window, once per pool image, in blocks of a fixed batch so that one
program serves every block.  The limit is the configuration's own
(``correct.logit_err`` in its file), set from readings of the program
and of the control as ``PERF.md`` records.
"""
from __future__ import annotations

import numpy as np

REF_BLOCK = 8


def reference_logits(ref, cfg: dict, params, pool: np.ndarray,
                     indices, **arith) -> dict:
    """{pool index: reference logits} for the ``indices`` asked for;
    ``arith`` selects the control's arithmetic (``quant_bits``,
    ``products``)."""
    import jax
    bits = arith.pop("quant_bits", 8 if cfg["precision"] == "int8"
                     else None)
    fwd = jax.jit(lambda p, x: ref.forward(p, x, cfg, quant_bits=bits,
                                           **arith))
    idx = sorted(set(int(i) for i in indices))
    out = {}
    for b in range(0, len(idx), REF_BLOCK):
        blk = idx[b:b + REF_BLOCK]
        x = pool[blk]
        if len(blk) < REF_BLOCK:
            x = np.concatenate([x, np.zeros((REF_BLOCK - len(blk),)
                                            + x.shape[1:], x.dtype)])
        y = np.asarray(jax.device_get(fwd(params, x)), np.float64)
        out.update(zip(blk, y))
    return out


def logit_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.max(np.abs(got - want))
                 / max(float(np.max(np.abs(want))), 1e-30))


def worst(answers, refs: dict) -> float:
    """Largest ``logit_err`` over (pool index, logits) ``answers``."""
    return max((logit_err(y, refs[i]) for i, y in answers), default=0.0)
