"""Pluggable kernel registry: one uniform interface per fused kernel.

ME-ViT (arXiv 2402.09709) argues the hardware version of this point — a
uniform processing-element interface is what lets new op types slot into
the pipeline without restructuring it.  This is the software analogue:
every fused execution path registers a ``KernelImpl`` under a
``(kind, precision)`` key, and both the planner
(``core.fusion.plan_program``) and the executor
(``core.program.execute``) consult the registry instead of hand-threaded
``dispatch_*`` functions and per-kind if/elif precision branches.

Built-in registrations (loaded lazily from the kernel packages):

    ("dsconv", "fp")       kernels/dsconv/ops.py     DW+PW megakernel
    ("dsconv", "int8")     kernels/dsconv/ops.py     FIX8, in-kernel requant
    ("mbconv", "fp")       kernels/mbconv/ops.py     PW+DW+PW megakernel
    ("mbconv", "int8")     kernels/mbconv/ops.py     FIX8, in-kernel requant
    ("fmbconv", "fp")      kernels/fmbconv/ops.py    dense 3x3 + PW (L series)
    ("resblock", "fp")     kernels/resblock/ops.py   3x3 + 3x3 + residual
                           (the L series' stem)
    ("msa",    "fp")       kernels/relu_attn/ops.py  single-launch MSA module
    ("msa",    "int8")     kernels/int8_matmul/ops.py  + W8A8 projections
    ("group_agg", "int8")  kernels/group_conv/ops.py  MSA multi-scale
                           aggregation (depthwise s x s + grouped 1x1)

## The epilogue contract (the int8 dataflow)

``apply`` takes an optional ``epilogue`` (a ``core.program.Epilogue``
with ``out_dtype="int8"``): the kernel then quantizes its own output
in-kernel (per-batch-element symmetric absmax) and returns a
``core.quantization.QTensor`` — plus the fp tensor when the epilogue's
residual policy is ``"keep-fp"``.  Impl capability flags tell the
planner's producer->consumer pass (``core.fusion.assign_epilogues``)
what each family supports:

    takes_q   ``apply`` accepts a ``QTensor`` input (skips the
              consumer-side activation quantize entirely)
    emits_q   ``apply`` implements the int8 act-quant epilogue

``adds_residual`` declares that ``apply`` adds a residual site's input
to its output in-kernel, so ``core.program.execute`` does not add it
again (the impl's ``ref`` still returns the bare block).

``batch_dependent_tiles`` declares that ``tune`` keys its block choices
on the batch axis; ``plan_program(..., reuse=)`` then only accepts
exact-batch donors for this family instead of the per-sample-geometry
match.

## Registering a new kernel (worked example)

``kernels/group_conv/ops.py`` is the worked example, grown from the
ROADMAP item it closes: the grouped int8 kernel for the MSA multi-scale
aggregation convs (depthwise s x s + grouped 1x1, one Pallas launch per
scale — the FIX8 msa module calls it instead of falling back to the
reference ``conv2d_int8``).  The additive recipe it followed:

1. write the Pallas kernel + wrapper (``kernels/group_conv/kernel.py``
   + ``ops.py`` with ``group_agg_apply_int8(params, x, ...)``);
2. register it there (an int8-only kind is fine — ``get_probe`` falls
   back to whatever precision the kind ships)::

       @register
       class GroupAggInt8Kernel(KernelBase):
           kind, precision, dtype = "group_agg", "int8", "i8"
           takes_q = True
           def site_precision(self, params): ...
           def apply(self, params, x, site, decision=None, *,
                     interpret=None, epilogue=None): ...
           def ref(self, params, x, site, **kw): ...   # fallback path

3. emit a ``Site(kind="group_agg", ...)`` in ``core.program.lower``
   (or, as here, fold it into the msa site's apply) and add the module
   to ``_BUILTIN_MODULES`` below.

No changes to ``plan_program``, ``execute``, the benchmarks or the
cycle model: any non-structural ``Site`` kind is fusible, the planner's
generic loop resolves the impl by key, ``execute`` runs ``apply`` when
the decision fuses and the impl's ``ref`` otherwise, and the drift-gate
tests pin the launch-count consequences explicitly
(``core.fusion.EXPECTED_B1_SUPERSITE_LAUNCHES_INT8``).
``tests/test_program.py::test_registry_new_kernel_plans_and_executes``
exercises this flow end-to-end with a dummy kind.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Protocol, Tuple

__all__ = ["KernelImpl", "KernelBase", "register", "get_kernel",
           "get_probe", "registered_kinds", "available", "unregister",
           "conv_block_precision", "resolve_conv_precision"]

VMEM_UNLIMITED = float("inf")


class KernelImpl(Protocol):
    """The uniform kernel interface the planner and executor consume.

    ``kind``/``precision`` key the registry; ``dtype`` is the analytic
    dtype tag ("f32" | "i8") used for VMEM sizing and autotune cache
    keys; ``vmem_budget`` is the per-launch budget ``vmem_bytes`` is
    checked against (``VMEM_UNLIMITED`` for streamed kernels).
    ``takes_q``/``emits_q`` are the int8-dataflow capability flags the
    epilogue-assignment pass consults; ``batch_dependent_tiles`` scopes
    donor-plan block reuse to exact-batch matches.
    """
    kind: str
    precision: str
    dtype: str
    vmem_budget: float
    takes_q: bool
    emits_q: bool
    adds_residual: bool
    batch_dependent_tiles: bool

    def site_precision(self, params) -> str:
        """Precision the site's param subtree carries: fp | int8 | mixed."""
        ...

    def resolve_precision(self, site_precision: str, requested: str
                          ) -> Tuple[str, Optional[str]]:
        """(site precision, requested) -> (run precision, fallback reason
        or None to proceed)."""
        ...

    def vmem_bytes(self, site, dtype: str | None = None) -> float:
        """Analytic per-grid-step VMEM for the site's shape."""
        ...

    def tune(self, site, *, autotune: bool = True,
             interpret: bool | None = None) -> Dict[str, int]:
        """Block-size choices (autotuned when ``autotune``, else cached/
        heuristic) to freeze into the site's decision."""
        ...

    def candidates(self, site) -> Tuple[Dict[str, int], ...]:
        """The family's candidate block configs for this site — the
        per-site dimension of the offline schedule search's space
        (``repro.search``).  Empty = nothing to sweep."""
        ...

    def block_work(self, site, blocks: Dict[str, int]) -> float:
        """Analytic relative overcompute of tiling ``site`` with
        ``blocks`` (>= 1.0; 1.0 = the tiles divide the tiled axis
        exactly).  Pure host arithmetic — the search's device-free
        block score."""
        ...

    def apply(self, params, x, site, decision=None, *,
              interpret: bool | None = None, epilogue=None):
        """Run the fused kernel on one site.  ``decision`` (a
        ``core.fusion.SiteDecision``) supplies block sizes; ``None``
        means defaults.  ``x`` may be a ``core.quantization.QTensor``
        when the impl declares ``takes_q``; an int8 ``epilogue`` (only
        ever passed when the impl declares ``emits_q``) makes the
        kernel quantize its own output and return a ``QTensor``."""
        ...

    def ref(self, params, x, site, *, epilogue=None, **kw):
        """The site's reference-path computation (parity oracle).
        Takes fp input; with an int8 ``epilogue`` it mirrors the
        producer-side emission as an XLA-level ``quantize_act`` of the
        reference output — the oracle the epilogue parity tests diff
        kernels against."""
        ...


# ---------------------------------------------------------------------------
# shared precision-resolution policies
# ---------------------------------------------------------------------------

def conv_block_precision(block) -> str:
    """Precision of a conv+BN (or qconv) block tree: every subblock
    quantized -> int8, none -> fp, anything else -> mixed."""
    kinds = {"int8" if (isinstance(v, dict) and "qconv" in v) else "fp"
             for v in block.values() if isinstance(v, dict)}
    if kinds == {"int8"}:
        return "int8"
    if kinds == {"fp"}:
        return "fp"
    return "mixed"


def resolve_conv_precision(site_prec: str, requested: str
                           ) -> Tuple[str, Optional[str]]:
    """Conv-kind policy: the megakernels consume one weight dtype, so a
    forced mismatch (or a part-quantized tree) demotes to reference."""
    if site_prec == "mixed":
        return "fp", "mixed"
    if requested in ("auto", site_prec):
        return site_prec, None
    return "fp", "quantized" if site_prec == "int8" else "not-quantized"


class KernelBase:
    """Default ``KernelImpl`` behavior: conv-style precision policy, no
    VMEM constraint, no tunable blocks, no int8-dataflow capabilities.
    Impls override what differs."""
    kind = ""
    precision = "fp"
    dtype = "f32"
    vmem_budget = VMEM_UNLIMITED
    takes_q = False               # apply accepts QTensor inputs
    emits_q = False               # apply implements the int8 epilogue
    adds_residual = False         # apply adds a residual site's input
    batch_dependent_tiles = False  # tune keys blocks on the batch axis

    def site_precision(self, params) -> str:
        return conv_block_precision(params)

    def resolve_precision(self, site_prec, requested):
        return resolve_conv_precision(site_prec, requested)

    def vmem_bytes(self, site, dtype=None) -> float:
        return 0.0

    def tune(self, site, *, autotune=True, interpret=None):
        return {}

    def candidates(self, site):
        return ()

    def block_work(self, site, blocks):
        return 1.0

    def apply(self, params, x, site, decision=None, *, interpret=None,
              epilogue=None):
        raise NotImplementedError(type(self).__name__)

    def ref(self, params, x, site, **kw):
        raise NotImplementedError(type(self).__name__)


# ---------------------------------------------------------------------------
# the registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[Tuple[str, str], Any] = {}
_BUILTIN_MODULES = (
    "repro.kernels.dsconv.ops",
    "repro.kernels.mbconv.ops",
    "repro.kernels.fmbconv.ops",
    "repro.kernels.resblock.ops",
    "repro.kernels.relu_attn.ops",
    "repro.kernels.int8_matmul.ops",
    "repro.kernels.group_conv.ops",
    "repro.kernels.supersite.ops",
)
_builtins_loaded = False


def register(cls):
    """Class decorator: instantiate and register under
    ``(cls.kind, cls.precision)``.  Last registration wins, so a user
    kernel can shadow a built-in."""
    impl = cls()
    assert impl.kind and impl.precision, cls
    _REGISTRY[(impl.kind, impl.precision)] = impl
    return cls


def unregister(kind: str, precision: str) -> None:
    _REGISTRY.pop((kind, precision), None)


def _ensure_builtins() -> None:
    global _builtins_loaded
    if _builtins_loaded:
        return
    import importlib
    for mod in _BUILTIN_MODULES:
        importlib.import_module(mod)
    # flag only after every import succeeded, so a transient failure
    # surfaces as the real ImportError on retry, not a misleading
    # "no kernel registered" KeyError forever after
    _builtins_loaded = True


def get_kernel(kind: str, precision: str = "fp"):
    """Look up the ``KernelImpl`` for a (kind, precision) pair."""
    _ensure_builtins()
    try:
        return _REGISTRY[(kind, precision)]
    except KeyError:
        raise KeyError(
            f"no kernel registered for {(kind, precision)!r}; "
            f"available: {sorted(_REGISTRY)}") from None


def get_probe(kind: str):
    """The impl that answers kind-level questions (``site_precision``,
    ``resolve_precision``, reference path) — the "fp" registration when
    present, else any registration of that kind, so a kind that only
    ships one precision (e.g. an int8-only grouped conv) still plans."""
    _ensure_builtins()
    impl = _REGISTRY.get((kind, "fp"))
    if impl is not None:
        return impl
    for (k, _), candidate in sorted(_REGISTRY.items()):
        if k == kind:
            return candidate
    raise KeyError(f"no kernel registered for kind {kind!r}; "
                   f"available: {sorted(_REGISTRY)}")


def registered_kinds() -> set:
    """Every kind with at least one registration."""
    _ensure_builtins()
    return {k for k, _ in _REGISTRY}


def available() -> list[Tuple[str, str]]:
    """Sorted (kind, precision) keys of every registered kernel."""
    _ensure_builtins()
    return sorted(_REGISTRY)
