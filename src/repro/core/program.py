"""Typed program IR: ONE lowering of EfficientViT that everything runs.

The paper's core claim is a *reconfigurable* engine driven by one
compiled schedule (TMP dataflow, §III/§IV).  CHOSEN (arXiv 2407.12736)
makes the software version of that point: the win comes from a
compile-time stack with a single program representation.  This module is
that representation for the repo:

    ``lower(cfg) -> Program``     architecture walk, done ONCE
    ``execute(program, params, x, plan=...)``
                                  the forward — interprets the IR
    ``manifest(program)``         hardware op records (MACs/shapes) for
                                  the cycle model + fig6/table2

The forward, the fusion plan's site set, the analytic HBM accounting
and the benchmark numbers all derive from the same frozen ``Site``
sequence, so they cannot disagree with what actually runs.

Execution routes fusible sites through the pluggable kernel registry
(``repro.kernels.registry``) when a ``FusionPlan`` decision says so;
with ``plan=None`` every site runs its reference op.  Registering a new
kernel (see the registry docstring for the worked grouped-int8 example)
makes it schedulable here with no changes to this file.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Mapping, Tuple

import jax.numpy as jnp

from repro.common.errors import LoweringError
from repro.core.efficientvit import (
    B1, EfficientViTConfig, OpRecord, activation, conv_bn_act, dsconv,
    fmbconv, mbconv, stage_layout)
from repro.core.relu_attention import MSAConfig, msa
from repro.layers.norms import layernorm

__all__ = ["Epilogue", "EPILOGUE_FP", "Site", "SuperSite", "Program",
           "lower", "execute", "manifest", "site_records", "FUSIBLE_KINDS",
           "SUPERSITE_KINDS", "params_at"]


@dataclasses.dataclass(frozen=True)
class Epilogue:
    """Typed producer-side output descriptor of one ``Site``.

    The precision boundary of the int8 dataflow lives HERE, between
    producer and consumer, not inside each kernel: when the fusion
    planner's producer->consumer pass (``core.fusion.plan_program``)
    assigns ``out_dtype="int8"``, the producer emits the quantized
    activation itself (in-kernel for the Pallas megakernels, XLA-fused
    for structural convs) and the consumer never pays the extra fp32
    HBM read + standalone quantize that the pre-epilogue pipeline did.

    ``scale``     act-quant scale source: ``"none"`` (fp output) or
                  ``"dynamic"`` (per-batch-element symmetric absmax —
                  identical to the reference per-tensor scheme at
                  batch 1, within quantization noise otherwise).
    ``residual``  residual policy:
                  ``"none"``     pure int8 emission — the fp activation
                                 never materializes past the kernel;
                  ``"post-add"`` the site's OWN residual add runs fp;
                                 quantization applies after it (XLA,
                                 fused into the add);
                  ``"keep-fp"``  the CONSUMER's residual add needs the
                                 fp activation — the producer emits
                                 both fp and int8 (the residual-fp
                                 correction in the HBM accounting).
    """
    out_dtype: str = "fp32"    # "fp32" | "int8"
    scale: str = "none"        # "none" | "dynamic"
    residual: str = "none"     # "none" | "post-add" | "keep-fp"

    @property
    def emits_q(self) -> bool:
        return self.out_dtype == "int8"

    @property
    def keeps_fp(self) -> bool:
        """The fp activation also crosses the site boundary."""
        return self.out_dtype == "fp32" or self.residual != "none"


EPILOGUE_FP = Epilogue()

# Structural kinds ``execute`` interprets inline (single XLA convs, the
# pooling and the head); every OTHER kind is fusible — it plans through
# the kernel registry, so a newly registered kind (see kernels/
# registry.py's worked example) is schedulable the moment ``lower``
# emits its Site.  FUSIBLE_KINDS lists the built-ins.
STRUCTURAL_KINDS = ("conv_bn", "gap", "fc")
FUSIBLE_KINDS = ("dsconv", "mbconv", "fmbconv", "resblock", "msa")
# Conv-chain kinds the inter-layer super-site pass may group into one
# launch (core.fusion.plan_program's grouping pass + kernels/supersite).
SUPERSITE_KINDS = ("dsconv", "mbconv")


@dataclasses.dataclass(frozen=True)
class Site:
    """One schedulable node of the lowered network.

    ``name`` is the dotted site id shared with ``FusionPlan`` decisions
    (e.g. ``"S3.evit0.msa"``); ``param_path`` indexes the param tree
    (str = dict key, int = list index); ``attrs`` carries kind-specific
    geometry (mbconv / fmbconv / resblock: ``mid``; msa: ``heads``/
    ``head_dim``/``scales``/``n_branches``; conv_bn: ``k``; fc:
    ``norm`` when a LayerNorm precedes the activation).
    """
    name: str
    kind: str                  # conv_bn | dsconv | mbconv | fmbconv |
    #                            resblock | msa | gap | fc
    stage: str                 # stem | S1..S4 | head
    param_path: Tuple[Any, ...]
    in_shape: Tuple[int, ...]  # (B, H, W, C) — (B, C) for fc
    out_shape: Tuple[int, ...]
    stride: int = 1
    residual: bool = False     # out = x + op(x)
    act: str = ""              # the activation the site applies:
    #                            conv_bn / fc its trailing one, conv
    #                            blocks the one inside ("" = none;
    #                            core.efficientvit.ACTIVATIONS)
    attrs: Mapping[str, Any] = dataclasses.field(default_factory=dict)
    epilogue: Epilogue = EPILOGUE_FP   # producer-side output descriptor
    #                          (assigned by core.fusion.plan_program's
    #                          producer->consumer pass; lower() emits fp)

    @property
    def local_name(self) -> str:
        """Site name with the stage prefix stripped (manifest naming)."""
        prefix = f"{self.stage}."
        return self.name[len(prefix):] if self.name.startswith(prefix) \
            else self.name


@dataclasses.dataclass(frozen=True)
class SuperSite:
    """A chain of consecutive conv Sites lowered as ONE Pallas launch.

    The paper's *inter-layer* TMP fusion at the IR level: member sites'
    intermediate activations live only in VMEM scratch, and member
    weights are packed once into a resident block shared across grid
    steps (``kernels/supersite``).  Built by the fusion planner's
    grouping pass (``core.fusion.plan_program``) — ``of`` validates the
    chain so an invalid grouping fails at plan time as a typed
    ``LoweringError``, never as a shape error inside a jitted executor.
    """
    name: str
    stage: str
    sites: Tuple[Site, ...]

    @classmethod
    def of(cls, program: "Program", names, name: str | None = None
           ) -> "SuperSite":
        """Validate + build a super-site from member site names.

        Members must be >= 2 consecutive sites of ``program``, all of
        one stage, all super-site-fusible conv kinds, with an unbroken
        activation chain (each consumes exactly its predecessor's
        output).  Violations raise ``LoweringError`` naming the site.
        """
        names = tuple(names)
        if len(names) < 2:
            raise LoweringError(
                f"super-site needs >= 2 members, got {names}",
                site=names[0] if names else None)
        idx = {s.name: i for i, s in enumerate(program.sites)}
        for n in names:
            if n not in idx:
                raise LoweringError(f"super-site member {n!r} is not a "
                                    f"site of the program", site=n)
        order = [idx[n] for n in names]
        if order != list(range(order[0], order[0] + len(names))):
            raise LoweringError(
                f"super-site members {names} are not consecutive "
                f"program sites", site=names[0])
        members = tuple(program.sites[i] for i in order)
        stage = members[0].stage
        for m in members:
            if m.kind not in SUPERSITE_KINDS:
                raise LoweringError(
                    f"super-site member {m.name} has kind {m.kind!r}; "
                    f"only {SUPERSITE_KINDS} chain", site=m.name)
            if m.stage != stage:
                raise LoweringError(
                    f"super-site member {m.name} is in stage {m.stage}, "
                    f"group started in {stage}", site=m.name)
        for a, b in zip(members, members[1:]):
            if a.out_shape != b.in_shape:
                raise LoweringError(
                    f"super-site chain break {a.name} -> {b.name}: "
                    f"{a.out_shape} != {b.in_shape}", site=b.name)
        return cls(name or f"{stage}.ss", stage, members)

    # Site-like surface so registry impls / the cycle model can treat a
    # super-site as one schedulable unit.
    kind: str = dataclasses.field(default="supersite", init=False)

    @property
    def members(self) -> Tuple[str, ...]:
        return tuple(s.name for s in self.sites)

    @property
    def in_shape(self) -> Tuple[int, ...]:
        return self.sites[0].in_shape

    @property
    def out_shape(self) -> Tuple[int, ...]:
        return self.sites[-1].out_shape

    @property
    def stride(self) -> int:
        out = 1
        for s in self.sites:
            out *= s.stride
        return out


@dataclasses.dataclass(frozen=True)
class Program:
    """Frozen, ordered lowering of one EfficientViT configuration."""
    cfg: EfficientViTConfig
    batch: int
    image_size: int
    sites: Tuple[Site, ...]

    def site(self, name: str) -> Site:
        for s in self.sites:
            if s.name == name:
                return s
        raise KeyError(name)

    def by_kind(self, *kinds: str) -> Tuple[Site, ...]:
        return tuple(s for s in self.sites if s.kind in kinds)

    def fusible(self) -> Tuple[Site, ...]:
        """Sites the kernel registry can route — the fusion-plan keys.
        Any non-structural kind qualifies, so new registered kinds are
        planned without touching this module."""
        return tuple(s for s in self.sites
                     if s.kind not in STRUCTURAL_KINDS)

    def with_epilogues(self, plan) -> "Program":
        """The program annotated with the plan's epilogue assignments.

        Returns a NEW program whose sites carry their assigned
        ``Epilogue`` (``core.fusion.plan_program``'s producer->consumer
        pass); consumers of the epilogue *field* — the serving executor
        cache, the delivered-HBM accounting in ``core.fusion``, the
        cycle model — read it from here so the dtype each boundary
        actually delivers is inspectable from the program itself.
        """
        eps = getattr(plan, "epilogues", None) or {}
        sites = tuple(
            dataclasses.replace(s, epilogue=eps[s.name]) if s.name in eps
            else s for s in self.sites)
        return Program(self.cfg, self.batch, self.image_size, sites)


def params_at(params, path: Tuple[Any, ...]):
    """Resolve a ``Site.param_path`` against a param tree."""
    node = params
    for key in path:
        node = node[key]
    return node


# ---------------------------------------------------------------------------
# lower: cfg -> Program (the single architecture walk)
# ---------------------------------------------------------------------------

_SEQ_FIELDS = ("widths", "depths", "msa_scales", "head_widths",
               "stage_blocks", "expand_ratios")


def lower(cfg: EfficientViTConfig = B1, *, batch: int = 1,
          image_size: int | None = None) -> Program:
    """Lower a config to the frozen ``Site`` sequence.

    Cached (configs are frozen dataclasses): re-lowering inside a jit
    trace or a per-request loop is a dict lookup.  List-valued
    ``Sequence`` fields are normalized to tuples first so such configs
    stay usable (the cache hashes the config).
    """
    repl = {f: tuple(v) for f in _SEQ_FIELDS
            if not isinstance(v := getattr(cfg, f), (tuple, type(None)))}
    if repl:
        cfg = dataclasses.replace(cfg, **repl)
    return _lower(cfg, batch, image_size)


def _validate_geometry(sites: Tuple[Site, ...], size: int) -> None:
    """Geometry invariants for any (batch, resolution) lowering.

    The serving runtime lowers arbitrary resolutions, not just the
    config default, so the shape chain is checked here once instead of
    surfacing as a conv shape error deep inside a jitted executor: each
    site consumes exactly what its predecessor produced, residual sites
    are shape-preserving, and no spatial extent collapses to zero.

    Violations raise ``LoweringError`` (a ``ValueError`` subclass, for
    pre-existing callers) naming the offending site, so the serving
    layer's fault handling can type-dispatch on it and blame the site.
    """
    prev = None
    for s in sites:
        if any(dim <= 0 for dim in s.out_shape):
            raise LoweringError(
                f"site {s.name}: out_shape {s.out_shape} has a "
                f"non-positive dim at image_size={size}", site=s.name)
        if prev is not None and s.in_shape != prev.out_shape:
            raise LoweringError(
                f"geometry break at {prev.name} -> {s.name}: "
                f"{prev.out_shape} != {s.in_shape}", site=s.name)
        if s.residual and s.in_shape != s.out_shape:
            raise LoweringError(
                f"residual site {s.name} is not shape-preserving: "
                f"{s.in_shape} -> {s.out_shape}", site=s.name)
        prev = s


# stage_layout's block kinds -> Site kinds
_SITE_KIND = {"ds": "dsconv", "res": "resblock", "mb": "mbconv",
              "fmb": "fmbconv"}


@functools.lru_cache(maxsize=64)
def _lower(cfg: EfficientViTConfig, batch: int,
           image_size: int | None) -> Program:
    w = cfg.widths
    size = image_size or cfg.image_size
    B = batch
    if B < 1:
        raise LoweringError(f"batch must be >= 1, got {B}")
    if size % 32:
        raise LoweringError(
            f"image_size={size}: EfficientViT downsamples by 2 five "
            f"times (stem, S1, S2, S3.down, S4.down), so serving "
            f"resolutions must be multiples of 32 (192/224/256/...)")
    try:
        blocks = stage_layout(cfg)
        activation(cfg.act)
    except ValueError as e:
        raise LoweringError(f"{cfg.name}: {e}") from None
    if cfg.head_norm not in ("none", "ln"):
        raise LoweringError(f"{cfg.name}: head_norm={cfg.head_norm!r}, "
                            f"not 'none' or 'ln'")
    act = cfg.act
    sites: list[Site] = []
    r = size // 2

    sites.append(Site("stem.conv1", "conv_bn", "stem", ("stem_conv",),
                      (B, size, size, 3), (B, r, r, w[0]), stride=2,
                      act=act, attrs={"k": 3}))
    for b in blocks:
        ro = r // b.stride
        shapes = ((B, r, r, b.c_in), (B, ro, ro, b.c_out))
        name = f"{b.stage}.{b.name}"
        if b.kind == "att":
            sites.append(Site(
                f"{name}.msa", "msa", b.stage, b.path + ("msa",), *shapes,
                residual=True,
                attrs={"heads": b.c_in // cfg.head_dim,
                       "head_dim": cfg.head_dim,
                       "scales": tuple(cfg.msa_scales),
                       "n_branches": 1 + len(cfg.msa_scales)}))
            sites.append(Site(
                f"{name}.mb", "mbconv", b.stage, b.path + ("mbconv",),
                *shapes, residual=True, act=act,
                attrs={"mid": b.c_in * b.expand}))
        else:
            sites.append(Site(
                name, _SITE_KIND[b.kind], b.stage, b.path, *shapes,
                stride=b.stride, residual=b.residual, act=act,
                attrs={} if b.kind == "ds" else {"mid": b.c_in * b.expand}))
        r = ro
    hw1, hw2 = cfg.head_widths
    sites.append(Site("head.conv", "conv_bn", "head", ("head", "conv"),
                      (B, r, r, w[4]), (B, r, r, hw1), act=act,
                      attrs={"k": 1}))
    sites.append(Site("head.gap", "gap", "head", (),
                      (B, r, r, hw1), (B, hw1)))
    sites.append(Site("head.fc1", "fc", "head", ("head", "fc1"),
                      (B, hw1), (B, hw2), act=act,
                      attrs={"norm": "ln"} if cfg.head_norm == "ln"
                      else {}))
    sites.append(Site("head.fc2", "fc", "head", ("head", "fc2"),
                      (B, hw2), (B, cfg.num_classes)))
    _validate_geometry(tuple(sites), size)
    return Program(cfg, B, size, tuple(sites))


# ---------------------------------------------------------------------------
# execute: interpret the IR (reference ops + registry dispatch)
# ---------------------------------------------------------------------------

def _fc(p, h):
    if "qw" in p:
        from repro.core.quantization import matmul_int8
        return matmul_int8(h, p["qw"], p["scale"])
    y = jnp.einsum("bc,cf->bf", h, p["w"].astype(h.dtype))
    return y + p["b"].astype(h.dtype) if "b" in p else y


def _dispatch(site: Site, p, y, plan, cfg, kernel_ep):
    """Fusible site: registry kernel when the plan fuses it, else the
    reference block.

    A fused decision of any kind runs the registered impl's ``apply`` at
    the decision's precision.  Otherwise the built-in kinds run their
    reference block forward and any other registered kind its impl's
    ``ref``.  ``y`` may be a ``QTensor`` from the producer's epilogue
    (only ever assigned to fused int8 consumers); ``kernel_ep`` is the
    in-kernel part of this site's own epilogue (``None`` for fp output
    or a post-add policy, which ``execute`` applies after the residual).
    """
    from repro.core.quantization import act_fp

    d = plan.get(site.name) if plan is not None else None
    if d is not None and d.fused:
        from repro.kernels.registry import get_kernel
        # the kwarg is only passed when an epilogue is actually assigned,
        # so registered impls predating the epilogue contract stay
        # compatible
        ep_kw = {} if kernel_ep is None else {"epilogue": kernel_ep}
        impl = get_kernel(site.kind, d.precision)
        return impl.apply(p, y, site, d, interpret=plan.interpret, **ep_kw)
    y = act_fp(y)
    if site.kind == "msa":
        return msa(p, y, MSAConfig(site.in_shape[-1], site.attrs["head_dim"],
                                   site.attrs["scales"], cfg.dtype))
    if site.kind == "dsconv":
        return dsconv(p, y, stride=site.stride, act=site.act)
    if site.kind == "mbconv":
        return mbconv(p, y, stride=site.stride, act=site.act)
    if site.kind == "fmbconv":
        return fmbconv(p, y, stride=site.stride, act=site.act)
    from repro.kernels.registry import get_probe
    return get_probe(site.kind).ref(p, y, site)


def _adds_residual(site: Site, plan) -> bool:
    """The site's fused kernel adds the residual in-kernel
    (``KernelImpl.adds_residual``), so ``execute`` must not add it again."""
    d = plan.get(site.name) if plan is not None else None
    if d is None or not d.fused:
        return False
    from repro.kernels.registry import get_kernel
    return getattr(get_kernel(site.kind, d.precision), "adds_residual",
                   False)


def execute(program: Program, params, x, *, plan=None, profile=None):
    """Run the lowered program.  x: (B, H, W, 3) -> (B, num_classes).

    ``plan`` is an optional ``core.fusion.FusionPlan`` (built by
    ``core.fusion.plan_program`` over the same ``Program``) routing
    fusible sites through the registry's Pallas megakernels at the
    precision each decision carries, and carrying the producer->consumer
    ``Epilogue`` assignments that make producers emit int8 activations
    for fused int8 consumers (``QTensor`` boundaries; residual adds stay
    fp per each epilogue's residual policy).  ``plan=None`` runs the
    reference ops.

    ``profile`` is an optional ``repro.obs.profile.SiteProfiler``: each
    site's output is blocked on (``block_until_ready``) at the site
    boundary and the wall-clock window recorded under the site name.
    That barrier serializes the pipeline, so profiled execution is for
    offline model-drift audits only — never the serving path, and never
    under jit (the barrier is meaningless on tracers).
    """
    from repro.core.quantization import act_fp, quantize_act

    cfg = program.cfg
    epilogues = getattr(plan, "epilogues", None) or {}
    # super-site groups (core.fusion's grouping pass): the whole member
    # chain runs as one launch, entered at the first member.  Disabled
    # under profiling (the drift report needs one wall-clock window PER
    # site).
    groups = (getattr(plan, "groups", None) or {}) \
        if profile is None else {}
    group_entry: dict[str, Any] = {}
    group_skip: set[str] = set()
    for g in groups.values():
        group_entry[g.members[0]] = g
        group_skip.update(g.members[1:])
    y = x
    for site in program.sites:
        if site.name in group_skip:
            continue
        if site.name in group_entry:
            g = group_entry[site.name]
            from repro.kernels.registry import get_kernel
            impl = get_kernel("supersite", g.precision)
            sup = SuperSite.of(program, g.members, name=g.name)
            exit_ep = epilogues.get(g.members[-1])
            y = impl.apply(params, y, sup, g, interpret=plan.interpret,
                           epilogue=exit_ep)
            continue
        if profile is not None:
            profile.begin(site)
        p = params_at(params, site.param_path) if site.param_path else None
        ep = epilogues.get(site.name)
        if site.kind == "conv_bn":
            y = conv_bn_act(p, y, stride=site.stride, act=site.act)
            if ep is not None and ep.emits_q:
                # structural producer: XLA fuses the act-quant into the
                # conv/BN epilogue — the boundary tensor is int8
                y = quantize_act(y, keep_fp=ep.residual != "none")
        elif site.kind == "gap":
            y = jnp.mean(act_fp(y), axis=(1, 2))
        elif site.kind == "fc":
            y = _fc(p, act_fp(y))
            if site.attrs.get("norm") == "ln":
                y = layernorm(p["ln"], y)
            if site.act:
                y = activation(site.act)(y)
        else:
            # the kernel only runs the epilogue itself for non-residual
            # sites; a residual producer's quantize applies post-add
            kernel_ep = ep if (ep is not None and ep.emits_q
                               and not site.residual) else None
            out = _dispatch(site, p, y, plan, cfg, kernel_ep)
            if site.residual and not _adds_residual(site, plan):
                s = act_fp(y) + act_fp(out)
                if ep is not None and ep.emits_q:   # "post-add" policy
                    y = quantize_act(s, keep_fp=True)
                else:
                    y = s
            else:
                y = out     # QTensor when the kernel ran its epilogue
        if profile is not None:
            y = profile.end(site, y)
    return y


# ---------------------------------------------------------------------------
# manifest: IR -> hardware op records (cycle model / fig6 / table2)
# ---------------------------------------------------------------------------

def _mbconv_records(site: Site) -> list[OpRecord]:
    _, H, _, C = site.in_shape
    _, Ho, _, F = site.out_shape
    mid = site.attrs["mid"]
    n = site.local_name
    return [
        OpRecord(site.stage, f"{n}.pw1", "pw", H, H, C, mid),
        OpRecord(site.stage, f"{n}.dw", "dw", Ho, Ho, mid, mid, 3,
                 fused_with_prev=False),
        OpRecord(site.stage, f"{n}.pw2", "pw", Ho, Ho, mid, F,
                 fused_with_prev=True),
    ]


def _dense_pair_records(site: Site) -> list[OpRecord]:
    """FusedMBConv (3x3 conv C -> mid at the stride, 1x1 mid -> F) and
    ResBlock (3x3 C -> mid, 3x3 mid -> F): two dense convs each."""
    _, _, _, C = site.in_shape
    _, Ho, _, F = site.out_shape
    mid = site.attrs["mid"]
    n = site.local_name
    k2 = 3 if site.kind == "resblock" else 1
    return [
        OpRecord(site.stage, f"{n}.conv1", "conv", Ho, Ho, C, mid, 3),
        OpRecord(site.stage, f"{n}.conv2", "conv" if k2 > 1 else "pw",
                 Ho, Ho, mid, F, k2, fused_with_prev=k2 == 1),
    ]


def _msa_records(site: Site) -> list[OpRecord]:
    _, r, _, c = site.in_shape
    heads, head_dim = site.attrs["heads"], site.attrs["head_dim"]
    scales = site.attrs["scales"]
    total = heads * head_dim
    n_tok = r * r
    n_scales = 1 + len(scales)
    pre = site.local_name[:-len(".msa")]         # "evit{bi}"
    ops = [OpRecord(site.stage, f"{pre}.qkv", "pw", r, r, c, 3 * total)]
    for s in scales:
        ops.append(OpRecord(site.stage, f"{pre}.agg{s}.dw", "dw", r, r,
                            3 * total, 3 * total, s))
        # grouped 1x1: reduction = channels per group
        ops.append(OpRecord(site.stage, f"{pre}.agg{s}.pw", "group_pw",
                            r, r, head_dim, 3 * total, fused_with_prev=True))
    # ReLU(K)^T V : per head d x d state over n_tok tokens
    ops.append(OpRecord(site.stage, f"{pre}.ktv", "matmul",
                        n_scales * heads * head_dim, 1, n_tok, head_dim))
    # ReLU(Q) @ [KtV | ksum]: fused with previous on MAT engine
    ops.append(OpRecord(site.stage, f"{pre}.qz", "matmul",
                        n_scales * heads * n_tok, 1, head_dim,
                        head_dim + 1, fused_with_prev=True))
    ops.append(OpRecord(site.stage, f"{pre}.proj", "pw", r, r,
                        n_scales * total, c))
    return ops


def site_records(program: Program) -> list[Tuple[Site, list[OpRecord]]]:
    """Per-site hardware op records: ``[(site, [ops...]), ...]``.

    The grouped form of ``manifest``: every ``fused_with_prev`` pairing
    the cycle model exploits is *within* one site's op list (the DW+PW
    of a DSConv, the DW+PW2 of an MBConv, the KtV+QZ and agg DW+PW of
    an MSA module), never across a site boundary — so scheduling each
    site's ops independently and concatenating is exactly equivalent to
    scheduling the flat manifest.  That equivalence is what lets the
    offline schedule search (``repro.search``) attribute cycles and
    DRAM bytes to individual sites and re-cost them under per-site
    fusion/precision decisions.
    """
    out: list[Tuple[Site, list[OpRecord]]] = []
    for site in program.sites:
        ops: list[OpRecord] = []
        if site.kind == "conv_bn":
            _, _, _, C = site.in_shape
            _, r, _, F = site.out_shape
            k = site.attrs.get("k", 1)
            kind = "conv" if k > 1 else "pw"
            ops.append(OpRecord(site.stage, site.local_name, kind, r, r, C,
                                F, k))
        elif site.kind == "dsconv":
            _, r, _, C = site.in_shape
            F = site.out_shape[-1]
            n = site.local_name
            ops.append(OpRecord(site.stage, f"{n}.dw", "dw", r, r, C, C, 3))
            ops.append(OpRecord(site.stage, f"{n}.pw", "pw", r, r, C, F,
                                fused_with_prev=True))
        elif site.kind == "mbconv":
            ops.extend(_mbconv_records(site))
        elif site.kind in ("fmbconv", "resblock"):
            ops.extend(_dense_pair_records(site))
        elif site.kind == "msa":
            ops.extend(_msa_records(site))
        elif site.kind == "fc":
            ops.append(OpRecord(site.stage, site.local_name, "matmul", 1, 1,
                                site.in_shape[-1], site.out_shape[-1]))
        # gap: no MACs, no record (legacy manifest had none either)
        out.append((site, ops))
    return out


def manifest(program: Program) -> list[OpRecord]:
    """Expand the IR into per-hardware-op records (one inference; the
    batch dim is excluded)."""
    return [op for _, ops in site_records(program) for op in ops]
