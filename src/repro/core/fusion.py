"""Fusion planning: freeze per-site kernel routing for one ``Program``.

This is the software analogue of the paper's TMP dataflow compiler pass
(and of CHOSEN's compile-time optimization stack, arXiv 2407.12736):
``plan_program`` runs ONE generic loop over the lowered IR's fusible
sites (``core.program.lower``), consulting the kernel registry
(``repro.kernels.registry``) for each — which precision the site's
params support, whether the shapes fit the kernel's VMEM budget, and
which autotuned block sizes to freeze.  The jitted forward
(``core.program.execute``) then consults the frozen plan — dispatch is
pure table lookup, no tracing-time tuning.

Precision is a first-class dispatch axis, not a bail-out: a FIX8 tree
(``core.quantization.quantize_efficientvit``) routes to the int8
megakernels — int8 weights resident in VMEM, int32 MXU accumulation,
in-kernel requantization between stages — exactly the paper's 8x8-bit PE
array fed by the TMP dataflow (§III/§IV-A; ME-ViT arXiv 2402.09709 shows
the same single-load + low-precision pairing is where the memory win
lives).

Fusible sites (= ``Program.fusible()``, the IR is the source of truth):
  * ``stem.ds{i}``            DSConv        -> kernels/dsconv  (DW+PW)
  * ``S{1,2}.mb{i}``          MBConv        -> kernels/mbconv  (PW+DW+PW)
  * ``S{3,4}.down``           MBConv        -> kernels/mbconv
  * ``S{3,4}.evit{i}.mb``     MBConv        -> kernels/mbconv
  * ``S{3,4}.evit{i}.msa``    MSA module    -> kernels/relu_attn (+
                              kernels/int8_matmul projections for FIX8)
  * L series: ``stem.res0`` ResBlock -> kernels/resblock (3x3 + 3x3 +
    residual); ``S{1,2}.down`` / ``S{1,2}.fmb{i}`` FusedMBConv ->
    kernels/fmbconv (dense 3x3 + PW); ``S3.down`` / ``S3.mb{i}`` /
    ``S4.down`` MBConv

Anything that fails a check runs the reference path; ``execute`` with
``plan=None`` is the reference forward.
"""
from __future__ import annotations

import dataclasses
from typing import Mapping

__all__ = ["SiteDecision", "SiteOverride", "GroupDecision", "FusionPlan",
           "plan_program", "plan_report", "report_dict", "launch_counts",
           "site_traffic", "EXPECTED_B1_SUPERSITE_LAUNCHES",
           "EXPECTED_B1_SUPERSITE_LAUNCHES_INT8"]

# Drift gate: fused launches of EfficientViT-B1 @224.  B1 has 22
# fusible sites (1 stem DSConv + 2+3 MBConv + 2 downsamples + (3+4) x
# (MSA + MBConv)); the super-site pass (``kernels/supersite``) collapses
# S1 [mb0, mb1] (-1 launch) and S2 [mb0, mb1, mb2] (-2) into one launch
# each — stem.ds0 is a run of one and the S3/S4 conv sites interleave
# with MSA sites, so no other run qualifies.  At int8 each fused MSA
# site adds one grouped aggregation launch per scale (kernels/
# group_conv): 7 msa x 1 scale.  benchmarks/e2e_latency.py and
# tests/test_program.py fail if a change moves either number without an
# explicit expectation update here.
EXPECTED_B1_SUPERSITE_LAUNCHES = 19           # 22 - 3
EXPECTED_B1_SUPERSITE_LAUNCHES_INT8 = 26      # 19 + 7


@dataclasses.dataclass(frozen=True)
class SiteDecision:
    name: str              # e.g. "S3.evit0.msa"
    kind: str              # dsconv | mbconv | msa
    fused: bool
    reason: str            # "ok" | "vmem" | "quantized" | "not-quantized"
    #                        | "mixed"
    #                        | "fault" (demoted by the degradation ladder)
    #                        | "search" (demoted by an offline-searched
    #                          schedule override, repro.search)
    blocks: Mapping[str, int] = dataclasses.field(default_factory=dict)
    shape: tuple = ()      # (B, H, W, C, mid, F, stride) / (BH, N, D, S, C)
    precision: str = "fp"  # "fp" | "int8" — which kernel family runs
    reused: bool = False   # blocks inherited from a donor plan (no re-tune)
    epilogue: object = None   # core.program.Epilogue for this site's OWN
    #                           output (producer side), None -> fp
    q_in: bool = False     # the producer's epilogue delivers this site's
    #                        input already quantized (int8 boundary)
    group: str = ""        # super-site membership ("" = ungrouped): the
    #                        grouping pass stamps members with the
    #                        ``GroupDecision`` name so artifacts and the
    #                        accounting can reconstruct the fusion groups

    def to_dict(self) -> dict:
        """JSON-serializable form (schedule artifacts, benchmark dumps)."""
        ep = self.epilogue
        return {
            "name": self.name, "kind": self.kind, "fused": self.fused,
            "reason": self.reason, "blocks": dict(self.blocks),
            "shape": list(self.shape), "precision": self.precision,
            "reused": self.reused, "q_in": self.q_in, "group": self.group,
            "epilogue": None if ep is None else {
                "out_dtype": ep.out_dtype, "scale": ep.scale,
                "residual": ep.residual},
        }


@dataclasses.dataclass(frozen=True)
class SiteOverride:
    """One site's entry in an externally supplied schedule.

    The injection lever of the offline schedule search
    (``repro.search``): ``plan_program(overrides={name: SiteOverride})``
    consults the override *before* its own policy, so a searched — or
    artifact-shipped — schedule decides routing instead of the
    tuner/heuristics:

      ``fused=False``       pin the site to the reference path (reason
                            ``reason``, default ``"search"``);
      ``fused=True``/None   plan normally, but with ``precision`` (when
                            set) as this site's requested precision and
                            ``blocks`` (when set) frozen verbatim — the
                            tuner is never consulted, which is what
                            makes artifact-warm cold starts sweep-free.

    The VMEM budget check still runs for fused overrides: an override
    can only choose among safe schedules, never force an unlaunchable
    tile into a plan.
    """
    fused: bool | None = None
    precision: str | None = None      # None -> the plan-level request
    blocks: Mapping[str, int] | None = None   # None -> donor/tuner path
    reason: str = "search"
    group_break: bool | None = None   # True: the super-site grouping pass
    #                                   must not extend a chain ACROSS this
    #                                   site (it may still START one here) —
    #                                   the search's split/merge lever over
    #                                   fusion-group boundaries

    @classmethod
    def from_decision(cls, d: "SiteDecision | dict") -> "SiteOverride":
        """Pin a previously frozen decision (e.g. a ``ScheduleArtifact``
        entry) so replanning reproduces it.  ``group_break`` is left for
        the artifact's own group post-pass (``ScheduleArtifact.
        overrides_for``) — a single decision row cannot know its run
        context."""
        if isinstance(d, SiteDecision):
            d = d.to_dict()
        return cls(fused=bool(d["fused"]),
                   precision=d.get("precision"),
                   blocks=dict(d.get("blocks") or {}),
                   reason=d.get("reason", "search"))


@dataclasses.dataclass(frozen=True)
class GroupDecision:
    """One super-site fusion group frozen into a plan: ``members`` name
    the consecutive conv sites the executor collapses into a single
    ``kernels/supersite`` launch (``core.program.SuperSite.of`` re-derives
    the validated chain from the program at execute time)."""
    name: str                 # e.g. "S1.ss0"
    members: tuple            # member site names, program order
    precision: str = "fp"     # uniform across the chain
    blocks: Mapping[str, int] = dataclasses.field(default_factory=dict)
    #                           fp: {"block_rows": R}; int8: {} (whole-map)
    shape: tuple = ()         # in_shape + out_shape of the chain
    kind: str = "supersite"

    def to_dict(self) -> dict:
        return {"name": self.name, "members": list(self.members),
                "precision": self.precision, "blocks": dict(self.blocks),
                "shape": list(self.shape), "kind": self.kind}


@dataclasses.dataclass(frozen=True)
class FusionPlan:
    decisions: Mapping[str, SiteDecision]
    interpret: bool | None = None   # None -> backend auto-detect
    # producer-side output epilogues by site name — includes STRUCTURAL
    # producers (e.g. a quantized stem conv feeding a fused int8 DSConv),
    # which have no SiteDecision of their own
    epilogues: Mapping[str, object] = dataclasses.field(default_factory=dict)
    # super-site fusion groups by group name (the grouping pass's output;
    # member decisions carry the back-pointer in ``SiteDecision.group``)
    groups: Mapping[str, GroupDecision] = dataclasses.field(
        default_factory=dict)

    def get(self, name):
        return self.decisions.get(name)

    def n_fused(self) -> int:
        return sum(d.fused for d in self.decisions.values())

    def table(self) -> str:
        """Markdown routing table (EXPERIMENTS.md / benchmark output)."""
        rows = ["| site | kind | route | precision | blocks | reason |",
                "|------|------|-------|-----------|--------|--------|"]
        for d in self.decisions.values():
            route = "fused" if d.fused else "reference"
            blocks = ",".join(f"{k}={v}" for k, v in d.blocks.items()) or "-"
            rows.append(f"| {d.name} | {d.kind} | {route} | {d.precision} "
                        f"| {blocks} | {d.reason} |")
        return "\n".join(rows)


# ---------------------------------------------------------------------------
# the planner: ONE loop over Program.fusible(), all policy in the registry
# ---------------------------------------------------------------------------

def decision_shape(site) -> tuple:
    """A ``Site`` -> the legacy ``SiteDecision.shape`` tuple the analytic
    accounting consumes: conv kinds (B, H, W, C, mid, F, stride); msa
    (BH, n_tok, head_dim, n_branches, channels)."""
    if site.kind == "msa":
        B, H, W, C = site.in_shape
        bh = site.attrs["n_branches"] * B * site.attrs["heads"]
        return (bh, H * W, site.attrs["head_dim"],
                site.attrs["n_branches"], C)
    if len(site.in_shape) == 4:
        B, H, W, C = site.in_shape
        F = site.out_shape[-1]
        mid = site.attrs.get("mid", C)
        return (B, H, W, C, mid, F, site.stride)
    # registered non-builtin kind with an unconventional layout
    return tuple(site.in_shape) + tuple(site.out_shape)


def _reusable_blocks(reuse, site, prec, impl):
    """Donor blocks for this site, or None if no safe donor exists.

    A donor decision qualifies when it fused the *same-named* site at
    the same precision with identical per-sample geometry — everything
    in the decision shape except the leading batch axis (the image
    batch for conv kinds, the folded branch*batch*head axis for msa).
    Batch is exactly the axis serving buckets vary, so a donor plan from
    another bucket at the same resolution shares its tuned blocks and
    the new bucket skips the tuner entirely.

    A kernel family that declares ``batch_dependent_tiles`` (its tuner
    keys tiles on the batch axis too) drops the donor match down to the
    EXACT shape including batch: handing one bucket's batch-tuned block
    to another bucket would freeze a stale tile into the new plan.
    """
    d = reuse.get(site.name) if reuse is not None else None
    if (d is None or not d.fused or d.kind != site.kind
            or d.precision != prec):
        return None
    shape = decision_shape(site)
    if getattr(impl, "batch_dependent_tiles", False):
        if tuple(d.shape) != tuple(shape):
            return None
    elif tuple(d.shape[1:]) != tuple(shape[1:]):
        return None
    return dict(d.blocks)


def _decide(site, params, *, autotune, interpret, precision,
            reuse=None, override=None):
    from repro.kernels.registry import get_kernel, get_probe

    shape = decision_shape(site)
    if override is not None and override.fused is False:
        return SiteDecision(site.name, site.kind, False, override.reason,
                            shape=shape,
                            precision=override.precision or "fp")
    if override is not None and override.precision is not None:
        precision = override.precision
    probe = get_probe(site.kind)          # precision policy is per-kind
    prec, fail = probe.resolve_precision(probe.site_precision(params),
                                         precision)
    if fail is not None:
        return SiteDecision(site.name, site.kind, False, fail, shape=shape)
    impl = get_kernel(site.kind, prec)
    if impl.vmem_bytes(site) > impl.vmem_budget:
        return SiteDecision(site.name, site.kind, False, "vmem",
                            shape=shape, precision=prec)
    if override is not None and override.blocks is not None:
        # searched/artifact blocks are frozen verbatim: no tuner
        # consultation at all, which is the artifact-warm zero-sweep
        # guarantee (the blocks were validated when the search built
        # the schedule against this exact config hash)
        return SiteDecision(site.name, site.kind, True, "ok",
                            dict(override.blocks), shape, precision=prec)
    blocks = _reusable_blocks(reuse, site, prec, impl)
    reused = blocks is not None
    if not reused:
        blocks = impl.tune(site, autotune=autotune, interpret=interpret)
    return SiteDecision(site.name, site.kind, True, "ok", blocks, shape,
                        precision=prec, reused=reused)


# ---------------------------------------------------------------------------
# producer->consumer epilogue assignment (the int8 dataflow)
# ---------------------------------------------------------------------------

def assign_epilogues(program, params, decisions):
    """One pass over consecutive (producer, consumer) site pairs.

    A consumer *wants* an int8 input when it is a fused int8 site whose
    kernel family consumes quantized activations (``KernelImpl.
    takes_q``) — or a structural conv whose params are quantized (the
    ``conv2d_int8`` path).  A producer *can* emit one when it is a fused
    int8 site whose kernel implements the act-quant epilogue
    (``KernelImpl.emits_q``) — or a structural quantized conv, whose
    emission XLA fuses into the conv+BN computation.  When both hold,
    the producer gets an ``Epilogue(out_dtype="int8")`` with the
    residual policy the pair needs: ``"post-add"`` when the producer
    itself is residual (its fp add runs first, quantization after),
    ``"keep-fp"`` when the consumer is residual (its fp add needs the
    unquantized activation alongside), ``"none"`` otherwise — the pure
    1 byte/element boundary.

    Returns ``(epilogues, q_in)``: the site-name -> Epilogue map (which
    includes structural producers) and the set of consumer names whose
    input arrives quantized.
    """
    from repro.core.program import Epilogue, params_at
    from repro.kernels.registry import get_kernel

    def _quantized_conv(site):
        if site.kind != "conv_bn" or not site.param_path:
            return False
        p = params_at(params, site.param_path)
        return isinstance(p, dict) and "qconv" in p

    def _fused_int8(site):
        d = decisions.get(site.name)
        return d is not None and d.fused and d.precision == "int8"

    def _consumes_q(site):
        if site.kind == "conv_bn":
            return _quantized_conv(site)
        return _fused_int8(site) and getattr(
            get_kernel(site.kind, "int8"), "takes_q", False)

    def _emits_q(site):
        if site.kind == "conv_bn":
            return _quantized_conv(site)
        return _fused_int8(site) and getattr(
            get_kernel(site.kind, "int8"), "emits_q", False)

    epilogues: dict[str, object] = {}
    q_in: set[str] = set()
    for prod, cons in zip(program.sites, program.sites[1:]):
        if not (_consumes_q(cons) and _emits_q(prod)):
            continue
        residual = ("post-add" if prod.residual
                    else "keep-fp" if cons.residual else "none")
        epilogues[prod.name] = Epilogue("int8", "dynamic", residual)
        q_in.add(cons.name)
    return epilogues, q_in


def _group_supersites(program, decisions, overrides):
    """The inter-layer super-site pass: maximal runs of consecutive,
    same-stage, uniform-precision fused conv sites -> ``GroupDecision``
    fusion groups, each executed as ONE ``kernels/supersite`` launch.

    Runs AFTER epilogue assignment (the int8 fit check needs the exit
    member's epilogue) and mutates ``decisions`` in place: members are
    stamped with ``group=<name>``; an fp site the per-site pass demoted
    for VMEM (reason ``"vmem"``) is *rescued* into a group when the
    banded chain fits — spatial tiling is exactly what the lone whole-map
    kernel lacked — and becomes ``fused=True, reason="ok"``.  Any other
    demotion reason (``"fault"``, ``"search"``, precision mismatches)
    excludes the site and splits the run around it, which is
    how the serving degradation ladder's per-site demotions break groups
    back into member launches instead of falling to reference wholesale.
    """
    from repro.core.program import SUPERSITE_KINDS, SuperSite
    from repro.kernels.supersite.ops import (
        VMEM_BUDGET_BYTES, choose_block_rows, supersite_vmem_bytes_int8)

    overrides = overrides or {}
    groups: dict[str, GroupDecision] = {}
    counters: dict[str, int] = {}
    run: list = []                       # [(site, decision), ...]

    def member_decision(site):
        if site.kind not in SUPERSITE_KINDS:
            return None
        d = decisions.get(site.name)
        if d is None:
            return None
        if d.fused and d.reason == "ok":
            return d
        # the VMEM rescue: only fp — int8 grouping is whole-map, so a
        # site that didn't fit alone won't fit inside a chain either,
        # and the epilogue pass already planned around its fp boundary
        if not d.fused and d.reason == "vmem" and d.precision == "fp":
            return d
        return None

    def flush():
        nonlocal run
        members, run = run, []
        if len(members) < 2:
            return
        names = tuple(s.name for s, _ in members)
        prec = members[0][1].precision
        sup = SuperSite.of(program, names)       # validates the chain
        if prec == "fp":
            rows = choose_block_rows(sup)
            if rows is None:
                return                           # no band height fits
            blocks = {"block_rows": rows}
        else:
            ep = members[-1][1].epilogue
            keep_fp = (ep is not None and ep.emits_q
                       and ep.residual != "none")
            if supersite_vmem_bytes_int8(
                    sup, keep_fp=keep_fp) > VMEM_BUDGET_BYTES:
                return                           # whole-map doesn't fit
            blocks = {}
        stage = names[0].split(".", 1)[0]
        i = counters.get(stage, 0)
        counters[stage] = i + 1
        gname = f"{stage}.ss{i}"
        groups[gname] = GroupDecision(
            gname, names, precision=prec, blocks=blocks,
            shape=tuple(sup.in_shape) + tuple(sup.out_shape))
        for s, d in members:
            decisions[s.name] = dataclasses.replace(
                d, fused=True, reason="ok", group=gname)

    prev_stage = None
    for site in program.sites:
        d = member_decision(site)
        if d is None:
            flush()
            prev_stage = None
            continue
        ov = overrides.get(site.name)
        stage = site.name.split(".", 1)[0]
        if run and (bool(getattr(ov, "group_break", None))
                    or stage != prev_stage
                    or d.precision != run[0][1].precision):
            flush()
        run.append((site, d))
        prev_stage = stage
    flush()
    return groups


def plan_program(program, params, *, autotune: bool = True,
                 interpret: bool | None = None, precision: str = "auto",
                 reuse: FusionPlan | None = None, demote=(),
                 overrides: Mapping[str, SiteOverride] | None = None
                 ) -> FusionPlan:
    """Freeze per-site routing for a lowered ``core.program.Program``.

    Three passes, always in this order:

    1. per-site decisions (``_decide``): each fusible site runs its
       registered kernel family at the resolved precision, or the
       reference path with a reason;
    2. the producer->consumer epilogue pass (``assign_epilogues``):
       producers of fused int8 consumers get an int8 ``Epilogue``, so
       the executed program delivers 1 byte/element activation
       boundaries (residual adds stay fp); fp plans assign none;
    3. the inter-layer grouping pass (``_group_supersites``): maximal
       runs of >=2 consecutive fused conv sites of one stage collapse
       into single-launch ``FusionPlan.groups`` with the chain's weights
       packed VMEM-resident once per launch.

    ``precision``: "auto" (default) matches each site's params — fp32
    trees run the fp megakernels, ``quantize_efficientvit`` trees run
    the FIX8 ones; "fp"/"int8" force one family and demote mismatched
    sites to the reference path.  ``interpret=None`` auto-detects the
    backend (compile on TPU, interpret elsewhere).

    ``reuse``: an optional donor ``FusionPlan`` (typically another batch
    bucket at the same resolution, built by the serving executor cache).
    Sites whose per-sample geometry matches a fused donor decision
    inherit its block choices without consulting the tuner — their
    decisions carry ``reused=True``.  Sites with no safe donor (other
    resolution, precision mismatch, donor fell back, or an exact-batch
    mismatch for a ``batch_dependent_tiles`` kernel family) tune
    normally.

    ``demote``: site names forced to the reference path with reason
    ``"fault"`` — the serving degradation ladder's lever: after a fused
    launch or plan failure blamed on one site, the executor rebuilds
    its plan with exactly that site demoted while every other site
    stays fused.  A demoted member splits its super-site group.

    A failure inside one site's decision (an autotune sweep crash, a
    registry probe raising) is re-raised as a typed
    ``common.errors.PlanError`` naming the site, so the serving layer
    can blame — and demote — exactly the offending site.

    ``overrides``: an optional ``{site name: SiteOverride}`` schedule —
    the offline schedule search's injection point (``repro.search``).
    An override wins over the tuner/heuristics for its site: it can pin
    the site to the reference path, force a precision, freeze block
    sizes verbatim (no tuner consultation) and, with ``group_break``,
    split a super-site run at that site.  ``demote`` still wins over an
    override — a fault-ladder demotion must not be resurrected by a
    stale artifact.

    Runs outside jit: autotune sweeps (when ``autotune=True`` and the
    cache is cold) time the real kernels on synthetic inputs here, never
    at trace time.
    """
    from repro.common.errors import PlanError, ReproError
    from repro.core.program import params_at
    from repro.kernels.compat import default_interpret

    assert precision in ("auto", "fp", "int8"), precision
    interpret = default_interpret(interpret)
    demote = frozenset(demote)
    decisions: dict[str, SiteDecision] = {}
    for site in program.fusible():
        if site.name in demote:
            decisions[site.name] = SiteDecision(
                site.name, site.kind, False, "fault",
                shape=decision_shape(site))
            continue
        try:
            decisions[site.name] = _decide(
                site, params_at(params, site.param_path),
                autotune=autotune, interpret=interpret,
                precision=precision, reuse=reuse,
                override=(overrides or {}).get(site.name))
        except Exception as e:
            site_name = getattr(e, "site", None) if isinstance(
                e, ReproError) else None
            raise PlanError(f"planning {site.name} failed: {e}",
                            site=site_name or site.name) from e
    ep_map, q_in = assign_epilogues(program, params, decisions)
    for name, d in decisions.items():
        ep = ep_map.get(name)
        arrives_q = name in q_in
        if ep is not None or arrives_q:
            decisions[name] = dataclasses.replace(
                d, epilogue=ep, q_in=arrives_q)
    groups = _group_supersites(program, decisions, overrides)
    return FusionPlan(decisions=decisions, interpret=interpret,
                      epilogues=ep_map, groups=groups)


# ---------------------------------------------------------------------------
# analytic accounting (feeds benchmarks/e2e_latency.py + EXPERIMENTS.md)
# ---------------------------------------------------------------------------

def _mbconv_bytes(B, H, W, C, mid, F, stride, precision="fp"):
    """Activation HBM bytes: unfused = every op round-trips HBM (read
    inputs, write output; the reference FIX8 chain dequantizes to fp32
    between ops, so unfused bytes are fp32 either way); fused = x in
    once (int8 for the FIX8 kernel), out once (fp32).

    The 1-byte int8 input is the steady-state FIX8 pipeline number: it
    assumes the producer emits (or its epilogue fuses) the int8
    activation, as on the paper's accelerator (``plan_program``'s
    epilogue pass; ``hbm_delivered`` in ``plan_report`` counts what the
    executed boundaries actually move)."""
    Ho, Wo = H // stride, W // stride
    xn = B * H * W * C
    midn = B * H * W * mid
    dwn = B * Ho * Wo * mid
    outn = B * Ho * Wo * F
    unfused = (xn + 2 * midn + 2 * dwn + outn) * 4   # both intermediates r/w
    fused = xn * (1 if precision == "int8" else 4) + outn * 4
    return unfused, fused


def _dsconv_bytes(B, H, W, C, F, precision="fp"):
    xn = B * H * W * C
    outn = B * H * W * F
    unfused = (2 * xn + xn + outn) * 4
    fused = xn * (1 if precision == "int8" else 4) + outn * 4
    return unfused, fused


def _msa_bytes(BH, N, D):
    """Per-module attention-core traffic (all branches/heads folded).

    Unfused reference dataflow materializes ReLU(Q)/ReLU(K), the KV
    state, the numerator and the divisor in HBM between ops; the fused
    single-pass kernel reads Q/K/V once and writes the output once.
    (The attention core runs fp32 at either precision — the FIX8 win on
    MSA sites is in the projection weights, counted separately.)
    """
    u = BH * N * D * 4                 # one (N, D) activation per head-fold
    state = BH * (D * D + D) * 4
    den = BH * N * 4
    unfused = (3 * u            # q, k, v in
               + 4 * u          # relu(Q), relu(K) write + read back
               + 2 * state      # KV state + ksum write + read
               + 2 * u          # numerator write + read
               + 2 * den        # divisor write + read
               + u)             # out
    fused = 3 * u + u
    return unfused, fused


def _weight_bytes(kind, shape, precision) -> int:
    """HBM weight bytes per launch at the site's precision.

    Weights are re-read from HBM every launch, so FIX8 cuts this 4x —
    the dominant term for the late, weight-heavy stages at batch 1
    (exactly the paper's motivation for 8-bit storage)."""
    per = 1 if precision == "int8" else 4
    if kind == "mbconv":
        _, _, _, C, mid, F, _ = shape
        n = C * mid + 9 * mid + mid * F
    elif kind == "dsconv":
        _, _, _, C, _, F, _ = shape
        n = 9 * C + C * F
    else:                                          # msa: qkv + proj
        _, _, _, n_branches, C = shape
        n = 3 * C * C + n_branches * C * C
    return n * per


def _site_accounting(kind, shape, precision):
    """(hbm_unfused, hbm_fused, weight_bytes, (launches_ref, fused))."""
    if kind == "mbconv":
        B, H, W, C, mid, F, stride = shape
        unf, fus = _mbconv_bytes(B, H, W, C, mid, F, stride, precision)
        launches = (3, 1)
    elif kind == "dsconv":
        B, H, W, C, _, F, _ = shape
        unf, fus = _dsconv_bytes(B, H, W, C, F, precision)
        launches = (2, 1)
    elif kind == "msa":
        BH, N, D, n_branches = shape[:4]
        unf, fus = _msa_bytes(BH, N, D)
        # FIX8: the multi-scale aggregation convs run the grouped int8
        # Pallas kernel (kernels/group_conv) — one fused launch per
        # scale next to the single attention-core launch; at fp they
        # remain XLA convs (uncounted, like the reference path's)
        fused_launches = n_branches if precision == "int8" else 1
        launches = (2 * n_branches, fused_launches)  # old per-branch 2-pass
    else:
        # registered non-builtin kind: no analytic byte model yet —
        # count one launch either way, contribute zero bytes rather
        # than guessing (plan_report totals stay additive)
        return 0, 0, 0, (1, 1)
    return unf, fus, _weight_bytes(kind, shape, precision), launches


def _delivered_bytes(kind, shape, fused, unf, fus, q_in, epilogue):
    """Activation bytes the executed program ACTUALLY moves at this
    site, derived from the epilogue assignments (not the steady-state
    assumption): the input boundary is 1 byte/element only when the
    producer's epilogue emitted it (``q_in``); the output boundary is
    what this site's own epilogue writes — int8 (1), fp (4), or both
    (5: the residual-fp correction).  Conv kinds only; the MSA core
    accounting (and unknown kinds) is precision-independent and passes
    through the analytic number.
    """
    if not fused or kind not in ("mbconv", "dsconv"):
        return fus if fused else unf
    B, H, W, C, _, F, stride = shape
    xn = B * H * W * C
    # same output geometry as _mbconv_bytes/_dsconv_bytes respectively
    outn = (B * (H // stride) * (W // stride) * F if kind == "mbconv"
            else B * H * W * F)
    in_b = xn * (1 if q_in else 4)
    if epilogue is None or not epilogue.emits_q:
        out_b = outn * 4
    else:
        out_b = outn * (1 + (4 if epilogue.keeps_fp else 0))
    return in_b + out_b


def site_traffic(site, *, precision: str = "fp", q_in: bool = False) -> dict:
    """Analytic HBM/launch accounting straight from a ``Site`` — the
    registry-side twin of ``plan_report`` rows, used to assert the two
    derivations (IR geometry vs frozen decision shapes) cannot drift.

    The delivered column reads the site's OWN ``epilogue`` field (use a
    plan-annotated program, ``Program.with_epilogues``) plus ``q_in``
    for the input side, since the input boundary's dtype lives on the
    producer's epilogue."""
    shape = decision_shape(site)
    unf, fus, w_bytes, launches = _site_accounting(
        site.kind, shape, precision)
    ep = site.epilogue if site.epilogue.emits_q else None
    return {"site": site.name, "kind": site.kind, "hbm_unfused": unf,
            "hbm_fused": fus, "hbm_w": w_bytes,
            "hbm_delivered": _delivered_bytes(site.kind, shape, True, unf,
                                              fus, q_in, ep),
            "launches_ref": launches[0], "launches_fused": launches[1]}


def plan_report(plan: FusionPlan) -> list[dict]:
    """Per-site analytic HBM bytes (unfused vs fused) + launch counts.

    ``hbm_fused`` stays the steady-state analytic number (1 byte/element
    int8 fused-site input, fp32 out); ``hbm_delivered`` is what the
    executed program moves given the plan's epilogue assignments — the
    two agree within the residual-fp correction once producer-side
    emission covers the chain, which is exactly what
    ``benchmarks/e2e_latency.py`` gates.

    Super-site members (``SiteDecision.group``) report what the single
    chain launch actually moves: the group's one launch lands on its
    FIRST member's row (0 for the rest); ``hbm_delivered`` is the chain
    entry boundary on the first member, 0 for interior members (their
    boundaries live in VMEM), and the exit boundary (per the exit
    epilogue) on the last.  ``hbm_w`` keeps the per-site weight bytes —
    the resident pack reads each member's weights exactly once per
    launch, same total as the per-site convention.
    """
    first_of, last_of = {}, {}
    for g in plan.groups.values():
        first_of[g.members[0]] = g
        last_of[g.members[-1]] = g
    rows = []
    for d in plan.decisions.values():
        unf, fus, w_bytes, launches = _site_accounting(d.kind, d.shape,
                                                       d.precision)
        hbm_fused = fus if d.fused else unf
        grouped = bool(d.group) and d.kind in ("mbconv", "dsconv")
        if grouped:
            launches_fused = 1 if d.name in first_of else 0
            B, H, W, C, _, F, stride = d.shape
            delivered = 0
            if d.name in first_of:
                delivered += B * H * W * C * (1 if d.q_in else 4)
            if d.name in last_of:
                outn = (B * (H // stride) * (W // stride) * F
                        if d.kind == "mbconv" else B * H * W * F)
                ep = d.epilogue
                if ep is None or not ep.emits_q:
                    delivered += outn * 4
                else:
                    delivered += outn * (1 + (4 if ep.keeps_fp else 0))
        else:
            launches_fused = launches[1] if d.fused else launches[0]
            delivered = _delivered_bytes(d.kind, d.shape, d.fused,
                                         unf, fus, d.q_in, d.epilogue)
        rows.append({
            "site": d.name, "kind": d.kind, "fused": d.fused,
            "reason": d.reason, "precision": d.precision,
            "group": d.group,
            "hbm_unfused": unf, "hbm_fused": hbm_fused,
            "saving_x": unf / fus if d.fused and fus else 1.0,
            "hbm_w": w_bytes,
            "hbm_total": hbm_fused + w_bytes,
            "hbm_delivered": delivered,
            "q_in": d.q_in,
            "epilogue": d.epilogue,
            "launches_ref": launches[0],
            "launches_fused": launches_fused,
        })
    return rows


def report_dict(plan: FusionPlan) -> list[dict]:
    """``plan_report`` with every value JSON-serializable: the
    ``epilogue`` column rendered as a plain dict (via
    ``SiteDecision.to_dict``'s convention) instead of the dataclass.
    The machine-readable form benchmarks and the offline schedule
    search consume — no more hand-parsing of ``FusionPlan.table``."""
    rows = []
    for r in plan_report(plan):
        ep = r["epilogue"]
        rows.append({**r, "epilogue": None if ep is None else {
            "out_dtype": ep.out_dtype, "scale": ep.scale,
            "residual": ep.residual}})
    return rows


def launch_counts(plan: FusionPlan) -> dict:
    rep = plan_report(plan)
    return {
        "reference": sum(r["launches_ref"] for r in rep),
        "fused": sum(r["launches_fused"] for r in rep),
    }
