"""Partition-rule matching: regex-on-param-path -> PartitionSpec.

Params are nested dicts of arrays.  Each model family publishes a list of
``(path_regex, logical_axes)`` rules; :func:`match_partition_rules` walks
the param tree and produces a matching tree of ``PartitionSpec`` resolved
against the active logical->mesh mapping.  Resolution is divisibility-
aware: a mesh axis that does not divide a dim is *released* so a later dim
of the same tensor can claim it (e.g. grok-1 has 8 experts on a 16-way
model axis — expert dim demotes, d_ff picks the axis up instead).
Unmatched params are replicated.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.common.tree import match_first, tree_map_with_path_str
from repro.distributed.ctx import ShardingCtx

# Default logical->mesh rules for the production mesh.  ZeRO/FSDP-style
# parameter sharding rides the data axes, tensor parallel on "model",
# experts on "model" too (EP and TP share the axis; per-tensor dedup keeps
# a mesh axis from being used twice in one spec).
DEFAULT_RULES = {
    "dp": ("pod", "data"),      # batch / token dim of activations
    "fsdp": ("data",),          # ZeRO-sharded param dim
    "fsdp_pod": ("pod", "data"),  # ZeRO over every data-parallel rank
    "sp": None,                  # sequence parallel (enabled per-shape)
    "sp_kv": ("model",),        # decode-cache context (seq) sharding
    "tp": ("model",),           # tensor parallel
    "ep": ("model",),           # expert parallel
    "heads": ("model",),        # attention heads (activations)
    "vocab": ("model",),
}


def make_ctx(mesh: Mesh, overrides: Optional[dict] = None) -> ShardingCtx:
    rules = dict(DEFAULT_RULES)
    if overrides:
        rules.update(overrides)
    names = set(mesh.axis_names)
    for k, v in list(rules.items()):
        if v is None:
            continue
        if isinstance(v, str):
            v = (v,)
        kept = tuple(a for a in v if a in names)
        rules[k] = kept if kept else None
    return ShardingCtx(mesh=mesh, rules=rules)


def resolve_param_spec(ctx: ShardingCtx, logical: Sequence[Optional[str]],
                       shape: Sequence[int]) -> P:
    """Logical axes -> mesh PartitionSpec for one tensor, divisibility-aware.

    ``logical`` is RIGHT-ALIGNED against ``shape``: rules describe the
    trailing (semantic) dims, and any leading layer-stacking dims appear
    unsharded.  A mesh axis that does not divide its dim is released for
    later dims of the same tensor.
    """
    mesh_shape = dict(zip(ctx.mesh.axis_names, ctx.mesh.devices.shape))
    used: set[str] = set()
    out: list = []
    logical = tuple(logical)
    if len(logical) < len(shape):  # right-align
        logical = (None,) * (len(shape) - len(logical)) + logical
    for dim, name in zip(shape, logical):
        if name is None or ctx.rules.get(name) is None:
            out.append(None)
            continue
        axes = ctx.rules[name]
        axes = (axes,) if isinstance(axes, str) else tuple(axes)
        cand = tuple(a for a in axes if a not in used)
        picked: tuple = ()
        if cand:
            total = int(np.prod([mesh_shape[a] for a in cand]))
            if dim % total == 0:
                picked = cand
            else:  # fall back to the largest single axis that divides
                divisors = [a for a in cand if dim % mesh_shape[a] == 0]
                if divisors:
                    best = max(divisors, key=lambda a: mesh_shape[a])
                    picked = (best,)
        used.update(picked)
        if not picked:
            out.append(None)
        elif len(picked) == 1:
            out.append(picked[0])
        else:
            out.append(picked)
    return P(*out)


def match_partition_rules(rules, params, ctx: ShardingCtx):
    """Build a PartitionSpec tree for ``params`` from ``(regex, axes)`` rules."""

    def assign(path: str, x):
        logical = match_first(rules, path, default=())
        return resolve_param_spec(ctx, logical, x.shape)

    return tree_map_with_path_str(assign, params)


def named_shardings(spec_tree, mesh: Mesh):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda s: isinstance(s, P),
    )


def data_parallel_specs(mesh: Mesh, params, *, batch_axis: str = "batch"):
    """Pure data-parallel layout for the vision serving mesh.

    EfficientViT at serving batch sizes is activation-bound, so the
    serving mesh shards only the batch axis: every param is replicated
    on every device, activations split along ``batch_axis``.  Returns
    ``(param_specs, act_spec)`` ready for ``jax.shard_map``'s
    in/out specs.  Built through the same rule machinery as the LLM
    meshes (an empty rule set — everything falls through to replicated)
    so a future tensor-parallel vision mesh only adds rules here.
    """
    ctx = make_ctx(mesh, {k: None for k in DEFAULT_RULES})
    param_specs = match_partition_rules([], params, ctx)
    return param_specs, P(batch_axis)
