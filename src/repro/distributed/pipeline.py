"""Pipeline parallelism over the pod axis (GPipe-style, shard_map).

The default multi-pod layout runs pure DP across pods: every pod holds
all layers and the gradient all-reduce crosses the (slow) inter-pod
links.  Pipeline parallelism is the alternative when params-per-pod is
the constraint: each pod holds HALF the layers, and only *activations*
(mb x S x D per microbatch) cross pods — orders of magnitude fewer bytes
than a gradient all-reduce for big models.

Mechanics (P stages on the "pipe" mesh axis, M microbatches):

  * the stacked block params get a leading stage dim sharded over the
    pipe axis; inside shard_map each stage holds only its (L/P, ...)
    slice — a 1T model's per-pod bytes halve at P=2.
  * one fori-style scan runs M + P - 1 ticks; at each tick every stage
    applies its layers to its in-flight activation and
    ``collective_permute``s the result to the next stage (the classic
    GPipe schedule; bubble fraction (P-1)/(M+P-1)).
  * stage 0 ingests microbatch t at tick t; the last stage's outputs of
    ticks >= P-1 are collected.  Autodiff through scan + permute yields
    the standard backward pipeline (reverse permutes) for free.

Scope: this module is self-contained (embed / head / loss handled by the
caller-supplied stage functions); `pipeline_loss` wires it for a dense
decoder-only LM.  Exercised by tests/test_pipeline.py on fake devices
and by `launch/dryrun_pp.py` on the 512-chip mesh.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def pipelined_apply(stage_fn: Callable, stage_params, x_micro, *,
                    mesh, pipe_axis: str = "pod", extra_specs=P(),
                    manual_axes=None):
    """Run ``stage_fn`` as a P-stage pipeline over ``pipe_axis``.

    stage_fn(local_params, h) -> h'   (one stage's layers)
    stage_params: pytree with leading dim = n_stages (sharded over pipe)
    x_micro: (M, mb, S, D) microbatched input (replicated over pipe)
    Returns (M, mb, S, D) outputs as produced by the LAST stage (valid on
    every pod after the final broadcast).
    """
    n_stages = mesh.shape[pipe_axis]
    M = x_micro.shape[0]

    def inner(params_loc, xm):
        params_sq = jax.tree_util.tree_map(lambda a: a[0], params_loc)
        sid = lax.axis_index(pipe_axis)
        fwd_perm = [(i, i + 1) for i in range(n_stages - 1)]

        def tick(carry, t):
            h_in, outs = carry
            feed = xm[jnp.minimum(t, M - 1)]
            h_in = jnp.where(sid == 0, feed, h_in)
            h_out = stage_fn(params_sq, h_in)
            midx = t - (n_stages - 1)
            write = jnp.logical_and(sid == n_stages - 1, midx >= 0)
            upd = lax.dynamic_update_index_in_dim(
                outs, h_out, jnp.maximum(midx, 0), 0)
            outs = jnp.where(write, upd, outs)
            h_next = lax.ppermute(h_out, pipe_axis, fwd_perm)
            return (h_next, outs), None

        h0 = jnp.zeros_like(xm[0])
        outs0 = jnp.zeros_like(xm)
        (_, outs), _ = lax.scan(tick, (h0, outs0),
                                jnp.arange(M + n_stages - 1))
        if n_stages > 1:
            # broadcast the last stage's collected outputs to the other
            # stages (a ppermute source must be unique, so the sender
            # keeps its own copy via the where)
            from_last = lax.ppermute(
                outs, pipe_axis,
                [(n_stages - 1, i) for i in range(n_stages - 1)])
            outs = jnp.where(sid == n_stages - 1, outs, from_last)
        return outs

    stage_specs = jax.tree_util.tree_map(
        lambda _: P(pipe_axis), stage_params)
    manual = {} if manual_axes is None else {"axis_names": set(manual_axes)}
    return jax.shard_map(
        inner, mesh=mesh,
        in_specs=(stage_specs, extra_specs),
        out_specs=extra_specs, check_vma=False, **manual,
    )(stage_params, x_micro)


def split_stages(stacked_params, n_stages: int):
    """(L, ...) stacked block params -> (n_stages, L/P, ...)."""
    def resh(a):
        L = a.shape[0]
        assert L % n_stages == 0, (L, n_stages)
        return a.reshape((n_stages, L // n_stages) + a.shape[1:])

    return jax.tree_util.tree_map(resh, stacked_params)
