"""Mixture-of-Experts FFN with top-k token-choice routing.

Two execution paths sharing one routing/dispatch core:

``moe_dense``   — single-logical-device formulation (sort-based slotting,
  no (T, E, C) one-hot): the reference semantics, used on CPU smoke runs
  and as the oracle in tests.  Under GSPMD at 256-way scale its scatter
  dispatch gets *replicated* (the kimi-k2 baseline measured 957 GB/device
  — EXPERIMENTS.md §Perf iteration 1), which motivates:

``moe_shard_map`` — explicit-collective formulation, mode per topology:
    * ``a2a``  (train/prefill, E % ep == 0): tokens stay (dp x sp)-
      sharded; each device routes its local tokens, builds an (E, c, D)
      dispatch buffer, ``all_to_all`` over the model axis regroups it to
      (E_loc, ep*c, D), local experts run, reverse ``all_to_all``, local
      combine.  Wire cost = 2 x k x t_loc x D — the textbook GShard
      dispatch, instead of GSPMD's replicated scatter.
    * ``repl`` (decode, tokens replicated over the model axis): each
      device serves only its own expert slice and psums the partial
      outputs — expert-parallel inference.
    * ``tp``   (E < ep_size, e.g. grok-1's 8 experts on a 16-way axis):
      experts replicated, d_ff tensor-sharded over the model axis;
      partial outputs psum — Megatron-style MoE-TP.
  Expert weights are ZeRO-sharded over the data axis and all-gathered on
  use (``fsdp`` dim), mirroring the dense-layer recipe.

Token dropping uses LOCAL capacity (k*t_loc*cf/E per shard) in sharded
modes — the standard production semantics; with a generous capacity
factor the paths agree exactly (asserted in tests/test_moe.py).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.distributed.ctx import current_ctx, shard
from repro.layers.mlp import _act


@dataclasses.dataclass(frozen=True)
class MoeConfig:
    d_model: int
    d_ff: int                 # per-expert hidden size
    n_experts: int
    top_k: int
    capacity_factor: float = 1.25
    activation: str = "silu"
    gated: bool = True
    router_aux_weight: float = 0.01
    dtype: jnp.dtype = jnp.float32


def init_moe(key, cfg: MoeConfig):
    kr, k1, k2, k3 = jax.random.split(key, 4)
    E, D, F = cfg.n_experts, cfg.d_model, cfg.d_ff
    std_in, std_out = D ** -0.5, F ** -0.5
    p = {
        "router": {"w": jax.random.normal(kr, (D, E), jnp.float32) * std_in},
        "w_in": (jax.random.normal(k1, (E, D, F), jnp.float32) * std_in).astype(cfg.dtype),
        "w_out": (jax.random.normal(k2, (E, F, D), jnp.float32) * std_out).astype(cfg.dtype),
    }
    if cfg.gated:
        p["w_gate"] = (jax.random.normal(k3, (E, D, F), jnp.float32) * std_in).astype(cfg.dtype)
    return p


MOE_RULES = [
    (r"router/w$", (None, None)),
    (r"w_(in|gate)$", ("ep", "fsdp", "tp")),
    (r"w_out$", ("ep", "tp", "fsdp")),
]


def _capacity(cfg: MoeConfig, n_tokens: int) -> int:
    c = int(-(-cfg.top_k * n_tokens * cfg.capacity_factor // cfg.n_experts))
    return max(8, -(-c // 8) * 8)  # round up to a multiple of 8


# ---------------------------------------------------------------------------
# shared routing / slotting / combine primitives (pure, shape-local)
# ---------------------------------------------------------------------------

def _route(xf, router_w, cfg: MoeConfig):
    """xf (T, D) -> gates (T, k), idx (T, k), probs (T, E)  [fp32]."""
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, cfg.top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, idx, probs


def _slot_assign(idx, n_experts: int, capacity: int):
    """Sort-based slot ranking.  idx (T, k) -> slot_c (T, k), valid (T, k).

    slot = rank of the assignment within its expert; >capacity -> dropped
    (written to the overflow slot ``capacity``).
    """
    T, k = idx.shape
    e_flat = idx.reshape(-1)
    order = jnp.argsort(e_flat)
    counts = jnp.zeros((n_experts,), jnp.int32).at[e_flat].add(1)
    starts = jnp.cumsum(counts) - counts
    slot_sorted = jnp.arange(T * k, dtype=jnp.int32) - starts[e_flat[order]]
    slot = jnp.zeros((T * k,), jnp.int32).at[order].set(slot_sorted)
    slot = slot.reshape(T, k)
    valid = slot < capacity
    return jnp.where(valid, slot, capacity), valid


def _dispatch(xf, idx, slot_c, n_experts: int, capacity: int):
    """Scatter tokens into (E, C+1, D) buffers (slot C = overflow bin)."""
    T, D = xf.shape
    k = idx.shape[1]
    buf = jnp.zeros((n_experts, capacity + 1, D), xf.dtype)
    return buf.at[idx, slot_c].add(
        jnp.broadcast_to(xf[:, None, :], (T, k, D)), mode="drop")


def _deq(w, cd):
    """Dequantize-on-use for W8 expert weights ({'q','scale'} dicts)."""
    if isinstance(w, dict):
        return w["q"].astype(cd) * w["scale"].astype(cd)
    return w.astype(cd)


def _expert_ffn(h_in, w_in, w_gate, w_out, cfg: MoeConfig, cd):
    """(E, C, D) @ per-expert weights -> (E, C, D_out_partial)."""
    act = _act(cfg.activation)
    h = jnp.einsum("ecd,edf->ecf", h_in.astype(cd), _deq(w_in, cd))
    if w_gate is not None:
        g = jnp.einsum("ecd,edf->ecf", h_in.astype(cd), _deq(w_gate, cd))
        h = act(g) * h
    else:
        h = act(h)
    return jnp.einsum("ecf,efd->ecd", h, _deq(w_out, cd))


def _combine(out_buf, idx, slot_c, gates, valid, dtype):
    """Gather expert outputs back per token, gate-weighted sum."""
    E, Cp1, D = out_buf.shape
    gathered = out_buf[idx, slot_c]                     # (T, k, D)
    w = (gates * valid).astype(dtype)[..., None]
    return jnp.sum(gathered * w, axis=1)


def _aux_from_stats(me, frac, cfg: MoeConfig):
    return cfg.router_aux_weight * cfg.n_experts * jnp.sum(me * frac)


def _assign_frac(idx, n_experts: int):
    T, k = idx.shape
    counts = jnp.zeros((n_experts,), jnp.float32).at[idx.reshape(-1)].add(1.0)
    return counts / (T * k)


# ---------------------------------------------------------------------------
# dense (single logical device) path — the reference semantics
# ---------------------------------------------------------------------------

def moe_dense(params, x, cfg: MoeConfig):
    """x: (B, S, D) -> (y, aux_loss)."""
    B, S, D = x.shape
    T = B * S
    C = _capacity(cfg, T)
    xf = x.reshape(T, D)
    xf = shard(xf, "dp", None)

    gates, idx, probs = _route(xf, params["router"]["w"], cfg)
    aux = _aux_from_stats(jnp.mean(probs, axis=0),
                          _assign_frac(idx, cfg.n_experts), cfg)
    slot_c, valid = _slot_assign(idx, cfg.n_experts, C)
    buf = _dispatch(xf, idx, slot_c, cfg.n_experts, C)
    buf = shard(buf, "ep", "fsdp", None)
    out = _expert_ffn(buf[:, :C], params["w_in"], params.get("w_gate"),
                      params["w_out"], cfg, x.dtype)
    out_pad = jnp.concatenate([out, jnp.zeros((cfg.n_experts, 1, D),
                                              out.dtype)], axis=1)
    y = _combine(out_pad, idx, slot_c, gates, valid, out.dtype)
    y = shard(y, "dp", None)
    return y.reshape(B, S, D), aux


# ---------------------------------------------------------------------------
# shard_map path — explicit collectives
# ---------------------------------------------------------------------------

def _gather_fsdp(w, fsdp_axes, axis: int):
    """ZeRO gather; for W8 dicts only the int8 payload travels."""
    if isinstance(w, dict):
        return {"q": _gather_fsdp(w["q"], fsdp_axes, axis),
                "scale": w["scale"]}
    if not fsdp_axes:
        return w
    for a in fsdp_axes:
        w = lax.all_gather(w, a, axis=axis, tiled=True)
    return w


def _pmean(x, axes):
    for a in axes:
        x = lax.pmean(x, a)
    return x


def moe_shard_map(params, x, cfg: MoeConfig, ctx):
    """Distributed MoE.  x: (B, S, D) -> (y, aux).  See module docstring."""
    mesh = ctx.mesh
    names = mesh.axis_names
    ep_axis = "model"
    ep = mesh.shape[ep_axis]
    dp_axes = tuple(a for a in ("pod", "data") if a in names)
    fsdp_axes = tuple(a for a in ("data",) if a in names)
    B, S, D = x.shape
    E, k = cfg.n_experts, cfg.top_k
    cd = x.dtype

    seq_sharded = S % ep == 0 and S > 1
    if E % ep == 0:
        mode = "a2a" if seq_sharded else "repl"
    elif ep % E == 0:
        mode = "tp"
    else:
        return moe_dense(params, x, cfg)

    dp = 1
    for a in dp_axes:
        dp *= mesh.shape[a]
    assert B % dp == 0, (B, dp)
    t_loc = (B // dp) * (S // ep if mode == "a2a" else S)
    C = _capacity(cfg, t_loc)
    E_loc = E // ep if E % ep == 0 else E
    F = cfg.d_ff

    dp_spec = dp_axes if dp_axes else None
    x_spec = P(dp_spec, ep_axis, None) if mode == "a2a" \
        else P(dp_spec, None, None)
    # weight shards per MOE_RULES resolution on the production mesh
    if mode == "tp":   # experts replicated; F on model; ZeRO dim on data
        win_spec = P(None, fsdp_axes, ep_axis)
        wout_spec = P(None, ep_axis, fsdp_axes)
    else:
        win_spec = P(ep_axis, fsdp_axes, None)
        wout_spec = P(ep_axis, fsdp_axes, None)

    def wspec(w, base):
        """Spec tree for a (possibly W8-dict) expert weight."""
        if isinstance(w, dict):
            # scale is (E, 1, out): only the expert dim can shard
            sdims = [base[0]] + [None] * 2
            return {"q": base, "scale": P(*sdims)}
        return base
    token_axes = dp_axes + ((ep_axis,) if mode == "a2a" else ())

    def inner(xf, router_w, w_in, w_out, *maybe_gate):
        w_gate = maybe_gate[0] if maybe_gate else None
        t = xf.shape[0] * xf.shape[1]
        xt = xf.reshape(t, D)
        gates, idx, probs = _route(xt, router_w, cfg)
        me = _pmean(jnp.mean(probs, axis=0), token_axes)
        frac = _pmean(_assign_frac(idx, E), token_axes)
        aux = _aux_from_stats(me, frac, cfg)

        w_in_f = _gather_fsdp(w_in, fsdp_axes, 1)
        w_gate_f = (_gather_fsdp(w_gate, fsdp_axes, 1)
                    if w_gate is not None else None)

        if mode == "a2a":
            slot_c, valid = _slot_assign(idx, E, C)
            buf = _dispatch(xt, idx, slot_c, E, C)[:, :C]     # (E, C, D)
            # regroup: send expert block j to rank j -> (E_loc, ep*C, D)
            buf = lax.all_to_all(buf, ep_axis, split_axis=0, concat_axis=1,
                                 tiled=True)
            w_out_f = _gather_fsdp(w_out, fsdp_axes, 1)
            out = _expert_ffn(buf, w_in_f, w_gate_f, w_out_f, cfg, cd)
            out = lax.all_to_all(out, ep_axis, split_axis=1, concat_axis=0,
                                 tiled=True)                   # (E, C, D)
            out_pad = jnp.concatenate(
                [out, jnp.zeros((E, 1, D), out.dtype)], axis=1)
            y = _combine(out_pad, idx, slot_c, gates, valid, out.dtype)

        elif mode == "repl":
            # every rank sees every token; it serves only its expert slice
            j = lax.axis_index(ep_axis)
            lo = j * E_loc
            own = (idx >= lo) & (idx < lo + E_loc)
            idx_own = jnp.where(own, idx - lo, E_loc)   # E_loc = drop bin
            slot_c, valid = _slot_assign(
                jnp.where(own, idx_own, E_loc), E_loc + 1, C)
            valid &= own
            slot_c = jnp.where(own, slot_c, C)
            buf = _dispatch(xt, jnp.where(own, idx_own, 0), slot_c, E_loc,
                            C)[:, :C]
            w_out_f = _gather_fsdp(w_out, fsdp_axes, 1)
            out = _expert_ffn(buf, w_in_f, w_gate_f, w_out_f, cfg, cd)
            out_pad = jnp.concatenate(
                [out, jnp.zeros((E_loc, 1, D), out.dtype)], axis=1)
            y = _combine(out_pad, jnp.where(own, idx_own, 0), slot_c,
                         gates, valid, out.dtype)
            y = lax.psum(y, ep_axis)                    # partial experts

        else:  # tp: all experts, F-sharded; partial over model
            slot_c, valid = _slot_assign(idx, E, C)
            buf = _dispatch(xt, idx, slot_c, E, C)[:, :C]
            w_out_f = _gather_fsdp(w_out, fsdp_axes, 2)  # (E, F_loc, D)
            out = _expert_ffn(buf, w_in_f, w_gate_f, w_out_f, cfg, cd)
            out_pad = jnp.concatenate(
                [out, jnp.zeros((E, 1, D), out.dtype)], axis=1)
            y = _combine(out_pad, idx, slot_c, gates, valid, out.dtype)
            y = lax.psum(y, ep_axis)                    # partial d_ff

        return y.reshape(xf.shape), aux

    args = [x, params["router"]["w"], params["w_in"], params["w_out"]]
    in_specs = [x_spec, P(None, None), wspec(params["w_in"], win_spec),
                wspec(params["w_out"], wout_spec)]
    if cfg.gated:
        args.append(params["w_gate"])
        in_specs.append(wspec(params["w_gate"], win_spec))
    y, aux = jax.shard_map(
        inner, mesh=mesh, in_specs=tuple(in_specs),
        out_specs=(x_spec, P()), check_vma=False,
    )(*args)
    return y, aux


def moe(params, x, cfg: MoeConfig):
    """Dispatcher: shard_map path under a multi-device 'model' mesh,
    dense reference otherwise."""
    ctx = current_ctx()
    if ctx is not None and "model" in ctx.mesh.axis_names \
            and ctx.mesh.shape["model"] > 1:
        return moe_shard_map(params, x, cfg, ctx)
    return moe_dense(params, x, cfg)
