"""Mixture-of-Experts FFN (grok-1: 8e top-2; granite: 40e top-8).

TPU-native dispatch: instead of a GPU-style scatter/gather with atomics, we
use the sort-based dispatch that maps onto the MXU + XLA one-hot matmuls:

  router logits -> top-k expert choice per token -> capacity-bounded slot
  assignment via a per-expert cumulative-sum over the (flattened) token axis
  -> one-hot dispatch matmul packs tokens into [E, C, D] expert buffers ->
  grouped expert FFN (einsum over the E axis) -> one-hot combine matmul
  weighted by router probabilities.

Capacity C = ceil(T * top_k / E * capacity_factor); overflowing tokens are
dropped (standard Switch/GShard semantics) — their combine weight is zero and
the residual connection carries them through.

Sharding: expert weights carry an ("expert", "expert_ffn") logical axis pair.
Default ParallelConfig maps expert -> None, expert_ffn -> "model": tensor
parallel *within* every expert, which divides cleanly for both assigned MoE
archs (grok d_ff=32768, granite d_ff=512 -> granite flips to expert-parallel
via the per-arch override; 40 experts don't divide 16 either, so granite uses
expert->None too but d_ff=512 < 16 means expert_ffn drops to replicated —
its expert weights are small). §Perf explores the EP alternative for grok.

Aux loss: GShard/Switch load-balance loss (mean over experts of
fraction_dispatched * mean_router_prob * E), returned to the caller and added
to the task loss with a small coefficient.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from .common import ParamDef, act_fn, shard


def moe_defs(cfg: ModelConfig) -> dict:
    e, d, f = cfg.num_experts, cfg.d_model, cfg.d_ff
    return {
        "router": ParamDef((d, e), ("embed", None), scale=0.1),
        "w_gate": ParamDef((e, d, f), ("expert", "embed", "expert_ffn")),
        "w_up": ParamDef((e, d, f), ("expert", "embed", "expert_ffn")),
        "w_down": ParamDef((e, f, d), ("expert", "expert_ffn", "embed")),
    }


def _capacity(tokens: int, cfg: ModelConfig) -> int:
    per = tokens * cfg.num_experts_per_tok / max(cfg.num_experts, 1)
    cap = int(per * cfg.moe_capacity_factor) + 1
    return min(max(cap, cfg.num_experts_per_tok), tokens)


def _route(cfg: ModelConfig, p: dict, xt: jax.Array, cap: int):
    """Router + capacity-bounded slot assignment (shared by both impls).

    Returns (gate_vals [T,k], gate_idx [T,k], slot [T,k], keep [T,k],
    sel_onehot [T,k,E], probs [T,E])."""
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    t = xt.shape[0]
    logits = (xt @ p["router"]).astype(jnp.float32)          # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)            # [T, k]
    # renormalize the chosen gates (mixtral/grok convention)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    # position of each (token, choice) within its expert's queue
    sel_onehot = jax.nn.one_hot(gate_idx, e, dtype=jnp.float32)
    flat_sel = sel_onehot.reshape(t * k, e)
    pos_in_expert = jnp.cumsum(flat_sel, axis=0) - flat_sel   # [T*k, E]
    slot = jnp.sum(pos_in_expert * flat_sel, axis=-1).reshape(t, k)  # [T, k]
    keep = slot < cap
    gate_vals = gate_vals * keep
    return gate_vals, gate_idx, slot.astype(jnp.int32), keep, sel_onehot, \
        probs


def _expert_ffn(cfg: ModelConfig, p: dict, xe: jax.Array) -> jax.Array:
    """Grouped expert FFN on packed buffers [E, C, D] -> [E, C, D].

    Runs on SHARD-LOCAL capacity (see moe_ffn): the token/capacity dims
    are local, only the expert hidden dim shards (TP-within-expert).
    """
    g = jnp.einsum("ecd,edf->ecf", xe, p["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", xe, p["w_up"])
    g = shard(g, None, None, "model")
    u = shard(u, None, None, "model")
    h = act_fn(cfg.act)(g) * u
    ye = jnp.einsum("ecf,efd->ecd", h, p["w_down"])           # [E, C, D]
    return shard(ye, None, None, None)


def _dispatch_onehot(cfg, p, xt, cap, route):
    """GShard-style one-hot matmul dispatch/combine. O(T*E*C) work —
    MXU-friendly at short T, catastrophic at 32k+ prefill (§Perf)."""
    gate_vals, _, slot, keep, sel_onehot, _ = route
    slot_onehot = jax.nn.one_hot(slot, cap,
                                 dtype=jnp.float32) * keep[..., None]
    dispatch = jnp.einsum("tke,tkc->tec", sel_onehot, slot_onehot)  # [T,E,C]
    xe = jnp.einsum("td,tec->ecd", xt.astype(jnp.float32), dispatch)
    xe = shard(xe.astype(xt.dtype), None, None, None)
    ye = _expert_ffn(cfg, p, xe)
    combine = jnp.einsum("tke,tkc,tk->tec", sel_onehot, slot_onehot,
                         gate_vals.astype(jnp.float32))       # [T, E, C]
    return jnp.einsum("tec,ecd->td", combine, ye.astype(jnp.float32))


def _dispatch_scatter(cfg, p, xt, cap, route):
    """Scatter/gather dispatch: pack tokens into [E, C, D] with a
    scatter-add (O(T*k*D)), un-pack with a gather. The §Perf beyond-
    baseline implementation — drops the O(T*E*C) one-hot matmuls that
    dominate long-sequence MoE (granite prefill_32k: 40 experts x 16k
    capacity made dispatch 34x the useful expert FLOPs)."""
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    t, d = xt.shape
    gate_vals, gate_idx, slot, keep, _, _ = route
    flat_e = gate_idx.reshape(-1)                     # [T*k]
    # dropped tokens land in a dump slot (index cap) sliced away after
    flat_slot = jnp.where(keep.reshape(-1), slot.reshape(-1), cap)
    xrep = jnp.repeat(xt.astype(jnp.float32), k, axis=0)      # [T*k, D]
    xe = jnp.zeros((e, cap + 1, d), jnp.float32)
    xe = xe.at[flat_e, flat_slot].add(xrep)[:, :cap]
    xe = shard(xe.astype(xt.dtype), None, None, None)
    ye = _expert_ffn(cfg, p, xe)
    yf = ye.astype(jnp.float32)
    safe = jnp.minimum(flat_slot, cap - 1)
    picked = yf[flat_e, safe] * keep.reshape(-1)[:, None]     # [T*k, D]
    return jnp.sum(picked.reshape(t, k, d)
                   * gate_vals.astype(jnp.float32)[..., None], axis=1)


def _moe_local(cfg: ModelConfig, p: dict, xt: jax.Array):
    """Dispatch + expert FFN + combine on a (shard-)local token set."""
    cap = _capacity(xt.shape[0], cfg)
    route = _route(cfg, p, xt, cap)
    if cfg.moe_impl == "scatter":
        out = _dispatch_scatter(cfg, p, xt, cap, route)
    else:
        out = _dispatch_onehot(cfg, p, xt, cap, route)
    # --- load-balance aux loss (Switch) -------------------------------------
    sel_onehot, probs = route[4], route[5]
    frac_tokens = jnp.mean(sel_onehot[:, 0], axis=0)          # top-1 dispatch
    mean_prob = jnp.mean(probs, axis=0)
    aux = jnp.sum(frac_tokens * mean_prob) * cfg.num_experts
    return out, aux


def _auto_batch_axes(tokens: int) -> tuple[str, ...]:
    """Batch-ish mesh axes that are AUTO in the current trace context and
    divide the token count — the axes a serve-path moe can shard-map over.

    In the trainer's manual-data region these axes are Manual (the tokens
    are already local) -> returns (); in plain-jit serving they are Auto
    -> dispatch runs shard-locally per data shard, which is what keeps
    capacity (and the scatter/gather extent) per-shard instead of global.
    """
    from .common import structural_shardmap_enabled
    if not structural_shardmap_enabled():
        return ()
    am = jax.sharding.get_abstract_mesh()
    out = []
    size = 1
    for name, ty in zip(am.axis_names, am.axis_types):
        if name != "model" and ty == jax.sharding.AxisType.Auto:
            out.append(name)
            size *= am.shape[name]
    if not out or size <= 1 or tokens % size != 0:
        return ()
    return tuple(out)


def moe_ffn(cfg: ModelConfig, p: dict, x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """x: [B, S, D] -> (out [B, S, D], aux load-balance loss scalar).

    Token dim semantics: inside the trainer's manual-data shard_map the
    tokens are already shard-local. In auto (serve) context we shard_map
    over the batch axes ourselves so dispatch capacity stays local — a
    global [E, C_global, D] scatter cannot shard its capacity dim and
    would replicate the expert FFN on every chip (measured 13.9x extra
    FLOPs on granite prefill_32k before this, see EXPERIMENTS.md §Perf).
    """
    from jax.sharding import PartitionSpec as P

    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    baxes = _auto_batch_axes(b * s)
    if baxes:
        out, aux = jax.shard_map(
            lambda pp, xx: _moe_local(cfg, pp, xx),
            axis_names=set(baxes),
            in_specs=(P(), P(baxes)),
            out_specs=(P(baxes), P()),
            check_vma=False,
        )(p, xt)
        aux = aux  # mean over shards is a psum'd scalar already (vma off)
    else:
        out, aux = _moe_local(cfg, p, xt)
    return out.reshape(b, s, d).astype(x.dtype), aux
