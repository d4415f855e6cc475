"""Shared model substrate: param definitions, init, sharding specs, norms,
RoPE, MLPs, embeddings, chunked cross-entropy.

Parameters are plain nested dicts. Structure is declared once as a tree of
``ParamDef`` (shape + logical axes + initializer); the same tree drives
materialized init, abstract ShapeDtypeStructs for the dry-run, and
PartitionSpec resolution (logical axes -> mesh axes with divisibility
guards). Activation sharding uses ``with_sharding_constraint`` over the
GSPMD-auto ``model`` axis only — batch axes are manual (shard_map) in the
trainer, see DESIGN.md §4.
"""
from __future__ import annotations

import hashlib
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ParallelConfig


class ParamDef(NamedTuple):
    shape: tuple[int, ...]
    axes: tuple[Optional[str], ...]      # logical axis names, len == ndim
    init: str = "normal"                 # normal | zeros | ones | embed
    scale: float = 1.0                   # stddev multiplier for "normal"


def _path_key(seed_key: jax.Array, path: str) -> jax.Array:
    h = int.from_bytes(hashlib.md5(path.encode()).digest()[:4], "little")
    return jax.random.fold_in(seed_key, h)


def init_params(defs: Any, seed: int, dtype: Any) -> Any:
    """Materialize a ParamDef tree into concrete arrays."""
    root = jax.random.PRNGKey(seed)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        defs, is_leaf=lambda x: isinstance(x, ParamDef))
    leaves = []
    for kp, d in flat:
        path = jax.tree_util.keystr(kp)
        if d.init == "zeros":
            leaves.append(jnp.zeros(d.shape, dtype))
        elif d.init == "ones":
            leaves.append(jnp.ones(d.shape, dtype))
        else:
            fan_in = d.shape[0] if d.shape else 1
            std = d.scale / max(fan_in, 1) ** 0.5
            if d.init == "embed":
                std = d.scale
            k = _path_key(root, path)
            leaves.append((jax.random.normal(k, d.shape, jnp.float32)
                           * std).astype(dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def abstract_params(defs: Any, dtype: Any, mesh=None, pc: ParallelConfig | None = None) -> Any:
    """ShapeDtypeStruct tree (with shardings if mesh given) for .lower()."""
    specs = param_specs(defs, pc or ParallelConfig(), mesh) if mesh is not None else None

    def mk(d: ParamDef, spec):
        sharding = NamedSharding(mesh, spec) if mesh is not None else None
        return jax.ShapeDtypeStruct(d.shape, dtype, sharding=sharding)

    if specs is None:
        return jax.tree.map(lambda d: jax.ShapeDtypeStruct(d.shape, dtype),
                            defs, is_leaf=lambda x: isinstance(x, ParamDef))
    return jax.tree.map(mk, defs, specs,
                        is_leaf=lambda x: isinstance(x, ParamDef))


def param_specs(defs: Any, pc: ParallelConfig, mesh) -> Any:
    """Resolve logical axes -> PartitionSpec with divisibility guards.

    A rule value may be a single mesh axis or a tuple of axes (FSDP-style
    joint sharding, e.g. ("pod", "data")); tuples require divisibility by
    the product of their sizes.
    """
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape)) if mesh is not None else {}

    def _size(mesh_axis) -> int | None:
        axes = mesh_axis if isinstance(mesh_axis, tuple) else (mesh_axis,)
        n = 1
        for a in axes:
            if a not in axis_sizes:
                return None
            n *= axis_sizes[a]
        return n

    def resolve(d: ParamDef) -> P:
        out = []
        used: set = set()
        for dim, logical in zip(d.shape, d.axes):
            mesh_axis = pc.rule(logical) if logical else None
            size = _size(mesh_axis) if mesh_axis is not None else None
            flat = (set(mesh_axis) if isinstance(mesh_axis, tuple)
                    else {mesh_axis})
            if (mesh_axis is None or size is None or (flat & used)
                    or dim % size != 0):
                out.append(None)
            else:
                out.append(mesh_axis)
                used |= flat
        return P(*out)

    return jax.tree.map(resolve, defs, is_leaf=lambda x: isinstance(x, ParamDef))


import contextlib
import contextvars

_SHARD_OFF = contextvars.ContextVar("repro_shard_constraints_off",
                                    default=False)
_STRUCT_OFF = contextvars.ContextVar("repro_structural_shardmap_off",
                                     default=False)


@contextlib.contextmanager
def pure_gspmd():
    """Disable BOTH activation constraints and structural shard_map
    wrapping (moe shard-local dispatch) for the enclosed trace. Used by
    the FSDP dense step: a nested shard_map over the data axis inside a
    pjit whose params are data-sharded trips an XLA:CPU partitioner
    crash (Invalid binary instruction opcode copy) and would all-gather
    the full parameter tree anyway."""
    t1 = _SHARD_OFF.set(True)
    t2 = _STRUCT_OFF.set(True)
    try:
        yield
    finally:
        _STRUCT_OFF.reset(t2)
        _SHARD_OFF.reset(t1)


def structural_shardmap_enabled() -> bool:
    return not _STRUCT_OFF.get()


@contextlib.contextmanager
def no_activation_constraints():
    """Disable shard() constraints for the enclosed trace.

    Serve steps (plain jit) trace under this: GSPMD propagates layouts
    from the in/out shardings better than the hand constraints, which are
    written for the trainer's manual-data region (measured: gemma3
    prefill wire 6.2e10 auto vs 1.5e11 constrained — §Perf it.5)."""
    tok = _SHARD_OFF.set(True)
    try:
        yield
    finally:
        _SHARD_OFF.reset(tok)


def shard(x: jax.Array, *spec) -> jax.Array:
    """Constraint over the auto 'model' axis; no-op outside jit/mesh context
    and under ``no_activation_constraints()``.

    ``None`` dims are UNCONSTRAINED, not replicated: the same model code
    runs inside the trainer's manual-data region (batch dims local) AND in
    auto-sharded serving (batch dims sharded over data) — pinning batch
    dims to replicated would force per-layer activation gathers in serving
    (measured 29x extra prefill FLOPs, EXPERIMENTS.md §Perf it.4).
    """
    if _SHARD_OFF.get():
        return x
    full = P(*[P.UNCONSTRAINED if s is None else s for s in spec])
    try:
        return jax.lax.with_sharding_constraint(x, full)
    except (ValueError, RuntimeError):
        return x


# ---------------------------------------------------------------------------
# numerics
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def act_fn(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu,
            "relu": jax.nn.relu}[name]


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., S, H, hd]; positions: [..., S] i32."""
    hd = x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    # [..., S, 1, half]: broadcast over the heads dim
    ang = positions.astype(jnp.float32)[..., :, None, None] * freq[None, :]
    sin, cos = jnp.sin(ang), jnp.cos(ang)  # [..., S, 1, half]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1
    ).astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP (gated) + embedding / loss
# ---------------------------------------------------------------------------

def mlp_defs(cfg: ModelConfig) -> dict:
    return {
        "w_gate": ParamDef((cfg.d_model, cfg.d_ff), ("embed", "ffn")),
        "w_up": ParamDef((cfg.d_model, cfg.d_ff), ("embed", "ffn")),
        "w_down": ParamDef((cfg.d_ff, cfg.d_model), ("ffn", "embed")),
    }


def mlp(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    """Gated MLP, under the ``mlp`` scope."""
    with jax.named_scope("mlp"):
        g = shard(x @ p["w_gate"], None, None, "model")
        u = shard(x @ p["w_up"], None, None, "model")
        h = act_fn(cfg.act)(g) * u
        return shard(h @ p["w_down"], None, None, None)


def embed_defs(cfg: ModelConfig) -> dict:
    d = {"table": ParamDef((cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
                           init="embed", scale=0.02)}
    if not cfg.tie_embeddings:
        d["lm_head"] = ParamDef((cfg.vocab_size, cfg.d_model),
                                ("vocab", "embed"), init="embed", scale=0.02)
    return d


def embed_lookup(cfg: ModelConfig, p: dict, tokens: jax.Array) -> jax.Array:
    x = jnp.take(p["table"], tokens, axis=0)
    if cfg.embed_scale:
        x = x * jnp.sqrt(jnp.float32(cfg.d_model)).astype(x.dtype)
    return x


def lm_logits(cfg: ModelConfig, p: dict, h: jax.Array) -> jax.Array:
    """[..., D] -> [..., V], vocab-sharded."""
    table = p["lm_head"] if "lm_head" in p else p["table"]
    return shard(h @ table.T, None, None, "model")


def chunked_ce_loss(cfg: ModelConfig, embed_p: dict, hidden: jax.Array,
                    labels: jax.Array, mask: jax.Array | None = None) -> jax.Array:
    """Mean next-token CE without materializing [B,S,V] logits.

    hidden: [B,S,D]; labels: [B,S] i32 (targets aligned with hidden);
    mask: [B,S] f32 weights (None = all ones). Scans over sequence chunks;
    each chunk's logits are vocab-sharded and remat'd. Each chunk's logits
    and cross-entropy run under the ``loss`` scope, inside the scanned body
    so that the name stays bare (a scope outside it would read
    ``jvp(loss)`` / ``transpose(jvp(loss))`` under autodiff).
    """
    b, s, d = hidden.shape
    chunk = min(cfg.loss_chunk, s)
    n_chunk = -(-s // chunk)
    pad = n_chunk * chunk - s
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask if mask is not None else jnp.ones((b, s)),
                       ((0, 0), (0, pad)))
    elif mask is None:
        mask = jnp.ones((b, s))
    hs = hidden.reshape(b, n_chunk, chunk, d).swapaxes(0, 1)
    ls = labels.reshape(b, n_chunk, chunk).swapaxes(0, 1)
    ms = mask.reshape(b, n_chunk, chunk).swapaxes(0, 1)

    @jax.checkpoint
    def body(carry, xs):
        with jax.named_scope("loss"):
            h_c, l_c, m_c = xs
            logits = lm_logits(cfg, embed_p, h_c).astype(jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, l_c[..., None],
                                       axis=-1)[..., 0]
            nll = (lse - gold) * m_c
            return (carry[0] + jnp.sum(nll), carry[1] + jnp.sum(m_c)), None

    (tot, cnt), _ = jax.lax.scan(body, (jnp.float32(0.0), jnp.float32(0.0)),
                                 (hs, ls, ms))
    return tot / jnp.maximum(cnt, 1.0)
