"""Attention: GQA/MQA, RoPE, qk-norm, sliding-window, prefix-LM, cross-attn.

Train/prefill use a flash-style chunked implementation (pure JAX): an outer
``lax.map`` over query chunks and an inner ``fori_loop`` with *dynamic* kv
bounds doing online softmax — full [S,S] score tensors are never
materialized, and causal/window structure skips out-of-span kv chunks
entirely (not just masks them). Decode attends a single query against the
KV cache (linear cache for full attention, ring buffer for SWA layers).

Head layout: q [B,S,Hkv,G,hd] grouped per kv head; k/v [B,S,Hkv,hd].
Sharding constraints are applied on the flattened [B,S,H*hd] projections
(model axis); GSPMD propagates through the reshapes.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from .common import ParamDef, rms_norm, rope, shard

NEG_INF = -1e30


def attn_defs(cfg: ModelConfig) -> dict:
    hd, hq, hkv = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads
    d = {
        "wq": ParamDef((cfg.d_model, hq * hd), ("embed", "heads")),
        "wk": ParamDef((cfg.d_model, hkv * hd), ("embed", "kv_heads")),
        "wv": ParamDef((cfg.d_model, hkv * hd), ("embed", "kv_heads")),
        "wo": ParamDef((hq * hd, cfg.d_model), ("heads", "embed")),
    }
    if cfg.qk_norm:
        d["q_norm"] = ParamDef((hd,), (None,), init="zeros")
        d["k_norm"] = ParamDef((hd,), (None,), init="zeros")
    return d


class KVCache(NamedTuple):
    k: jax.Array          # [B, C, Hkv, hd] — C = seq_len or window (ring)
    v: jax.Array
    ring: bool            # static python bool via cache_spec construction


def cache_spec(cfg: ModelConfig, batch: int, seq_len: int,
               window: Optional[int]) -> tuple[tuple[int, ...], bool]:
    c = min(window, seq_len) if window else seq_len
    return (batch, c, cfg.num_kv_heads, cfg.head_dim), bool(window)


def _project_qkv(cfg: ModelConfig, p: dict, x: jax.Array,
                 positions: jax.Array, theta: float):
    b, s, _ = x.shape
    hd, hq, hkv = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads
    q = shard(x @ p["wq"], None, None, "model").reshape(b, s, hq, hd)
    k = shard(x @ p["wk"], None, None, None).reshape(b, s, hkv, hd)
    v = shard(x @ p["wv"], None, None, None).reshape(b, s, hkv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = rope(q, positions, theta)
    k = rope(k, positions, theta)
    return q, k, v


def _mask(pos_q, pos_k, *, causal, window, prefix_len, kv_limit):
    """[qc, kc] boolean mask from absolute positions."""
    m = pos_k[None, :] < kv_limit
    if causal:
        c = pos_k[None, :] <= pos_q[:, None]
        if window is not None:
            c = c & (pos_k[None, :] > pos_q[:, None] - window)
        if prefix_len is not None:
            both_prefix = (pos_q[:, None] < prefix_len) & (pos_k[None, :] < prefix_len)
            c = c | both_prefix
        m = m & c
    return m


def flash_attention(
    q: jax.Array,             # [B, Sq, Hq, hd]
    k: jax.Array,             # [B, Skv, Hkv, hd]
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    prefix_len: Optional[int] = None,       # STATIC (trace-time) prefix span
    q_offset: int = 0,        # absolute position of q[0] (== 0 for self-attn)
    q_chunk: int = 512,
    kv_chunk: int = 1024,
    softcap: Optional[float] = None,
    differentiable: bool = True,
) -> jax.Array:
    """Chunked online-softmax attention.

    ``differentiable=True`` (training): the q-chunk loop is a static Python
    unroll so each chunk's kv span [lo_c, hi_c) is a *static* interval —
    out-of-span kv chunks are skipped structurally (not masked), keeping
    causal/SWA FLOPs at the true count, and static bounds keep the inner
    ``fori_loop`` reverse-differentiable.

    ``differentiable=False`` (serving prefill): the q loop is a traced
    ``lax.map`` with dynamic kv bounds — same math, flat HLO (a 32k prefill
    over 64 q-chunks x 32 layers would otherwise explode compile time).
    """
    b, sq, hq, hd = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    scale = hd ** -0.5

    qc = min(q_chunk, sq)
    kc = min(kv_chunk, skv)
    nq, nk = -(-sq // qc), -(-skv // kc)
    qp = jnp.pad(q, ((0, 0), (0, nq * qc - sq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, nk * kc - skv), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, nk * kc - skv), (0, 0), (0, 0)))
    qp = qp.reshape(b, nq * qc, hkv, g, hd)
    kv_true = jnp.int32(skv)

    def q_body(qi):
        static = isinstance(qi, int)
        qs = qi * qc
        if static:
            q_blk = jax.lax.slice_in_dim(qp, qs, qs + qc, axis=1)
        else:
            q_blk = jax.lax.dynamic_slice_in_dim(qp, qs, qc, axis=1)
        q_blk = q_blk.astype(jnp.float32) * scale
        pos_q = q_offset + qs + jnp.arange(qc)

        # kv-chunk span for this q chunk (static ints on the training path)
        if not causal:
            lo_c, hi_c = 0, nk
        elif static:
            hi = min(q_offset + qs + qc, skv)
            if prefix_len is not None:
                hi = max(hi, int(prefix_len))
            hi_c = min(-(-hi // kc), nk)
            if window is None or prefix_len is not None:
                lo_c = 0
            else:
                lo_c = max(0, (q_offset + qs - window) // kc)
        else:
            hi = jnp.minimum(q_offset + qs + qc, skv)
            if prefix_len is not None:
                hi = jnp.maximum(hi, int(prefix_len))
            hi_c = jnp.minimum(-(-hi // kc), nk).astype(jnp.int32)
            if window is None or prefix_len is not None:
                lo_c = jnp.int32(0)
            else:
                lo_c = jnp.maximum(
                    0, (q_offset + qs - window) // kc).astype(jnp.int32)

        acc0 = jnp.zeros((b, hkv, g, qc, hd), jnp.float32)
        m0 = jnp.full((b, hkv, g, qc), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, qc), jnp.float32)

        def kv_body(j, carry):
            acc, m, l = carry
            ks = j * kc
            k_blk = jax.lax.dynamic_slice_in_dim(kp, ks, kc, axis=1)
            v_blk = jax.lax.dynamic_slice_in_dim(vp, ks, kc, axis=1)
            pos_k = ks + jnp.arange(kc)
            s_blk = jnp.einsum("bqhgd,bkhd->bhgqk", q_blk,
                               k_blk.astype(jnp.float32))
            if softcap is not None:
                s_blk = softcap * jnp.tanh(s_blk / softcap)
            msk = _mask(pos_q, pos_k, causal=causal, window=window,
                        prefix_len=prefix_len, kv_limit=kv_true)
            s_blk = jnp.where(msk[None, None, None], s_blk, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s_blk, axis=-1))
            p_blk = jnp.exp(s_blk - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p_blk, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p_blk, v_blk.astype(jnp.float32))
            return acc_new, m_new, l_new

        acc, m, l = jax.lax.fori_loop(lo_c, hi_c, kv_body, (acc0, m0, l0))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return out.transpose(0, 3, 1, 2, 4)        # [B, qc, Hkv, G, hd]

    if differentiable:
        out = jnp.concatenate([q_body(i) for i in range(nq)], axis=1)
    else:
        out = jax.lax.map(q_body, jnp.arange(nq))   # [nq, B, qc, hkv, g, hd]
        out = out.transpose(1, 0, 2, 3, 4, 5)
    out = out.reshape(b, nq * qc, hq, hd)
    return out[:, :sq].astype(q.dtype)


def decode_attention(
    q: jax.Array,             # [B, 1, Hq, hd]
    cache: KVCache,
    pos: jax.Array,           # scalar i32: index of the token being decoded
    *,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
) -> jax.Array:
    b, _, hq, hd = q.shape
    c, hkv = cache.k.shape[1], cache.k.shape[2]
    g = hq // hkv
    qf = q.reshape(b, hkv, g, hd).astype(jnp.float32) * hd ** -0.5
    kf = cache.k.astype(jnp.float32)
    s = jnp.einsum("bhgd,bchd->bhgc", qf, kf)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    valid = jnp.arange(c) <= (pos if not window else jnp.minimum(pos, c - 1))
    s = jnp.where(valid[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgc,bchd->bhgd", p, cache.v.astype(jnp.float32))
    return out.reshape(b, 1, hq, hd).astype(q.dtype)


def cache_insert(cache: KVCache, k_new: jax.Array, v_new: jax.Array,
                 pos: jax.Array) -> KVCache:
    """Insert one decode step's k/v ([B,1,Hkv,hd]) at pos (ring-aware)."""
    c = cache.k.shape[1]
    slot = jnp.remainder(pos, c) if cache.ring else pos
    k = jax.lax.dynamic_update_slice_in_dim(cache.k, k_new.astype(cache.k.dtype),
                                            slot, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(cache.v, v_new.astype(cache.v.dtype),
                                            slot, axis=1)
    return KVCache(k, v, cache.ring)


class AttnOut(NamedTuple):
    out: jax.Array
    cache: Optional[KVCache]


def attention_block(
    cfg: ModelConfig,
    p: dict,
    x: jax.Array,
    *,
    positions: jax.Array,
    theta: float,
    window: Optional[int] = None,
    causal: bool = True,
    prefix_len: Optional[jax.Array] = None,
    cache: Optional[KVCache] = None,
    decode_pos: Optional[jax.Array] = None,
    fill_cache: bool = False,
    softcap: Optional[float] = None,
    differentiable: bool = True,
) -> AttnOut:
    """Unified self-attention: train (no cache), prefill (fill_cache=True),
    decode (cache + decode_pos). Runs under the ``attn`` scope: the QKV
    projection, RoPE, the attention itself and the output projection."""
    with jax.named_scope("attn"):
        b, s, _ = x.shape
        q, k, v = _project_qkv(cfg, p, x, positions, theta)

        if cache is not None and decode_pos is not None:      # decode
            cache = cache_insert(cache, k, v, decode_pos)
            out = decode_attention(q, cache, decode_pos, window=window,
                                   softcap=softcap)
        else:                                                 # train / prefill
            out = flash_attention(
                q, k, v, causal=causal, window=window, prefix_len=prefix_len,
                q_chunk=cfg.attn_q_chunk, kv_chunk=cfg.attn_kv_chunk,
                softcap=softcap, differentiable=differentiable)
            if fill_cache and cache is not None:
                c = cache.k.shape[1]
                if cache.ring:
                    # keep the last `c` positions (prefill longer than window)
                    start = jnp.maximum(0, s - c)
                    n = min(c, s)
                    k_tail = jax.lax.dynamic_slice_in_dim(k, start, n, 1)
                    v_tail = jax.lax.dynamic_slice_in_dim(v, start, n, 1)
                    # ring layout: slot = pos % c; for pos = start..start+c-1
                    slots = jnp.remainder(start + jnp.arange(n), c)
                    kc_ = cache.k.at[:, slots].set(
                        k_tail.astype(cache.k.dtype))
                    vc_ = cache.v.at[:, slots].set(
                        v_tail.astype(cache.v.dtype))
                    cache = KVCache(kc_, vc_, True)
                else:
                    kc_ = jax.lax.dynamic_update_slice_in_dim(
                        cache.k, k.astype(cache.k.dtype), 0, axis=1)
                    vc_ = jax.lax.dynamic_update_slice_in_dim(
                        cache.v, v.astype(cache.v.dtype), 0, axis=1)
                    cache = KVCache(kc_, vc_, False)

        o = out.reshape(b, -1, cfg.num_heads * cfg.head_dim)
        o = shard(o, None, None, "model")
        return AttnOut(shard(o @ p["wo"], None, None, None), cache)
