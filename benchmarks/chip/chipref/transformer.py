"""Plain reference of a Llama-style decoder (InternLM2, arXiv:2403.17297):
RMSNorm before attention and MLP, grouped-query causal attention with
rotary position embeddings (the two halves of each head rotated against
each other), a SiLU-gated MLP, untied input and output embeddings.

Layout of the weights (the layout the trainer takes them in):
``embed.{table, lm_head} [V, D]``; ``layers.*`` stacked over a leading
layer axis: ``attn.{wq [D, Hq*hd], wk, wv [D, Hkv*hd], wo [Hq*hd, D]}``,
``ffn.{w_gate, w_up [D, F], w_down [F, D]}``, ``norm_attn``,
``norm_ffn [D]``; ``final_norm [D]``. A norm weight is stored as its
offset from 1 (the norm multiplies by ``1 + w``), so zeros are the
published initial value. Query head ``h`` reads key/value head
``h // (Hq / Hkv)``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .numerics import Numerics

Q_BLOCK = 512      # query rows per attention block (memory only)


def dims(cfg: dict) -> dict:
    d, hq = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"v": cfg["vocab_size"], "d": d, "f": cfg["intermediate_size"],
            "l": cfg["num_hidden_layers"], "hq": hq,
            "hkv": cfg["num_key_value_heads"], "hd": d // hq}


def weight_specs(cfg: dict) -> dict:
    """``(shape, std)`` per parameter; std 0 means zeros. Matrices are
    drawn with std 1/sqrt(fan-in), embeddings with 0.02."""
    m = dims(cfg)
    d, f, n, hq, hkv, hd = m["d"], m["f"], m["l"], m["hq"], m["hkv"], m["hd"]

    def mat(rows, cols):
        return ((n, rows, cols), rows ** -0.5)

    return {
        "embed": {"table": ((m["v"], d), 0.02),
                  "lm_head": ((m["v"], d), 0.02)},
        "layers": {
            "attn": {"wq": mat(d, hq * hd), "wk": mat(d, hkv * hd),
                     "wv": mat(d, hkv * hd), "wo": mat(hq * hd, d)},
            "ffn": {"w_gate": mat(d, f), "w_up": mat(d, f),
                    "w_down": mat(f, d)},
            "norm_attn": ((n, d), 0.0),
            "norm_ffn": ((n, d), 0.0),
        },
        "final_norm": ((d,), 0.0),
    }


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def _rope(x, theta):
    """x: [B, S, H, hd]."""
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * freq
    sin, cos = jnp.sin(ang).astype(x.dtype), jnp.cos(ang).astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(nx, q, k, v):
    """Causal GQA. q: [B, S, Hkv, G, hd]; k, v: [B, S, Hkv, hd]."""
    s, hd = q.shape[1], q.shape[-1]
    outs = []
    for qs in range(0, s, Q_BLOCK):
        qe = min(qs + Q_BLOCK, s)
        sc = nx.einsum("bqhgd,bkhd->bhgqk", q[:, qs:qe], k[:, :qe])
        sc = sc * jnp.asarray(hd ** -0.5, sc.dtype)
        causal = (jnp.arange(qe)[None, :] <= jnp.arange(qs, qe)[:, None])
        sc = jnp.where(causal, sc, jnp.asarray(-1e30, sc.dtype))
        pr = jax.nn.softmax(sc, axis=-1)
        outs.append(nx.einsum("bhgqk,bkhd->bqhgd", pr, v[:, :qe]))
    return jnp.concatenate(outs, axis=1)


def _layer(cfg, nx, lp, x):
    m = dims(cfg)
    b, s, _ = x.shape
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    h = _rms(x, lp["norm_attn"], eps)
    a = lp["attn"]
    q = nx.mm(h, a["wq"]).reshape(b, s, m["hq"], m["hd"])
    k = nx.mm(h, a["wk"]).reshape(b, s, m["hkv"], m["hd"])
    v = nx.mm(h, a["wv"]).reshape(b, s, m["hkv"], m["hd"])
    q, k = _rope(q, theta), _rope(k, theta)
    q = q.reshape(b, s, m["hkv"], m["hq"] // m["hkv"], m["hd"])
    o = _attention(nx, q, k, v).reshape(b, s, m["hq"] * m["hd"])
    x = x + nx.mm(o, a["wo"])
    h = _rms(x, lp["norm_ffn"], eps)
    f = lp["ffn"]
    g = jax.nn.silu(nx.mm(h, f["w_gate"])) * nx.mm(h, f["w_up"])
    return x + nx.mm(g, f["w_down"])


def nll_sum(cfg: dict, params: dict, tokens: jax.Array,
            nx: Numerics) -> tuple[jax.Array, jax.Array]:
    """(sum of next-token negative log-likelihoods, number of targets)."""
    p = jax.tree.map(nx.cast, params)
    x = p["embed"]["table"][tokens]
    layer = jax.checkpoint(partial(_layer, cfg, nx))
    for i in range(cfg["num_hidden_layers"]):
        x = layer(jax.tree.map(lambda a: a[i], p["layers"]), x)
    h = _rms(x, p["final_norm"], cfg["rms_norm_eps"])
    logits = nx.mm(h[:, :-1], p["embed"]["lm_head"].T)
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(logits.astype(jnp.float32),
                               tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.sum(lse - gold), jnp.float32(gold.size)


def flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward FLOPs per trained token (3 x 2 x MACs): the
    projections and MLP of every layer, causal attention over an average
    context of (seq + 1) / 2, and the output layer at the seq - 1
    positions that have a target. Recomputation does not count."""
    m = dims(cfg)
    d, hq, hkv, hd = m["d"], m["hq"], m["hkv"], m["hd"]
    proj = d * hq * hd * 2 + d * hkv * hd * 2 + 3 * d * m["f"]
    attn = 2 * hq * hd * (seq + 1) / 2
    head = d * m["v"] * (seq - 1) / seq
    return 6.0 * (m["l"] * (proj + attn) + head)
