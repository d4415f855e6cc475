"""The paper's own evaluation model: 2-layer LSTM LM, 1500 hidden units
(Press & Wolf 2016 setup on PTB/Wiki2, RedSync §6.2).

Untied encoder/decoder embeddings (the paper: "we do not tie the weights"),
vanilla SGD + gradient clipping. This model is the convergence test bed for
Table 1 / Table 2 / Fig 6 — it has the paper's signature property: enormous
softmax + embedding layers vs tiny recurrent compute, i.e. the high
communication-to-computation ratio RedSync targets.

The recurrence is a ``lax.scan`` over time (gates batched into one [D, 4H]
matmul). Decode carries (h, c) per layer.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from .common import ParamDef, chunked_ce_loss, shard


def param_defs(cfg: ModelConfig) -> dict:
    d, h, v = cfg.d_model, cfg.d_ff, cfg.vocab_size  # d_ff doubles as hidden
    defs: dict = {
        "embed": {"table": ParamDef((v, d), ("vocab", "embed"),
                                    init="embed", scale=0.05)},
        "lm_head": ParamDef((h, v), (None, "vocab"), scale=0.5),
        "lm_bias": ParamDef((v,), ("vocab",), init="zeros"),
    }
    for i in range(cfg.num_layers):
        in_dim = d if i == 0 else h
        defs[f"lstm_{i}"] = {
            "wx": ParamDef((in_dim, 4 * h), ("embed", None), scale=0.5),
            "wh": ParamDef((h, 4 * h), (None, None), scale=0.5),
            "b": ParamDef((4 * h,), (None,), init="zeros"),
        }
    return defs


def _cell(p: dict, x_t: jax.Array, h_prev: jax.Array, c_prev: jax.Array):
    z = x_t @ p["wx"] + h_prev @ p["wh"] + p["b"]
    i, f, g, o = jnp.split(z.astype(jnp.float32), 4, axis=-1)
    c = jax.nn.sigmoid(f + 1.0) * c_prev + jax.nn.sigmoid(i) * jnp.tanh(g)
    h = jax.nn.sigmoid(o) * jnp.tanh(c)
    return h.astype(x_t.dtype), c


def _run_layer(p: dict, x: jax.Array, h0, c0):
    """x: [B,S,in] -> [B,S,H]; scan over time."""
    def body(carry, x_t):
        h_prev, c_prev = carry
        h, c = _cell(p, x_t, h_prev, c_prev)
        return (h, c), h

    (h_last, c_last), hs = jax.lax.scan(
        body, (h0, c0), x.swapaxes(0, 1))
    return hs.swapaxes(0, 1), h_last, c_last


def _states0(cfg: ModelConfig, batch: int):
    h = cfg.d_ff
    return [(jnp.zeros((batch, h), cfg.dtype), jnp.zeros((batch, h),
                                                         jnp.float32))
            for _ in range(cfg.num_layers)]


def _logits(cfg: ModelConfig, params: dict, h: jax.Array) -> jax.Array:
    return shard(h @ params["lm_head"] + params["lm_bias"],
                 None, None, "model")


def loss(cfg: ModelConfig, params: dict, batch: dict) -> jax.Array:
    tokens = batch["tokens"]
    x = jnp.take(params["embed"]["table"], tokens, axis=0)
    states = _states0(cfg, tokens.shape[0])
    for i in range(cfg.num_layers):
        x, _, _ = _run_layer(params[f"lstm_{i}"], x, *states[i])
    # untied head: chunked CE against the lm_head projection
    b, s, h = x.shape
    chunk = min(cfg.loss_chunk, s - 1)
    hs, ls = x[:, :-1], tokens[:, 1:]
    n = -(-(s - 1) // chunk)
    pad = n * chunk - (s - 1)
    if pad:
        hs = jnp.pad(hs, ((0, 0), (0, pad), (0, 0)))
        ls = jnp.pad(ls, ((0, 0), (0, pad)))
    mask = (jnp.arange(n * chunk) < (s - 1)).astype(jnp.float32)
    mask = jnp.broadcast_to(mask, (b, n * chunk))

    hs = hs.reshape(b, n, chunk, h).swapaxes(0, 1)
    ls = ls.reshape(b, n, chunk).swapaxes(0, 1)
    ms = mask.reshape(b, n, chunk).swapaxes(0, 1)

    def body(carry, xs):
        with jax.named_scope("loss"):      # as common.chunked_ce_loss
            h_c, l_c, m_c = xs
            logits = _logits(cfg, params, h_c).astype(jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, l_c[..., None],
                                       axis=-1)[..., 0]
            return (carry[0] + jnp.sum((lse - gold) * m_c),
                    carry[1] + jnp.sum(m_c)), None

    (tot, cnt), _ = jax.lax.scan(
        jax.checkpoint(body), (jnp.float32(0.0), jnp.float32(0.0)),
        (hs, ls, ms))
    return tot / jnp.maximum(cnt, 1.0)


def init_cache(cfg: ModelConfig, batch: int, seq_len: int, dtype=None):
    return tuple((jnp.zeros((batch, cfg.d_ff), dtype or cfg.dtype),
                  jnp.zeros((batch, cfg.d_ff), jnp.float32))
                 for _ in range(cfg.num_layers))


def prefill(cfg: ModelConfig, params: dict, batch: dict, states):
    tokens = batch["tokens"]
    x = jnp.take(params["embed"]["table"], tokens, axis=0)
    new_states = []
    for i in range(cfg.num_layers):
        x, h, c = _run_layer(params[f"lstm_{i}"], x, *states[i])
        new_states.append((h, c))
    return tuple(new_states), _logits(cfg, params, x[:, -1:])


def decode_step(cfg: ModelConfig, params: dict, states, token: jax.Array,
                pos: jax.Array):
    x = jnp.take(params["embed"]["table"], token, axis=0)
    new_states = []
    for i in range(cfg.num_layers):
        h, c = _cell(params[f"lstm_{i}"], x[:, 0], *states[i])
        x = h[:, None]
        new_states.append((h, c))
    return _logits(cfg, params, x), tuple(new_states)
