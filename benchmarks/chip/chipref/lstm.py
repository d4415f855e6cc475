"""Plain reference of the paper's language model: a 2-layer LSTM with
untied input and output embeddings (Press & Wolf 2016; RedSync §6.2).

Layout of the weights (the layout the trainer takes them in):
``embed.table [V, E]``, ``lstm_i.{wx [in, 4H], wh [H, 4H], b [4H]}`` with
gates in the order input, forget, cell, output, ``lm_head [H, V]`` and
``lm_bias [V]``. The forget gate carries a constant bias of 1. The loss
is the mean next-token cross-entropy over every position but the last.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .numerics import Numerics


def dims(cfg: dict) -> tuple[int, int, int, int]:
    return (cfg["vocab_size"], cfg["embedding_size"], cfg["hidden_size"],
            cfg["num_layers"])


def weight_specs(cfg: dict) -> dict:
    """``(shape, std)`` per parameter; std 0 means zeros."""
    v, e, h, n = dims(cfg)
    specs: dict = {
        "embed": {"table": ((v, e), 0.05)},
        "lm_head": ((h, v), 0.5 / h ** 0.5),
        "lm_bias": ((v,), 0.0),
    }
    for i in range(n):
        d_in = e if i == 0 else h
        specs[f"lstm_{i}"] = {"wx": ((d_in, 4 * h), 0.5 / d_in ** 0.5),
                              "wh": ((h, 4 * h), 0.5 / h ** 0.5),
                              "b": ((4 * h,), 0.0)}
    return specs


def nll_sum(cfg: dict, params: dict, tokens: jax.Array,
            nx: Numerics) -> tuple[jax.Array, jax.Array]:
    """(sum of next-token negative log-likelihoods, number of targets)."""
    p = jax.tree.map(nx.cast, params)
    x = p["embed"]["table"][tokens]
    b = tokens.shape[0]
    for i in range(cfg["num_layers"]):
        lp = p[f"lstm_{i}"]
        h0 = jnp.zeros((b, cfg["hidden_size"]), nx.dtype)

        def cell(carry, x_t, lp=lp):
            h, c = carry
            z = nx.mm(x_t, lp["wx"]) + nx.mm(h, lp["wh"]) + lp["b"]
            i_g, f_g, g_g, o_g = jnp.split(z, 4, axis=-1)
            c = jax.nn.sigmoid(f_g + 1.0) * c + jax.nn.sigmoid(i_g) * jnp.tanh(g_g)
            h = jax.nn.sigmoid(o_g) * jnp.tanh(c)
            return (h, c), h

        _, hs = jax.lax.scan(cell, (h0, h0), x.swapaxes(0, 1))
        x = hs.swapaxes(0, 1)
    logits = nx.mm(x[:, :-1], p["lm_head"]) + p["lm_bias"]
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    return (jnp.sum((lse - gold).astype(jnp.float32)),
            jnp.float32(gold.size))


def flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward matmul FLOPs per trained token (3 x 2 x MACs):
    both LSTM layers at every position, the softmax layer at the
    ``seq - 1`` positions that have a target."""
    v, e, h, n = dims(cfg)
    lstm_macs = sum(((e if i == 0 else h) + h) * 4 * h for i in range(n))
    head_macs = h * v * (seq - 1) / seq
    return 6.0 * (lstm_macs + head_macs)
