"""``paper-lstm``-style configurations as the program's ModelConfig."""
from __future__ import annotations

import jax.numpy as jnp


def model_config(cfg: dict):
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=cfg["name"], family="lstm", num_layers=cfg["num_layers"],
        d_model=cfg["embedding_size"], num_heads=1, num_kv_heads=1,
        head_dim=cfg["hidden_size"], d_ff=cfg["hidden_size"],
        vocab_size=cfg["vocab_size"], tie_embeddings=False,
        dtype=jnp.dtype(cfg["dtype"]), **cfg.get("program", {}))
