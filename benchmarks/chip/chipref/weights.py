"""Weights from a seed: every leaf drawn on the device in one jitted call.

A spec tree maps each parameter to ``(shape, std)``; ``std == 0`` makes
zeros. The leaves are drawn in float32 from keys split off one root key
and cast to the dtype the configuration trains in. Seeds may exceed 32
bits: the root key folds in the low and the high word.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def seed_words(seed: int) -> tuple[int, int]:
    seed = int(seed)
    return seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF


def _root(lo: jax.Array, hi: jax.Array) -> jax.Array:
    key = jax.random.PRNGKey(0)
    return jax.random.fold_in(jax.random.fold_in(key, lo), hi)


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def _draw(specs, dtype, lo, hi):
    leaves, treedef = jax.tree.flatten(specs, is_leaf=_is_spec)
    keys = jax.random.split(_root(lo, hi), len(leaves))
    out = []
    for key, (shape, std) in zip(keys, leaves):
        if std == 0:
            out.append(jnp.zeros(shape, dtype))
        else:
            out.append((std * jax.random.normal(key, shape, jnp.float32))
                       .astype(dtype))
    return jax.tree.unflatten(treedef, out)


def make(specs, dtype, seed: int, out_shardings=None):
    """The weights of ``specs`` for ``seed``, made on the device."""
    fn = jax.jit(partial(_draw, specs, dtype), out_shardings=out_shardings)
    lo, hi = seed_words(seed)
    return fn(jnp.uint32(lo), jnp.uint32(hi))
