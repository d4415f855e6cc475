"""How a reference computes: the exact reference and its lower-precision
controls.

``REFERENCE`` keeps every array in float32 and runs every matmul at
``highest`` precision. A control is the same reference computed one step
of precision below what a configuration states:

* ``bf16``: every parameter, activation, gradient and optimizer buffer in
  bfloat16 (the control of a float32 configuration);
* ``fp8``: every matmul operand rounded to float8 (e4m3) with one scale
  per operand, the rest as the configuration states (the control of a
  bfloat16 configuration).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def _fp8(x: jax.Array) -> jax.Array:
    """Round to float8 e4m3 under one scale for the whole operand."""
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return jax.lax.stop_gradient(q * scale - x) + x


@dataclass(frozen=True)
class Numerics:
    name: str
    dtype: object          # parameters, activations and optimizer state
    operand: str           # "exact" | "fp8": rounding of matmul operands
    params: bool = False   # store the parameters in ``dtype`` too

    def cast(self, x: jax.Array) -> jax.Array:
        return x.astype(self.dtype)

    def mm(self, a: jax.Array, b: jax.Array) -> jax.Array:
        if self.operand == "fp8":
            a, b = _fp8(a), _fp8(b)
        out = jnp.matmul(a.astype(self.dtype), b.astype(self.dtype),
                         precision=HIGHEST)
        return out.astype(self.dtype)

    def einsum(self, spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
        if self.operand == "fp8":
            a, b = _fp8(a), _fp8(b)
        return jnp.einsum(spec, a.astype(self.dtype), b.astype(self.dtype),
                          precision=HIGHEST).astype(self.dtype)


REFERENCE = Numerics("reference", jnp.float32, "exact")
CONTROLS = {
    "bf16": Numerics("bf16", jnp.bfloat16, "exact", params=True),
    "fp8": Numerics("fp8", jnp.float32, "fp8"),
}
