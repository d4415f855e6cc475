"""Pallas TPU kernels for RedSync's compression hot spots.

Compiled on TPU (``tests/test_tpu_compile.py`` compiles each for a v5e
at published-width sizes) and interpreted on CPU, where the tests
compare them with the jnp twins in ``ref.py``.
"""
from . import ops, ref
