"""Mesh factories (TPU v5e target).

Single pod: 16x16 = 256 chips, axes ("data", "model").
Multi-pod:  2x16x16 = 512 chips, axes ("pod", "data", "model") — the "pod"
axis is an additional pure data-parallel dimension over DCI; RGC's sparse
allgather syncs over ("pod", "data").

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run needs to set XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(data: int = 4, model: int = 2):
    """Small ("data", "model") mesh over host devices (tests / examples)."""
    return _make_mesh((data, model), ("data", "model"))


def make_data_mesh(num_devices: int | None = None):
    """Pure data-parallel 1-D ``("data",)`` mesh over the first
    ``num_devices`` of ``jax.devices()`` (all when None) — the paper's
    setting: params replicated, one worker per device."""
    n = len(jax.devices()) if num_devices is None else num_devices
    return _make_mesh((n,), ("data",))


def mesh_from_spec(spec: str | None):
    """The launcher's ``--mesh`` value as a mesh: None -> single device
    (no mesh); ``pod`` / ``2pod`` -> production meshes; ``Dx1`` -> the
    pure ``("data",)`` mesh over D devices; ``DxM`` -> ("data", "model")."""
    if not spec:
        return None
    if spec == "pod":
        return make_production_mesh(multi_pod=False)
    if spec == "2pod":
        return make_production_mesh(multi_pod=True)
    d, m = (int(x) for x in spec.split("x"))
    return make_data_mesh(d) if m == 1 else make_host_mesh(d, m)
