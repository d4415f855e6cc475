"""Transport backends: wire packing + collectives over ``sync_axes``.

Five registered backends (§5.3/§5.4):

* ``fused_allgather``   — tensor fusion: concatenate every leaf message
                          into ONE buffer, a single allgather, then split
                          (§5.3 "batch small allgather operations").
* ``bucketed_allgather`` — tensor fusion with a byte budget: messages are
                          greedily packed into contiguous fixed-byte
                          buckets (``bucket_bytes``) and each bucket runs
                          one fused allgather. Bounds the collective
                          buffer (no single giant concat) while still
                          amortizing launch latency — the §5.3 trade-off
                          made tunable. Delivers byte-identical gathered
                          rows to ``fused_allgather``.
* ``hierarchical``      — §5.4 two-level exchange on a 2-axis mesh: a
                          sparse allgather over the inter-node axes
                          composed with a dense psum over the intra-node
                          axis (``sync.hierarchical_allgather``). The slow
                          hop carries p/n_local messages instead of p;
                          reassembly is bit-exact (disjoint psum), so
                          results match ``fused_allgather`` bitwise.
                          Small dense leaves ride the ordinary joint
                          pmean — XLA already routes dense allreduce
                          hierarchically on real topologies, and keeping
                          it joint preserves bitwise parity with the flat
                          transports.
* ``per_leaf_allgather`` — one collective per leaf (the unfused baseline;
                          what fig10's per-message latency term models).
* ``dense_psum``        — dense-only baseline; receiving a sparse message
                          is a configuration error.

All backends share the packed wire format of ``core.sync`` and the dense
psum fallback for small leaves, and accept a ``StageTimer`` hook
(``core.instrument``) for counter-grade facts (``collectives`` and
``messages`` per step). Transports consume *messages*, not leaves: with
``fuse_leaves`` the sync loop hands over ONE pre-packed buffer per
residual arena (``core.arena.pack_group``) which feeds straight into the
fusion/bucketing logic here — the per-leaf transport semantics are
unchanged, there are simply O(arenas) messages instead of O(leaves).
Outside a mesh (``sync_axes=()``) every collective degrades to the
single-worker identity, which is what the CPU smoke tests run.
"""
from __future__ import annotations

from typing import Any

import jax

from . import registry
from . import sync as sync_lib
from .instrument import NullTimer
from .selection import Selected

# Default fused-bucket byte budget. 4 MiB keeps each collective buffer
# well inside ICI/NIC message-size sweet spots while still fusing
# hundreds of small-leaf messages per bucket.
DEFAULT_BUCKET_BYTES = 4 * 1024 * 1024


def assign_buckets(nbytes: list[int], bucket_bytes: int) -> list[list[int]]:
    """Greedy contiguous bucketing of message byte sizes.

    Message ``i`` joins the current bucket unless that would push the
    bucket past ``bucket_bytes``; a message larger than the budget on its
    own still gets a (singleton) bucket — nothing is ever dropped or
    split. Contiguity preserves leaf order, so concat/split offsets match
    the fused layout within each bucket.
    """
    if bucket_bytes <= 0:
        raise ValueError(f"bucket_bytes must be > 0, got {bucket_bytes}")
    buckets: list[list[int]] = []
    cur: list[int] = []
    cur_bytes = 0
    for i, nb in enumerate(nbytes):
        if cur and cur_bytes + nb > bucket_bytes:
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += nb
    if cur:
        buckets.append(cur)
    return buckets


class _Base:
    name = "?"

    def __init__(self, sync_axes: tuple[str, ...] = (), timer=None):
        self.sync_axes = tuple(sync_axes)
        self.timer = timer if timer is not None else NullTimer()

    def num_workers(self) -> int:
        n = 1
        for ax in self.sync_axes:
            n *= jax.lax.axis_size(ax)
        return n

    def pack(self, sel: Selected, quantized: bool) -> jax.Array:
        return sync_lib.pack(sel, quantized)

    def allreduce_mean(self, grad: jax.Array) -> jax.Array:
        return sync_lib.dense_allreduce_mean(grad, self.sync_axes)

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"<transport {self.name} axes={self.sync_axes}>"


class FusedAllgather(_Base):
    name = "fused_allgather"

    def allgather(self, messages: list[jax.Array]) -> list[jax.Array]:
        if not messages:
            return []
        self.timer.count("messages", len(messages))
        self.timer.count("collectives")
        return sync_lib.fused_allgather(messages, self.sync_axes)


class BucketedAllgather(_Base):
    """§5.3 fusion under a byte budget: one fused allgather per bucket."""

    name = "bucketed_allgather"

    def __init__(self, sync_axes: tuple[str, ...] = (),
                 bucket_bytes: int = DEFAULT_BUCKET_BYTES, timer=None):
        super().__init__(sync_axes, timer)
        self.bucket_bytes = int(bucket_bytes)

    def allgather(self, messages: list[jax.Array]) -> list[jax.Array]:
        if not messages:
            return []
        nbytes = [int(m.shape[0]) * m.dtype.itemsize for m in messages]
        buckets = assign_buckets(nbytes, self.bucket_bytes)
        self.timer.count("messages", len(messages))
        self.timer.count("buckets", len(buckets))
        self.timer.count("collectives", len(buckets))
        out: list[jax.Array | None] = [None] * len(messages)
        for idxs in buckets:
            gathered = sync_lib.fused_allgather(
                [messages[i] for i in idxs], self.sync_axes)
            for i, g in zip(idxs, gathered):
                out[i] = g
        return out


class HierarchicalAllgather(_Base):
    """§5.4 intra-node dense psum + inter-node sparse allgather.

    ``intra_axis`` names the fast (intra-node) mesh axis; every other
    sync axis forms the slow inter-node hop. Defaults to the LAST sync
    axis — on the harness's ``("node", "local")`` mesh that is "local",
    and on the production multi-pod ``("pod", "data")`` batch axes it is
    "data" (ICI) with "pod" (DCI) as the inter hop. With fewer than two
    sync axes there is no hierarchy to exploit and the transport degrades
    to the flat fused gather.
    """

    name = "hierarchical"

    def __init__(self, sync_axes: tuple[str, ...] = (),
                 intra_axis: str | None = None, timer=None):
        super().__init__(sync_axes, timer)
        if intra_axis is not None and intra_axis not in self.sync_axes:
            raise ValueError(
                f"intra_axis {intra_axis!r} not among sync_axes "
                f"{self.sync_axes}")
        if intra_axis is None and len(self.sync_axes) >= 2:
            intra_axis = self.sync_axes[-1]
        self.intra_axis = intra_axis if len(self.sync_axes) >= 2 else None
        self.inter_axes = tuple(a for a in self.sync_axes
                                if a != self.intra_axis)

    def allgather(self, messages: list[jax.Array]) -> list[jax.Array]:
        if not messages:
            return []
        # same §5.3 fusion as fused_allgather, then the two-level exchange
        lens = [int(m.shape[0]) for m in messages]
        buf = jax.numpy.concatenate(messages)
        self.timer.count("messages", len(messages))
        self.timer.count("collectives", 2 if self.intra_axis else 1)
        gathered = sync_lib.hierarchical_allgather(
            buf, self.inter_axes, self.intra_axis, self.sync_axes)
        return sync_lib.split_rows(gathered, lens)


class PerLeafAllgather(_Base):
    name = "per_leaf_allgather"

    def allgather(self, messages: list[jax.Array]) -> list[jax.Array]:
        self.timer.count("messages", len(messages))
        self.timer.count("collectives", len(messages))
        return [sync_lib.sparse_allgather(m, self.sync_axes)
                for m in messages]


class DensePsum(_Base):
    name = "dense_psum"

    def allgather(self, messages: list[jax.Array]) -> list[jax.Array]:
        if messages:
            raise NotImplementedError(
                "dense_psum transport cannot carry sparse messages; use "
                "fused_allgather/per_leaf_allgather or a dense-only "
                "dispatch policy")
        return []


@registry.register(registry.TRANSPORT, "fused_allgather")
def _fused(sync_axes: tuple[str, ...] = (), timer=None,
           **_: Any) -> FusedAllgather:
    return FusedAllgather(sync_axes, timer=timer)


@registry.register(registry.TRANSPORT, "bucketed_allgather")
def _bucketed(sync_axes: tuple[str, ...] = (),
              bucket_bytes: int = DEFAULT_BUCKET_BYTES, timer=None,
              **_: Any) -> BucketedAllgather:
    return BucketedAllgather(sync_axes, bucket_bytes=bucket_bytes,
                             timer=timer)


@registry.register(registry.TRANSPORT, "hierarchical")
def _hierarchical(sync_axes: tuple[str, ...] = (),
                  intra_axis: str | None = None, timer=None,
                  **_: Any) -> HierarchicalAllgather:
    return HierarchicalAllgather(sync_axes, intra_axis=intra_axis,
                                 timer=timer)


@registry.register(registry.TRANSPORT, "per_leaf_allgather")
def _per_leaf(sync_axes: tuple[str, ...] = (), timer=None,
              **_: Any) -> PerLeafAllgather:
    return PerLeafAllgather(sync_axes, timer=timer)


@registry.register(registry.TRANSPORT, "dense_psum")
def _dense_psum(sync_axes: tuple[str, ...] = (), timer=None,
                **_: Any) -> DensePsum:
    return DensePsum(sync_axes, timer=timer)
