"""Sparse synchronization (RedSync §5.3/§5.4).

Message wire format (f32 vector, fixed capacity at trace time — the paper's
"(length, indices, values) packed into a single message"):

    [ count (i32 bitcast) | indices (i32 bitcast) x cap | payload ]

payload = values x cap (plain RGC) or a single scalar mean (quantized RGC).
Packing indices+values into ONE buffer mirrors §5.3 (single allgather instead
of two) and, on TPU, emits one ICI all-gather per fused group instead of two.

Tensor fusion (§5.3 "batch small allgather operations"): callers concatenate
many leaf messages into one flat buffer and allgather once; ``split_counts``
recovers the per-leaf segments.

Decompression (§5.4): scatter-add each worker's sparse message into the dense
f32 update — XLA scatter is the TPU-native cuSparse-axpyi analogue.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .selection import Selected


def _i2f(x: jax.Array) -> jax.Array:
    return jax.lax.bitcast_convert_type(x.astype(jnp.int32), jnp.float32)


def _f2i(x: jax.Array) -> jax.Array:
    return jax.lax.bitcast_convert_type(x, jnp.int32)


def message_len(capacity: int, quantized: bool) -> int:
    return 1 + capacity + (1 if quantized else capacity)


def pack_pieces(sel: Selected, quantized: bool) -> list[jax.Array]:
    """The wire-format segments of one message, in order (the single
    definition of the layout): ``[count | indices | payload]``. Callers
    concatenate — ``pack`` for one message, ``arena.pack_group`` for a
    whole arena's slot messages in one concatenate."""
    header = _i2f(sel.count[None])
    idx = _i2f(sel.indices)
    if quantized:
        denom = jnp.maximum(sel.count, 1).astype(jnp.float32)
        mean = (jnp.sum(sel.values) / denom)[None]
        return [header, idx, mean]
    return [header, idx, sel.values]


def pack(sel: Selected, quantized: bool) -> jax.Array:
    """Selected -> packed f32 wire message."""
    return jnp.concatenate(pack_pieces(sel, quantized))


def unpack_decompress(
    gathered: jax.Array, size: int, capacity: int, quantized: bool
) -> jax.Array:
    """[num_workers, msg_len] -> dense f32[size] SUM of all sparse messages.

    Padding indices (== size) and slots beyond each worker's ``count`` are
    dropped. Caller divides by N for the mean (Alg 1 line 7).
    """
    p = gathered.shape[0]
    counts = _f2i(gathered[:, 0])                      # [p]
    idx = _f2i(gathered[:, 1 : 1 + capacity])          # [p, cap]
    slot = jnp.arange(capacity)[None, :]
    live = slot < counts[:, None]
    if quantized:
        vals = jnp.broadcast_to(gathered[:, 1 + capacity][:, None], idx.shape)
    else:
        vals = gathered[:, 1 + capacity : 1 + 2 * capacity]
    # send dead slots out of range so 'drop' discards them
    idx = jnp.where(live, idx, size)
    dense = jnp.zeros((size,), jnp.float32)
    return dense.at[idx.reshape(-1)].add(vals.reshape(-1), mode="drop")


def sparse_allgather(msg: jax.Array, axes: tuple[str, ...]) -> jax.Array:
    """All-gather one packed message across the data-parallel mesh axes.

    Returns [num_workers, msg_len] with num_workers = prod(axis sizes).
    Empty ``axes`` (single-worker smoke paths) is the identity.

    The message travels as its int32 bits. XLA:TPU lowers this all-gather
    to an all-reduce (sum) of a zero buffer holding each worker's message
    in its own slot; on the f32 view that sum flushes the bitcast-int32
    counts and indices (denormals) to zero and drops every message. An
    integer sum with zeros moves the bits exactly.
    """
    if not axes:
        return msg[None]
    name = axes if len(axes) > 1 else axes[0]
    out = _i2f(jax.lax.all_gather(_f2i(msg), name))
    return out.reshape(-1, msg.shape[0])


def fused_allgather(messages: list[jax.Array], axes: tuple[str, ...]) -> list[jax.Array]:
    """Tensor fusion: concat all leaf messages -> ONE allgather -> split."""
    lens = [int(m.shape[0]) for m in messages]
    buf = jnp.concatenate(messages)
    gathered = sparse_allgather(buf, axes)             # [p, sum(lens)]
    return split_rows(gathered, lens)


def split_rows(gathered: jax.Array, lens: list[int]) -> list[jax.Array]:
    """[p, sum(lens)] fused buffer -> per-leaf [p, len] segments."""
    out, off = [], 0
    for length in lens:
        out.append(gathered[:, off : off + length])
        off += length
    return out


def hierarchical_allgather(msg: jax.Array, inter_axes: tuple[str, ...],
                           intra_axis: str | None,
                           sync_axes: tuple[str, ...] | None = None
                           ) -> jax.Array:
    """§5.4 two-level exchange: inter-node sparse allgather + intra-node
    dense psum.

    Hop 1 gathers the packed sparse messages over the (slow) inter-node
    axes only — each worker receives the messages of its same-local-rank
    peer on every node, so the expensive hop carries p/n_local messages
    instead of p. Hop 2 reassembles the full [p, len] message matrix over
    the (fast) intra-node axis as a dense psum: every worker scatters its
    inter-gathered rows into a zero-initialized full buffer at its own
    local-rank slot and the psum sums the disjoint contributions.

    The psum runs on the buffer bitcast to int32: each matrix entry is
    written by exactly one local worker (the rest contribute integer
    zeros), so integer addition makes the reassembly an exact bit move.
    An f32 psum would corrupt the message — the wire format embeds
    bitcast-int32 counts/indices whose f32 views are denormals, and
    backends running flush-to-zero (XLA:CPU reductions and TPU do) would zero
    them. Downstream decompression therefore sees byte-identical input to
    a flat ``sparse_allgather`` over the FULL axis tuple: rows come out
    inter-major, and when ``sync_axes`` names an order with the intra
    axis elsewhere than last (``jax.lax.all_gather`` over the joint axes
    is first-axis-major), the block is transposed back into that order —
    so parity with the flat gather holds for any ``intra_axis`` choice.
    """
    if intra_axis is None:
        return sparse_allgather(msg, inter_axes)
    if not inter_axes:
        return sparse_allgather(msg, (intra_axis,))
    g_inter = sparse_allgather(msg, inter_axes)        # [n_inter, len]
    n_local = jax.lax.axis_size(intra_axis)
    my_rank = jax.lax.axis_index(intra_axis)
    full = jnp.zeros((g_inter.shape[0], n_local, g_inter.shape[1]),
                     jnp.int32)
    full = jax.lax.dynamic_update_slice_in_dim(
        full, _f2i(g_inter)[:, None, :], my_rank, axis=1)
    full = jax.lax.psum(full, intra_axis)
    out = _i2f(full)                                   # [n_inter, n_local, L]
    if sync_axes and tuple(sync_axes) != tuple(inter_axes) + (intra_axis,):
        sizes = [jax.lax.axis_size(a) for a in inter_axes]
        out = out.reshape(*sizes, n_local, out.shape[-1])
        out = jnp.moveaxis(out, len(sizes), sync_axes.index(intra_axis))
    return out.reshape(-1, msg.shape[0])


def dense_allreduce_mean(grad: jax.Array, axes: tuple[str, ...]) -> jax.Array:
    """Paper's dense fallback / baseline: allreduce-mean over workers."""
    g = grad.astype(jnp.float32)
    return jax.lax.pmean(g, axes) if axes else g
