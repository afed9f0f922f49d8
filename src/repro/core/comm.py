"""Collective helpers for expert parallelism (paper §3.2 "global data
exchange") + beyond-paper hierarchical variants for the multi-pod mesh."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.obs.scopes import EXCHANGE, scoped


@scoped(EXCHANGE)
def exchange_counts(counts: jax.Array, axis: str) -> jax.Array:
    """Fig 2 step 1: exchange per-expert token counts over the expert axis.

    counts: (E,) local assignment counts, E = mp * E_local.
    returns (mp, E_local): incoming token counts per source rank.
    """
    mp = jax.lax.axis_size(axis)
    return jax.lax.all_to_all(counts.reshape(mp, -1), axis, 0, 0, tiled=True)


@scoped(EXCHANGE)
def exchange_tokens(buf: jax.Array, axis: str) -> jax.Array:
    """Fig 2 step 2: payload all-to-all.  buf (E, C, d) -> (E_local, mp*C, d)."""
    mp = jax.lax.axis_size(axis)
    E, C, d = buf.shape
    buf = buf.reshape(mp, E // mp, C, d)
    buf = jax.lax.all_to_all(buf, axis, 0, 0, tiled=True)
    return buf.transpose(1, 0, 2, 3).reshape(E // mp, mp * C, d)


@scoped(EXCHANGE)
def return_tokens(out: jax.Array, axis: str) -> jax.Array:
    """Inverse of :func:`exchange_tokens`: (E_local, mp*C, d) -> (E, C, d)."""
    mp = jax.lax.axis_size(axis)
    E_local, n, d = out.shape
    C = n // mp
    out = out.reshape(E_local, mp, C, d).transpose(1, 0, 2, 3)
    out = jax.lax.all_to_all(out, axis, 0, 0, tiled=True)
    return out.reshape(E_local * mp, C, d)


def native_ragged_all_to_all() -> bool:
    """True when the devices of the mesh being traced implement XLA's
    ragged-all-to-all (the TPU does; XLA:CPU leaves it unimplemented).

    Read from the abstract mesh's device kind, so a compile for a described
    TPU takes the native branch even in a process whose default backend is
    the CPU; outside a mesh the default backend decides.
    """
    dev = jax.sharding.get_abstract_mesh().abstract_device
    kind = dev.device_kind if dev is not None else jax.default_backend()
    return kind.lower() != "cpu"


@scoped(EXCHANGE)
def ragged_all_to_all_shards(send, send_sizes, recv_sizes, axis):
    """Exchange ``(mp, bound, ...)`` per-peer shards, valid-prefix ragged.

    ``send[p, :send_sizes[p]]`` are the rows for peer ``p`` (zero padding
    after); the result holds ``recv[s, :recv_sizes[s]]`` rows from source
    ``s`` (zero padding after) — i.e. exactly what a dense tiled dim-0
    all-to-all of the padded shards returns when padding is zeros.

    Where :func:`native_ragged_all_to_all` holds, only the valid prefixes
    go through ``lax.ragged_all_to_all``; otherwise the dense bounded-shard
    all-to-all moves the full static buffer.  Both branches return
    bit-identical arrays, so callers never see which transport ran.
    """
    if not native_ragged_all_to_all():
        return jax.lax.all_to_all(send, axis, 0, 0, tiled=True)
    mp, bound = send.shape[0], send.shape[1]
    flat = send.reshape(mp * bound, *send.shape[2:])
    offs = jnp.arange(mp, dtype=jnp.int32) * bound
    # my segment for peer p starts at p*bound locally and must land at slot
    # (my_rank * bound) on peer p — the same place the dense exchange puts it
    my = jax.lax.axis_index(axis).astype(jnp.int32) * bound
    out = jax.lax.ragged_all_to_all(
        flat, jnp.zeros_like(flat), offs, jnp.asarray(send_sizes, jnp.int32),
        jnp.full((mp,), my, jnp.int32), jnp.asarray(recv_sizes, jnp.int32),
        axis_name=axis)
    return out.reshape(send.shape)


@scoped(EXCHANGE)
def exchange_ragged(send: jax.Array, counts: jax.Array, axis, mp: int, *,
                    n_chunks: int = 1, wire_dtype=None, fill_fn=None):
    """Ragged (dropless) global data exchange, forward direction.

    send: (mp, bound, d) pad-to-max-per-peer shards; counts: (mp, E_local)
    kept rows per (destination rank, its expert) — the explicit valid
    lengths of the variable-size exchange.  Returns ``(recv, incoming,
    fill_out)``: the received shards, the counts arriving from each source
    rank (which size the receiver's compaction — core/dispatch
    ragged_recv_compact), and the optional shadow-filler output.

    With ``n_chunks > 1`` both the counts and payload exchanges are
    ppermute-decomposed (no blocking all-to-all in the HLO at all).
    """
    from repro.core import pipeline

    incoming = pipeline.counts_all_to_all(counts, axis, mp,
                                          decompose=n_chunks > 1)
    recv, fill_out = pipeline.ragged_pipelined_exchange(
        send, axis, mp, n_chunks, fill_fn=fill_fn, wire_dtype=wire_dtype)
    return recv, incoming, fill_out


@scoped(EXCHANGE)
def return_ragged(out: jax.Array, axis, mp: int, *, n_chunks: int = 1,
                  wire_dtype=None) -> jax.Array:
    """Inverse of :func:`exchange_ragged`'s payload leg: (mp, bound, d_out)
    expert outputs travel back to their source ranks, landing in the same
    slots the sources sent from (the tiled a2a is its own inverse)."""
    from repro.core import pipeline

    return pipeline.chunked_all_to_all(out, axis, mp, n_chunks,
                                       wire_dtype=wire_dtype,
                                       decompose=n_chunks > 1)


@scoped(EXCHANGE)
def exchange_ragged_intra(send: jax.Array, counts: jax.Array, inner_axis,
                          n_inner: int, *, decompose: bool = False,
                          wire_dtype=None):
    """Hop 1 of the two-level ragged exchange: aggregate within the node.

    send: (n_nodes, n_inner, bound, d) per-peer shards, peers node-major
    (rank = node * n_inner + inner); counts: (n_nodes, n_inner, E_local) the
    matching kept-row counts.  Both run a dim-1 all-to-all over the fast
    node-local axis, after which this rank is its node's *forwarding agent*
    for its own inner slot: entry ``[o, s]`` is sibling ``s``'s shard (and
    counts) destined for rank ``(o, my_inner)`` of every node ``o`` — ready
    for the node-level compaction (core/dispatch.make_hier_agg) that strips
    per-source padding off the slow inter-node leg.
    """
    from repro.core import pipeline

    shards = pipeline.all_to_all_dim1(send, inner_axis, n_inner,
                                      decompose=decompose,
                                      wire_dtype=wire_dtype)
    cnt = pipeline.all_to_all_dim1(counts, inner_axis, n_inner,
                                   decompose=decompose)
    return shards, cnt


@scoped(EXCHANGE)
def return_ragged_intra(out: jax.Array, inner_axis, n_inner: int, *,
                        decompose: bool = False, wire_dtype=None) -> jax.Array:
    """Inverse of :func:`exchange_ragged_intra`'s payload hop: de-aggregated
    (n_nodes, n_inner, bound, d_out) outputs travel back to their source
    siblings (the dim-1 tiled a2a is its own inverse)."""
    from repro.core import pipeline

    return pipeline.all_to_all_dim1(out, inner_axis, n_inner,
                                    decompose=decompose, wire_dtype=wire_dtype)


@scoped(EXCHANGE)
def exchange_ragged_inter(slim: jax.Array, kept_counts: jax.Array, node_axis,
                          n_nodes: int, *, n_chunks: int = 1, wire_dtype=None,
                          fill_fn=None):
    """Hop 2 of the two-level ragged exchange: the slim inter-node leg.

    slim: (n_nodes, inter_bound, d) aggregated per-node shards (only
    truly-needed rows + tail padding); kept_counts: (n_nodes, n_inner,
    E_local) full per-source-rank granularity, so the receiver can rebuild
    the exact flat-path compaction.  Unless the leg is ppermute-decomposed,
    the payload goes through :func:`ragged_all_to_all_shards` (only valid
    prefixes cross the wire where the devices implement the native
    primitive); otherwise the bounded-shard exchange moves the static
    buffer.  Returns ``(recv, incoming, fill_out)`` like
    :func:`exchange_ragged`.
    """
    from repro.core import pipeline

    incoming = pipeline.counts_all_to_all(
        kept_counts.reshape(n_nodes, -1), node_axis, n_nodes,
        decompose=n_chunks > 1).reshape(kept_counts.shape)
    if n_chunks <= 1:
        orig = slim.dtype
        w, wd = pipeline._to_wire(slim, orig, wire_dtype)
        recv = pipeline._from_wire(
            ragged_all_to_all_shards(
                w, kept_counts.sum(axis=(1, 2)), incoming.sum(axis=(1, 2)),
                node_axis), orig, wd)
        return recv, incoming, (fill_fn() if fill_fn is not None else None)
    recv, fill_out = pipeline.ragged_pipelined_exchange(
        slim, node_axis, n_nodes, n_chunks, fill_fn=fill_fn,
        wire_dtype=wire_dtype)
    return recv, incoming, fill_out


@scoped(EXCHANGE)
def return_ragged_inter(out: jax.Array, kept_counts: jax.Array,
                        incoming: jax.Array, node_axis, n_nodes: int, *,
                        n_chunks: int = 1, wire_dtype=None) -> jax.Array:
    """Inverse of :func:`exchange_ragged_inter`'s payload leg (sizes swap
    roles: each rank returns what it received, gets back what it sent)."""
    from repro.core import pipeline

    if n_chunks <= 1:
        orig = out.dtype
        w, wd = pipeline._to_wire(out, orig, wire_dtype)
        return pipeline._from_wire(
            ragged_all_to_all_shards(
                w, incoming.sum(axis=(1, 2)), kept_counts.sum(axis=(1, 2)),
                node_axis), orig, wd)
    return pipeline.chunked_all_to_all(out, node_axis, n_nodes, n_chunks,
                                       wire_dtype=wire_dtype,
                                       decompose=n_chunks > 1)


@scoped(EXCHANGE)
def hierarchical_all_to_all(buf: jax.Array, inner_axis: str,
                            outer_axis: str) -> jax.Array:
    """Beyond-paper: 2-hop all-to-all for multi-pod meshes.

    Cross-pod ICI/DCN links are far slower than intra-pod links, so exchange
    pod-locally first (aggregating messages destined for the same remote pod)
    and then do one large cross-pod exchange: (outer, inner, ...) layout.

    buf: (n_outer, n_inner, chunk...) — dim0 indexes destination outer rank,
    dim1 destination inner rank.
    """
    # hop 1: intra-pod exchange over the inner axis (fast links) so each inner
    # rank holds the traffic of its whole pod destined for one inner-peer slot
    buf = jax.lax.all_to_all(buf, inner_axis, 1, 1, tiled=True)
    # hop 2: cross-pod exchange over the outer (slow) axis, fully aggregated
    buf = jax.lax.all_to_all(buf, outer_axis, 0, 0, tiled=True)
    return buf


@scoped(EXCHANGE)
def all_to_all_bf16(buf: jax.Array, axis: str, split_axis: int = 0,
                    concat_axis: int = 0) -> jax.Array:
    """Beyond-paper: cast payload to bf16 across the wire (halves collective
    bytes; combine-weight math stays f32)."""
    orig = buf.dtype
    out = jax.lax.all_to_all(buf.astype(jnp.bfloat16), axis, split_axis,
                             concat_axis, tiled=True)
    return out.astype(orig)
