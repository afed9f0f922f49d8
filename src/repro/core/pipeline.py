"""Smart-schedule overlap — the paper's §5.2 pipelined global data exchange.

FastMoE's headline distributed speedup comes from partitioning the all-to-all
into groups so that sending, receiving and expert computation overlap.  The
XLA analogue: split the ``(mp, E_local, C, d)`` exchange buffer into
``n_chunks`` micro-shards along the capacity dim and emit a software-pipelined
schedule whose *dependency structure* permits overlap —

    S0 | S1  C0  R0 | S2  C1  R1 | ...  C_{n-1}  R_{n-1}

where S_i / R_i are chunk i's forward / return exchanges and C_i its expert
compute.  Chunk i+1's send is issued *before* chunk i's compute, so no
collective ever waits on the compute preceding it in program order, and XLA's
async collective scheduler can keep the ICI links and the MXU busy at the
same time.  Each exchange is further decomposed into ``mp - 1``
``ppermute``s (+ a local copy): ``collective-permute`` is the op XLA turns
into asynchronous ``-start``/``-done`` pairs, whereas a monolithic
``all-to-all`` is scheduled as one blocking step.

The schedule is *bit-exact* vs. the serial path: chunking the capacity dim
never regroups any expert's row reduction, and the decomposed exchange moves
identical bytes to identical slots.

Shadowed hot experts (repro/placement/shadow.py) slot in as overlap filler:
their local, exchange-free compute is issued right after the first send, i.e.
inside the bubble the serial schedule would spend blocked on the wire.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax.numpy as jnp
from jax import lax

from repro.obs.scopes import EXCHANGE, scoped


_BITS_FOR_BYTES = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32, 8: jnp.uint64}


def _to_wire(x: jnp.ndarray, orig, wire_dtype):
    """Narrow to the wire dtype AND bitcast to the same-width unsigned int.

    The bitcast is load-bearing: XLA's convert-mover hoists a plain
    ``convert`` past data-movement ops, so a bf16-cast payload would cross
    the wire as the full-width original dtype (observed on XLA:CPU) — the
    collective must *operand-type* at the wire width to actually shrink.
    A bitcast round-trip is bit-exact, so values are unchanged.
    """
    if wire_dtype is None or jnp.dtype(wire_dtype) == jnp.dtype(orig):
        return x, None
    w = x.astype(wire_dtype)
    return (lax.bitcast_convert_type(
        w, _BITS_FOR_BYTES[jnp.dtype(wire_dtype).itemsize]), wire_dtype)


def _from_wire(x: jnp.ndarray, orig, wire_dtype):
    if wire_dtype is None:
        return x.astype(orig)
    return lax.bitcast_convert_type(x, wire_dtype).astype(orig)


@scoped(EXCHANGE)
def ppermute_all_to_all(x: jnp.ndarray, axis, mp: int, *,
                        wire_dtype=None) -> jnp.ndarray:
    """``lax.all_to_all(x, axis, 0, 0, tiled=True)`` as mp-1 collective-permutes.

    x: (mp * n, ...) per-rank, dim 0 major-ordered by destination rank.
    ``axis`` may be a tuple of mesh axes (row-major linearization, matching
    ``lax.all_to_all``); ``mp`` is its static total size.  ``wire_dtype``
    casts the payload across the wire only (output dtype preserved).

    Shift s moves rank r's slice for rank (r+s)%mp; the receiver q writes it
    at slot (q-s)%mp — exactly where all_to_all concatenates the data coming
    from rank (q-s)%mp.  Each shift is an independent collective-permute, so
    XLA may issue all of them (and overlap them with unrelated compute)
    instead of scheduling one blocking fused all-to-all.
    """
    orig = x.dtype
    x, wd = _to_wire(x, orig, wire_dtype)
    if mp == 1:
        return _from_wire(x, orig, wd)
    n = x.shape[0] // mp
    idx = lax.axis_index(axis)
    own = lax.dynamic_slice_in_dim(x, idx * n, n, 0)
    out = lax.dynamic_update_slice_in_dim(jnp.zeros_like(x), own, idx * n, 0)
    for s in range(1, mp):
        send = lax.dynamic_slice_in_dim(x, ((idx + s) % mp) * n, n, 0)
        recv = lax.ppermute(send, axis,
                            [(r, (r + s) % mp) for r in range(mp)])
        out = lax.dynamic_update_slice_in_dim(out, recv,
                                              ((idx - s) % mp) * n, 0)
    return _from_wire(out, orig, wd)


@scoped(EXCHANGE)
def chunked_all_to_all(x: jnp.ndarray, axis, mp: int, n_chunks: int, *,
                       wire_dtype=None, decompose: bool = True) -> jnp.ndarray:
    """Tiled dim-0 all-to-all split into ``n_chunks`` independent exchanges.

    x: (mp, ...) per-rank (one slice per destination).  The chunk dim is
    x.shape[1], which must divide by ``n_chunks``.  Pure data movement —
    bit-exact vs. the monolithic collective for any chunking.
    """
    a2a = functools.partial(
        ppermute_all_to_all if decompose else _plain_all_to_all,
        axis=axis, mp=mp, wire_dtype=wire_dtype)
    if n_chunks <= 1:
        return a2a(x)
    return jnp.concatenate([a2a(c) for c in jnp.split(x, n_chunks, axis=1)],
                           axis=1)


@scoped(EXCHANGE)
def _plain_all_to_all(x, *, axis, mp, wire_dtype=None):
    del mp
    orig = x.dtype
    x, wd = _to_wire(x, orig, wire_dtype)
    return _from_wire(lax.all_to_all(x, axis, 0, 0, tiled=True), orig, wd)


@scoped(EXCHANGE)
def counts_all_to_all(counts: jnp.ndarray, axis, mp: int, *,
                      decompose: bool) -> jnp.ndarray:
    """The Fig-2 "exchange sizes" step: (mp, E_local) per-destination counts
    -> (mp, E_local) per-source counts.  ``decompose`` swaps the blocking
    all-to-all for mp-1 collective-permutes so the pipelined schedules'
    HLO contains no blocking exchange at all (capacity and ragged paths
    share this helper — their wire behavior must not drift apart)."""
    if decompose:
        return ppermute_all_to_all(counts, axis, mp)
    return lax.all_to_all(counts, axis, 0, 0, tiled=True)


def wire_fraction(mp: int, *, decompose: bool) -> float:
    """Fraction of a tiled dim-0 exchange that actually crosses the wire.

    The ppermute decomposition keeps each rank's own slice on-chip (only the
    mp-1 shifted slices move), so decomposed exchanges transfer (mp-1)/mp of
    the nominal buffer — which is also exactly what the optimized HLO's
    collective-permute output bytes sum to, keeping the device-side wire
    counters (repro.obs.counters) 1:1 comparable with
    ``roofline.collective_bytes``.  A monolithic all-to-all is accounted at
    its full output size, matching its HLO op.
    """
    return (mp - 1) / mp if (decompose and mp > 0) else 1.0


def resolve_chunks(requested: int, capacity: int) -> int:
    """Largest divisor of ``capacity`` that is <= ``requested`` (>= 1).

    The micro-shard split must tile the static capacity exactly; rather than
    failing on awkward (capacity, n_chunks) pairs, degrade to the nearest
    feasible pipeline depth (1 = serial).
    """
    n = max(1, min(int(requested), int(capacity)))
    while capacity % n:
        n -= 1
    return n


@scoped(EXCHANGE)
def ragged_pipelined_exchange(send: jnp.ndarray, axis, mp: int, n_chunks: int,
                              *, fill_fn: Optional[Callable[[], jnp.ndarray]] = None,
                              wire_dtype=None):
    """Forward half of the ragged (dropless) exchange, micro-sharded.

    send: (mp, bound, d) pad-to-max-per-peer shards (core/dispatch
    make_ragged_xplan layout).  With ``n_chunks > 1`` the bound dim splits
    into ppermute-decomposed micro-shards — every exchange is an
    async-schedulable collective-permute, none a blocking all-to-all — and
    ``fill_fn`` (the shadowed experts' local, exchange-free compute) issues
    in the first chunk's wire bubble, exactly like the capacity schedule's
    shadow filler.  Unlike :func:`pipelined_expert_exchange` the expert
    compute itself is NOT interleaved per chunk: the grouped kernels need
    the compacted expert-sorted rows, which exist only after every shard
    lands (ROADMAP follow-on).  Returns ``(recv, fill_out | None)``.
    """
    decompose = n_chunks > 1
    a2a = functools.partial(
        ppermute_all_to_all if decompose else _plain_all_to_all,
        axis=axis, mp=mp, wire_dtype=wire_dtype)
    if n_chunks <= 1:
        recv = a2a(send)
        return recv, (fill_fn() if fill_fn is not None else None)
    chunks = jnp.split(send, n_chunks, axis=1)
    recvs = [a2a(chunks[0])]
    fill_out = fill_fn() if fill_fn is not None else None  # S0 bubble
    recvs += [a2a(c) for c in chunks[1:]]
    return jnp.concatenate(recvs, axis=1), fill_out


@scoped(EXCHANGE)
def all_to_all_dim1(x: jnp.ndarray, axis, mp: int, *, decompose: bool = False,
                    wire_dtype=None) -> jnp.ndarray:
    """Tiled all-to-all splitting/concatenating on dim 1 (dim1 size == mp).

    The intra-node hop of the two-level ragged exchange: buffers are laid out
    ``(n_nodes, n_inner, ...)`` and the node-local exchange moves dim 1 while
    dim 0 (destination node) stays put.  Implemented as a transpose around
    the dim-0 helpers so the ppermute decomposition and wire-dtype bitcast
    behave identically to every other exchange in this module.
    """
    perm = (1, 0) + tuple(range(2, x.ndim))
    fn = ppermute_all_to_all if decompose else _plain_all_to_all
    return fn(x.transpose(perm), axis=axis, mp=mp,
              wire_dtype=wire_dtype).transpose(perm)


@scoped(EXCHANGE)
def hier_ragged_pipeline(send: jnp.ndarray, axis, mp: int, n_chunks: int,
                         chunk_fn: Callable[[jnp.ndarray, int], jnp.ndarray],
                         *, fill_fn: Optional[Callable[[], jnp.ndarray]] = None,
                         wire_dtype=None):
    """Inter-node leg of the two-level ragged exchange, with per-chunk compute.

    send: (mp, inter_bound, d) slim per-node shards (mp = n_nodes here).
    ``chunk_fn(recv_chunk, c) -> out_chunk`` runs the expert compute on chunk
    ``c``'s received rows — (mp, w, d) -> (mp, w, d_out) with
    ``w = inter_bound // n_chunks`` — using its own mini-compaction
    (core/dispatch.hier_chunk_plans).  The §5.2 smart schedule applies to
    this leg alone: S_{c+1} is issued before C_c and R_c right after, so at
    steady state one send, one grouped-GEMM and one receive are in flight —
    unlike the flat ragged path, the hierarchical receiver CAN compute per
    chunk, because each chunk's counts are known before its payload lands.
    ``fill_fn`` (shadowed experts) issues in S0's wire bubble.  Returns
    ``(ret (mp, inter_bound, d_out), fill_out | None)``.
    """
    decompose = n_chunks > 1
    a2a = functools.partial(
        ppermute_all_to_all if decompose else _plain_all_to_all,
        axis=axis, mp=mp, wire_dtype=wire_dtype)
    if n_chunks <= 1:
        recv = a2a(send)
        fill_out = fill_fn() if fill_fn is not None else None
        return a2a(chunk_fn(recv, 0)), fill_out
    chunks = jnp.split(send, n_chunks, axis=1)
    recv: list = [None] * n_chunks
    outs: list = [None] * n_chunks
    fill_out = None
    recv[0] = a2a(chunks[0])  # S0: warm the pipeline
    for c in range(n_chunks):
        if c + 1 < n_chunks:
            recv[c + 1] = a2a(chunks[c + 1])  # S_{c+1} before C_c
        if c == 0 and fill_fn is not None:
            fill_out = fill_fn()  # shadow compute fills the S0 bubble
        outs[c] = a2a(chunk_fn(recv[c], c))  # C_c then R_c
    return jnp.concatenate(outs, axis=1), fill_out


@scoped(EXCHANGE)
def pipelined_expert_exchange(
        buf: jnp.ndarray, axis, mp: int, n_chunks: int,
        compute_fn: Callable[[jnp.ndarray], jnp.ndarray], *,
        fill_fn: Optional[Callable[[], jnp.ndarray]] = None,
        wire_dtype=None, decompose: bool = True):
    """Dispatch a2a -> expert compute -> return a2a, software-pipelined.

    buf: (mp, E_local, C, d) dispatch buffer (dim 0 = destination rank).
    compute_fn: (E_local, rows, d) -> (E_local, rows, d_out) row-independent
    expert computation (the caller wraps any tp_axis gather/scatter).
    fill_fn: optional exchange-free local work (shadowed experts) issued in
    the first chunk's wire bubble; its result is returned alongside.

    Returns (out: (mp, E_local, C, d_out), fill_out | None).

    The schedule is the paper's Fig-6 smart schedule: chunk i+1's forward
    exchange is issued before chunk i's compute, and chunk i's return
    exchange right after it, so at steady state one send, one compute and
    one receive are always in flight together.
    """
    mp_, E_local, C, d = buf.shape
    assert mp_ == mp and C % n_chunks == 0, (buf.shape, mp, n_chunks)
    a2a = functools.partial(
        ppermute_all_to_all if decompose else _plain_all_to_all,
        axis=axis, mp=mp, wire_dtype=wire_dtype)

    if n_chunks <= 1:
        recv = a2a(buf)
        fill_out = fill_fn() if fill_fn is not None else None
        y = compute_fn(recv.transpose(1, 0, 2, 3).reshape(E_local, mp * C, d))
        y = y.reshape(E_local, mp, C, -1).transpose(1, 0, 2, 3)
        return a2a(y), fill_out

    Cc = C // n_chunks
    chunks = jnp.split(buf, n_chunks, axis=2)
    recv: list = [None] * n_chunks
    outs: list = [None] * n_chunks
    fill_out = None
    recv[0] = a2a(chunks[0])  # S0: warm the pipeline
    for i in range(n_chunks):
        if i + 1 < n_chunks:
            recv[i + 1] = a2a(chunks[i + 1])  # S_{i+1} before C_i
        if i == 0 and fill_fn is not None:
            fill_out = fill_fn()  # shadow compute fills the S0 bubble
        x = recv[i].transpose(1, 0, 2, 3).reshape(E_local, mp * Cc, d)
        y = compute_fn(x)  # C_i
        y = y.reshape(E_local, mp, Cc, -1).transpose(1, 0, 2, 3)
        outs[i] = a2a(y)  # R_i
    return jnp.concatenate(outs, axis=2), fill_out
