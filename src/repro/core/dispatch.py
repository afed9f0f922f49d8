"""Token scatter/gather — the paper's §4 reordered computation (Fig 4).

FastMoE's core single-device insight: batch all tokens routed to the same
expert contiguously (**scatter**), run one big GeMM per expert, then put
outputs back in original order (**gather**).

Two TPU-native realizations (DESIGN.md §2):

* ``capacity`` — GShard-style static buffers ``(E, C, d)``.  XLA needs static
  shapes, so FastMoE's runtime-sized recv buffers become a fixed per-expert
  capacity; overflow tokens are dropped (tracked).  This is the mode that
  composes with expert-parallel all-to-all.
* ``ragged`` — expert-sorted token array + group sizes, no drops; feeds the
  Pallas grouped-GEMM kernel.  Static total size (T*k), ragged within.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.obs.scopes import COMBINE, DISPATCH, scoped


def expert_capacity(num_tokens: int, num_experts: int, top_k: int,
                    capacity_factor: float, *, multiple: int = 8) -> int:
    """Static per-expert buffer length C."""
    c = math.ceil(num_tokens * top_k * capacity_factor / num_experts)
    return max(multiple, math.ceil(c / multiple) * multiple)


# ---------------------------------------------------------------------------
# Capacity (static-buffer) dispatch
# ---------------------------------------------------------------------------


class CapacityPlan(NamedTuple):
    """Routing of each (token, slot) pair into the (E, C) buffer grid."""

    expert_ids: jax.Array  # (T, k) int32
    positions: jax.Array  # (T, k) int32 — row within the expert buffer; ==C if dropped
    keep: jax.Array  # (T, k) bool
    load: jax.Array  # (E,) int32 — tokens *assigned* per expert (pre-drop)
    capacity: int


@scoped(DISPATCH)
def make_capacity_plan(expert_ids: jax.Array, num_experts: int,
                       capacity) -> CapacityPlan:
    """Assign buffer positions with slot-major priority (top-1 choices first),
    matching GShard so lower-k choices survive overflow.

    ``capacity`` may be a single int or a static per-expert sequence (the
    placement subsystem shrinks the a2a experts' buffers independently of the
    shadowed ones); ``plan.capacity`` is the buffer width = max over experts,
    and dropped rows get position == width so scatter/gather skip them.
    """
    import numpy as np
    T, k = expert_ids.shape
    if isinstance(capacity, (int, np.integer)):
        caps, width = None, int(capacity)
    else:
        caps_np = np.asarray(capacity, np.int32)
        assert caps_np.shape == (num_experts,), caps_np.shape
        caps, width = jnp.asarray(caps_np), int(caps_np.max())
    # slot-major flatten: all slot-0 assignments precede slot-1, etc.
    flat = expert_ids.T.reshape(-1)  # (k*T,)
    onehot = jax.nn.one_hot(flat, num_experts, dtype=jnp.int32)  # (kT, E)
    # 0-indexed position of each row within its expert's arrival order
    pos_in_expert = jnp.cumsum(onehot, axis=0) - onehot
    pos = jnp.take_along_axis(pos_in_expert, flat[:, None], axis=1)[:, 0]
    keep = pos < (width if caps is None else caps[flat])
    pos = jnp.where(keep, pos, width)  # out-of-range rows are dropped by scatter
    load = onehot.sum(axis=0)
    # back to token-major (T, k)
    unflatten = lambda a: a.reshape(k, T).T
    return CapacityPlan(expert_ids, unflatten(pos), unflatten(keep), load, width)


@scoped(DISPATCH)
def hold_capacity_plan(plan: CapacityPlan, held: int):
    """(plan, assignments to held experts): ``plan`` restricted to experts
    ``0 .. held-1``, one chip's share of the layer.  The other experts'
    assignments keep their slots in the uncut plan but are neither
    dispatched nor combined here (position == capacity, keep False); the
    capacity and each held expert's slots are the uncut layer's."""
    on = plan.expert_ids < held
    keep = plan.keep & on
    return (plan._replace(positions=jnp.where(keep, plan.positions,
                                              plan.capacity), keep=keep),
            jnp.sum(on.astype(jnp.float32)))


@scoped(DISPATCH)
def dispatch_capacity(x: jax.Array, plan: CapacityPlan,
                      num_experts: int) -> jax.Array:
    """Scatter tokens (T, d) into per-expert buffers (E, C, d)."""
    T, d = x.shape
    k = plan.expert_ids.shape[1]
    buf = jnp.zeros((num_experts, plan.capacity, d), x.dtype)
    eid = plan.expert_ids.reshape(-1)
    pos = plan.positions.reshape(-1)
    rows = jnp.repeat(jnp.arange(T), k)  # token index per (token, slot)
    # out-of-bounds pos==C rows are dropped (jnp scatter drop semantics)
    return buf.at[eid, pos].set(x[rows], mode="drop")


@scoped(COMBINE)
def combine_capacity(out_buf: jax.Array, plan: CapacityPlan,
                     combine_weights: jax.Array) -> jax.Array:
    """Gather expert outputs (E, C, dout) back to token order, weighted-sum over k."""
    T, k = plan.expert_ids.shape
    eid = plan.expert_ids.reshape(-1)
    pos = plan.positions.reshape(-1)
    gathered = out_buf.at[eid, pos].get(mode="fill", fill_value=0)  # (T*k, dout)
    gathered = gathered.reshape(T, k, -1)
    w = (combine_weights * plan.keep).astype(gathered.dtype)
    return jnp.einsum("tk,tkd->td", w, gathered)


@scoped(COMBINE)
def combine_capacity_slots(out_buf: jax.Array, plan: CapacityPlan,
                           combine_weights: jax.Array) -> jax.Array:
    """Per-slot weighted expert outputs (T, k, dout) — no k-reduction.

    The psum (decode) path reduces these across ranks *before* summing the k
    slots in fixed order, which makes the result bitwise-invariant to which
    rank serves which slot: a per-rank k-sum (combine_capacity's einsum) may
    FMA-fuse a token's co-located slot pair into one rounding, so permuting
    or shadowing experts would shift results by an ulp.  Here every slot
    contribution is rounded exactly once (the product), on whichever rank
    computes it, and the cross-slot sum happens identically everywhere.
    """
    T, k = plan.expert_ids.shape
    eid = plan.expert_ids.reshape(-1)
    pos = plan.positions.reshape(-1)
    gathered = out_buf.at[eid, pos].get(mode="fill", fill_value=0)
    gathered = gathered.reshape(T, k, -1)
    w = (combine_weights * plan.keep).astype(gathered.dtype)
    return w[:, :, None] * gathered


# ---------------------------------------------------------------------------
# Ragged (sorted) dispatch — FastMoE-faithful, no drops
# ---------------------------------------------------------------------------


class RaggedPlan(NamedTuple):
    sort_idx: jax.Array  # (T*k,) int32 — argsort of flat expert ids
    group_sizes: jax.Array  # (E,) int32
    token_rows: jax.Array  # (T*k,) int32 — source token per sorted row


@scoped(DISPATCH)
def make_ragged_plan(expert_ids: jax.Array, num_experts: int) -> RaggedPlan:
    T, k = expert_ids.shape
    flat = expert_ids.reshape(-1)  # token-major
    sort_idx = jnp.argsort(flat, stable=True).astype(jnp.int32)
    group_sizes = jnp.bincount(flat, length=num_experts).astype(jnp.int32)
    token_rows = (sort_idx // k).astype(jnp.int32)
    return RaggedPlan(sort_idx, group_sizes, token_rows)


@scoped(DISPATCH)
def dispatch_ragged(x: jax.Array, plan: RaggedPlan) -> jax.Array:
    """Gather tokens (T, d) into expert-sorted order (T*k, d)."""
    return x[plan.token_rows]


@scoped(COMBINE)
def combine_ragged(y_sorted: jax.Array, plan: RaggedPlan,
                   combine_weights: jax.Array) -> jax.Array:
    """Un-sort expert outputs (T*k, dout) and weighted-sum the k slots."""
    T, k = combine_weights.shape
    y_flat = jnp.zeros_like(y_sorted).at[plan.sort_idx].set(y_sorted)
    y = y_flat.reshape(T, k, -1)
    return jnp.einsum("tk,tkd->td", combine_weights.astype(y.dtype), y)


@scoped(COMBINE)
def combine_ragged_slots(y_sorted: jax.Array, plan: RaggedPlan,
                         combine_weights: jax.Array) -> jax.Array:
    """Ragged analogue of :func:`combine_capacity_slots`: un-sorted per-slot
    weighted outputs (T, k, dout), k-reduction left to the caller (the psum
    decode path sums slots after the cross-rank reduction, in fixed order)."""
    T, k = combine_weights.shape
    y_flat = jnp.zeros_like(y_sorted).at[plan.sort_idx].set(y_sorted)
    y = y_flat.reshape(T, k, -1)
    return combine_weights[:, :, None].astype(y.dtype) * y


# ---------------------------------------------------------------------------
# Expert-choice dispatch — exact capacities by construction (Zhou et al. 2022)
# ---------------------------------------------------------------------------
#
# Expert-choice inverts the selection: each expert picks its top-C tokens, so
# every expert buffer is exactly C rows — no capacity padding waste, no drops,
# flat load.  Dispatch is a plain gather ``x[token_idx]`` into the same
# (E, C, d) grid the capacity machinery exchanges, and the ragged layout is
# the degenerate uniform case ``group_sizes == C`` — both exchange paths get
# a second client without new plumbing.


def ec_capacity(num_tokens: int, num_experts: int,
                capacity_factor: float) -> int:
    """Per-expert row count for expert-choice routing.

    Must match ``gate.expert_choice_moe``'s dense reference exactly — the
    dispatched paths are differentially tested against it.  Clamped to the
    token count: an expert can't pick more tokens than exist (top-C over T
    rows requires C <= T), and beyond that every expert already takes
    everything.
    """
    return max(1, min(num_tokens,
                      int(num_tokens * capacity_factor / num_experts)))


@scoped(COMBINE)
def combine_ec(out: jax.Array, token_idx: jax.Array, weights: jax.Array,
               num_tokens: int) -> jax.Array:
    """Scatter-add weighted expert outputs back to token order.

    ``out`` (E, C, dout) must be in LOGICAL expert order (callers gather
    physically-placed outputs through the plan's table first) so the
    scatter-add ordering — and therefore the f32 rounding — is invariant to
    the expert layout, matching the dense reference bitwise.
    """
    E, C, dout = out.shape
    y = jnp.zeros((num_tokens, dout), out.dtype)
    return y.at[token_idx.reshape(-1)].add(
        (out * weights[..., None].astype(out.dtype)).reshape(E * C, dout))


@scoped(DISPATCH)
def ec_to_physical(token_idx: jax.Array, table: jax.Array | None) -> jax.Array:
    """Permute the (E, C) expert-choice token grid from logical to physical
    expert order (row e of the result belongs to physical slot e).  Uniform
    capacities make this a pure row permutation — group sizes are unchanged.
    ``table`` is the logical->physical id table (None = identity)."""
    if table is None:
        return token_idx
    return jnp.zeros_like(token_idx).at[table].set(token_idx)


# ---------------------------------------------------------------------------
# Cross-rank ragged plans — the distributed dropless exchange (ISSUE 4)
# ---------------------------------------------------------------------------
#
# The capacity a2a pads every expert buffer to C rows before the wire; the
# ragged exchange instead moves the *locally sorted* rows in per-peer shards:
# each rank's rows destined for peer p form one contiguous segment of its
# expert-sorted array (experts are contiguous per rank), scattered into shard
# p of a (mp, bound, d) send buffer.  ``bound`` is the static pad-to-max-
# per-peer width that keeps the exchange jit-able; the *valid lengths* ride
# separately as the (mp, E_local) counts all-to-all, so the receiver can
# compact the padded shards back into a load-sized expert-sorted array for
# the grouped kernels (RAGGED_FNS).  bound = T*k is provably dropless.


class RaggedXPlan(NamedTuple):
    """Send-side geometry of the ragged (dropless) all-to-all.

    Indexes the rank's expert-sorted rows (make_ragged_plan order): physical
    experts [0, num_owned) are exchanged, any shadowed tail is served
    locally (repro/placement/shadow.py contract).
    """

    send_dest: jax.Array  # (T*k,) int32 — slot in the flat (mp*bound) send
    # buffer; == mp*bound for rows not exchanged (shadowed / over-bound)
    peer_counts: jax.Array  # (mp, E_local) int32 — rows that FIT the bound,
    # per (destination rank, its local expert); the counts-a2a payload
    keep: jax.Array  # (T*k,) bool — owned rows that fit the per-peer bound
    num_owned_rows: jax.Array  # () int32 — rows routed to owned experts


@scoped(DISPATCH)
def make_ragged_xplan(group_sizes: jax.Array, num_rows: int, num_owned: int,
                      num_peers: int, bound: int) -> RaggedXPlan:
    """Lay this rank's ``num_rows`` sorted rows into per-peer shards of width
    ``bound``.

    group_sizes: (E,) of the local expert sort (physical order).  The first
    ``num_owned`` experts live on the a2a (``num_owned // num_peers`` per
    peer, contiguous per-rank blocks); the rest are shadowed.  Rows of one
    peer keep their expert-sorted order inside the shard, so the receiver
    can reconstruct expert segments from the exchanged counts alone.
    """
    e_pp = num_owned // num_peers
    raw = group_sizes[:num_owned].reshape(num_peers, e_pp)
    peer_tot = raw.sum(axis=1)
    cum = jnp.cumsum(peer_tot)  # (mp,) inclusive
    num_owned_rows = cum[-1]
    i = jnp.arange(num_rows, dtype=jnp.int32)
    owned = i < num_owned_rows
    peer = jnp.clip(jnp.searchsorted(cum, i, side="right"),
                    0, num_peers - 1).astype(jnp.int32)
    within = i - (cum[peer] - peer_tot[peer])  # position inside the shard
    keep = owned & (within < bound)
    send_dest = jnp.where(keep, peer * bound + within,
                          num_peers * bound).astype(jnp.int32)
    # kept rows per (peer, expert): experts fill the shard in order, so the
    # bound truncates the trailing experts of an over-full shard
    off_in_peer = jnp.cumsum(raw, axis=1) - raw  # exclusive, per peer
    peer_counts = jnp.clip(bound - off_in_peer, 0, raw).astype(jnp.int32)
    return RaggedXPlan(send_dest, peer_counts, keep, num_owned_rows)


@scoped(DISPATCH)
def ragged_recv_compact(incoming: jax.Array, bound: int):
    """Compaction map for the received (mp, bound, d) shards.

    incoming: (mp, E_local) kept-row counts from each source rank (the
    counts-a2a output).  Shard s holds ``incoming[s].sum()`` valid rows,
    expert-sorted with segment lengths ``incoming[s]``.  Returns
    ``(dest, group_sizes)``: ``dest`` (mp*bound,) maps each received slot to
    its row in the expert-sorted compact array (== mp*bound for padding →
    scatter-drop / gather-fill), and ``group_sizes`` (E_local,) are the
    compact array's per-expert segment lengths, src-major within an expert —
    i.e. global token order when ranks hold contiguous token blocks.
    """
    mp, e_local = incoming.shape
    gs = incoming.sum(axis=0)  # (E_local,)
    e_off = jnp.cumsum(gs) - gs  # exclusive expert offsets in compact array
    prior = jnp.cumsum(incoming, axis=0) - incoming  # earlier-src rows per e
    in_off = jnp.cumsum(incoming, axis=1) - incoming  # within-src expert offs
    cum_src = jnp.cumsum(incoming, axis=1)  # (mp, E_local) inclusive
    src_tot = incoming.sum(axis=1)  # (mp,)
    idx = jnp.arange(mp * bound, dtype=jnp.int32)
    s, j = idx // bound, idx % bound
    # expert of slot (s, j): how many inclusive-cumsum boundaries j passed
    e = jnp.clip((j[:, None] >= cum_src[s]).sum(axis=1),
                 0, e_local - 1).astype(jnp.int32)
    valid = j < src_tot[s]
    dest = e_off[e] + prior[s, e] + (j - in_off[s, e])
    return jnp.where(valid, dest, mp * bound).astype(jnp.int32), gs


# ---------------------------------------------------------------------------
# Two-level (hierarchical) ragged exchange — node-level aggregation (ISSUE 7)
# ---------------------------------------------------------------------------
#
# On a mesh with a node axis (DistConfig.node_axis), the flat per-peer shards
# first exchange within the node (fast links, dim-1 a2a): afterwards each
# rank is its node's *forwarding agent* for its own inner slot, holding every
# sibling's shard destined for rank (o, my_inner) of every node o.  The agent
# compacts the n_inner valid prefixes into ONE slim shard per destination
# node (width ``inter_bound``), so the slow inter-node exchange carries only
# truly-needed rows — per-source padding never crosses a node boundary, and
# an adaptive ``inter_bound`` (LoadMonitor-calibrated) shrinks the wire with
# actual load.  The receiver rebuilds the exact expert-sorted compact array
# of the flat path (source-rank-major within an expert), so the two paths
# are bit-exact when nothing drops.


class HierAggPlan(NamedTuple):
    """Forwarding-agent geometry: n_inner padded shards -> one slim shard."""

    agg_dest: jax.Array  # (n_nodes*n_inner*bound,) int32 — slot in the flat
    # (n_nodes*inter_bound) slim buffer; == n_nodes*inter_bound when invalid
    kept_counts: jax.Array  # (n_nodes, n_inner, E_local) int32 — rows that
    # fit the inter bound, per (dest node, source sibling, expert); the
    # inter-node counts-leg payload
    dropped: jax.Array  # () f32 — rows this agent dropped at the inter bound


@scoped(DISPATCH)
def make_hier_agg(cnt_agg: jax.Array, bound: int,
                  inter_bound: int) -> HierAggPlan:
    """Compact per-sibling padded shards into slim per-node shards.

    cnt_agg: (n_nodes, n_inner, E_local) — after the intra counts hop, the
    kept-row counts of sibling ``s``'s shard for destination node ``o``
    (each shard a valid prefix of ``cnt_agg[o, s].sum()`` rows padded to
    ``bound``).  Sibling prefixes concatenate in sibling order inside the
    slim shard; ``inter_bound`` truncates the trailing rows of an over-full
    node shard (tracked in ``dropped`` — never silent).
    """
    n_nodes, n_inner, e_local = cnt_agg.shape
    seg = cnt_agg.sum(-1)  # (n_nodes, n_inner) valid prefix lengths
    off = jnp.cumsum(seg, axis=1) - seg  # sibling offsets in the slim shard
    idx = jnp.arange(n_nodes * n_inner * bound, dtype=jnp.int32)
    o = idx // (n_inner * bound)
    s = (idx // bound) % n_inner
    b = idx % bound
    pos = off[o, s] + b
    valid = (b < seg[o, s]) & (pos < inter_bound)
    agg_dest = jnp.where(valid, o * inter_bound + pos,
                         n_nodes * inter_bound).astype(jnp.int32)
    # kept rows per (o, s, e): experts fill each sibling run in order, so the
    # inter bound truncates trailing (sibling, expert) segments — the same
    # clip pattern as make_ragged_xplan's peer_counts
    e_off = off[..., None] + (jnp.cumsum(cnt_agg, axis=-1) - cnt_agg)
    kept = jnp.clip(inter_bound - e_off, 0, cnt_agg).astype(jnp.int32)
    dropped = (cnt_agg.sum() - kept.sum()).astype(jnp.float32)
    return HierAggPlan(agg_dest, kept, dropped)


def _hier_slots(incoming: jax.Array, inter_bound: int):
    """Per-slot structure of the received slim shards.

    incoming: (n_nodes, n_inner, E_local) kept counts from every source rank
    (node-major).  Shard ``i`` holds compacted sibling-major runs, each run
    expert-sorted with lengths ``incoming[i, s]``.  Returns per flat slot
    (n_nodes*inter_bound,): source sibling ``s``, within-sibling row ``r``,
    expert ``e``, and validity.
    """
    n_nodes, n_inner, e_local = incoming.shape
    seg = incoming.sum(-1)  # (n_nodes, n_inner)
    soff = jnp.cumsum(seg, axis=1) - seg
    cum_sib = jnp.cumsum(seg, axis=1)  # inclusive
    cum_e = jnp.cumsum(incoming, axis=-1)  # inclusive, within sibling
    idx = jnp.arange(n_nodes * inter_bound, dtype=jnp.int32)
    i, q = idx // inter_bound, idx % inter_bound
    s = jnp.clip((q[:, None] >= cum_sib[i]).sum(axis=1),
                 0, n_inner - 1).astype(jnp.int32)
    r = q - soff[i, s]
    e = jnp.clip((r[:, None] >= cum_e[i, s]).sum(axis=1),
                 0, e_local - 1).astype(jnp.int32)
    valid = q < cum_sib[i, n_inner - 1]
    return i, s, r, e, valid


@scoped(DISPATCH)
def ragged_recv_compact_hier(incoming: jax.Array, inter_bound: int):
    """Two-level analogue of :func:`ragged_recv_compact`.

    Maps each received slim slot to its row in the SAME expert-sorted
    compact array the flat path builds (source-rank-major within an expert,
    ranks node-major) — the bit-exactness anchor of the hierarchical path.
    Returns ``(dest (n_nodes*inter_bound,), group_sizes (E_local,))``;
    invalid slots map to ``n_nodes*inter_bound``.
    """
    n_nodes, n_inner, e_local = incoming.shape
    flat_cnt = incoming.reshape(n_nodes * n_inner, e_local)  # src-rank major
    gs = flat_cnt.sum(axis=0)
    e_off = jnp.cumsum(gs) - gs
    prior = jnp.cumsum(flat_cnt, axis=0) - flat_cnt  # earlier-src rows per e
    in_off = jnp.cumsum(incoming, axis=-1) - incoming  # within-sib expert offs
    i, s, r, e, valid = _hier_slots(incoming, inter_bound)
    dest = e_off[e] + prior[i * n_inner + s, e] + (r - in_off[i, s, e])
    return (jnp.where(valid, dest, n_nodes * inter_bound).astype(jnp.int32),
            gs.astype(jnp.int32))


@scoped(DISPATCH)
def hier_chunk_plans(incoming: jax.Array, inter_bound: int, n_chunks: int):
    """Per-chunk mini-compaction maps for per-received-chunk expert compute.

    Chunk ``c`` of the inter-node exchange delivers slots ``[c*w, (c+1)*w)``
    of every source node's slim shard (``w = inter_bound // n_chunks``).
    Each chunk's valid rows form their own expert-sorted mini array so the
    grouped kernels can run on chunk ``c`` while chunk ``c+1`` is still in
    flight — the per-chunk dynamic group slicing of the §5.2 follow-on.
    Returns ``(dest (n_chunks, n_nodes*w), gs (n_chunks, E_local))``; ``dest``
    maps chunk slots (node-major) into the mini array (invalid → n_nodes*w).
    """
    n_nodes, n_inner, e_local = incoming.shape
    w = inter_bound // n_chunks
    _, _, _, e, valid = _hier_slots(incoming, inter_bound)
    # regroup flat slots (i, q) -> (chunk c, node i, within-chunk q')
    e_c = e.reshape(n_nodes, n_chunks, w).transpose(1, 0, 2).reshape(
        n_chunks, n_nodes * w)
    v_c = valid.reshape(n_nodes, n_chunks, w).transpose(1, 0, 2).reshape(
        n_chunks, n_nodes * w)
    onehot = jax.nn.one_hot(e_c, e_local, dtype=jnp.int32) * v_c[..., None]
    gs = onehot.sum(axis=1)  # (n_chunks, E_local)
    g_off = jnp.cumsum(gs, axis=-1) - gs
    before = jnp.cumsum(onehot, axis=1) - onehot  # earlier chunk slots per e
    dest = (jnp.take_along_axis(g_off, e_c, axis=1)
            + jnp.take_along_axis(before, e_c[..., None], axis=2)[..., 0])
    dest = jnp.where(v_c, dest, n_nodes * w).astype(jnp.int32)
    return dest, gs.astype(jnp.int32)


# ---------------------------------------------------------------------------
# Tile padding for the Pallas grouped GEMM (groups aligned to row tiles)
# ---------------------------------------------------------------------------


class TiledRagged(NamedTuple):
    x: jax.Array  # (P, d) — sorted rows scattered into tile-aligned slots
    row_valid: jax.Array  # (P,) bool
    tile_group: jax.Array  # (P // tile,) int32 — expert id owning each row tile
    dest: jax.Array  # (T*k,) int32 — tile-aligned slot of each sorted row
    padded_offsets: jax.Array  # (E,) int32 — start of each expert's padded block


def pad_to_tiles(x_sorted: jax.Array, group_sizes: jax.Array, tile: int,
                 num_experts: int) -> TiledRagged:
    """Re-lay sorted rows so every expert's block starts on a tile boundary.

    Static output size P = ceil(T*k/tile)*tile + E*tile upper bound (each group
    padded up to a tile multiple).
    """
    n = x_sorted.shape[0]
    padded_sizes = (group_sizes + tile - 1) // tile * tile
    padded_offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                      jnp.cumsum(padded_sizes)[:-1].astype(jnp.int32)])
    group_starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                    jnp.cumsum(group_sizes)[:-1].astype(jnp.int32)])
    P = (n + tile - 1) // tile * tile + num_experts * tile  # static upper bound
    sorted_eid = jnp.repeat(jnp.arange(num_experts, dtype=jnp.int32),
                            group_sizes, total_repeat_length=n)
    within = jnp.arange(n, dtype=jnp.int32) - group_starts[sorted_eid]
    dest = padded_offsets[sorted_eid] + within
    x_p = jnp.zeros((P, x_sorted.shape[1]), x_sorted.dtype).at[dest].set(x_sorted)
    row_valid = jnp.zeros((P,), jnp.bool_).at[dest].set(True)
    # expert owning each tile: tiles within [padded_offsets[e], +padded_sizes[e])
    tile_starts = jnp.arange(P // tile, dtype=jnp.int32) * tile
    tile_group = jnp.clip(
        jnp.searchsorted(padded_offsets, tile_starts, side="right") - 1,
        0, num_experts - 1).astype(jnp.int32)
    return TiledRagged(x_p, row_valid, tile_group, dest.astype(jnp.int32),
                       padded_offsets)


def unpad_tiles(y_padded: jax.Array, tiled: TiledRagged) -> jax.Array:
    """Inverse of :func:`pad_to_tiles` row layout (back to sorted order)."""
    return y_padded[tiled.dest]
