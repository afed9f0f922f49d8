"""Hierarchical two-level ragged exchange (ISSUE 7 tentpole tests).

The two-level path (intra-node aggregation hop + slim inter-node hop,
``DistConfig.node_axis``) must be *the same function* as the flat ragged
exchange: outputs AND grads bit-identical on the 2-node x 4-inner fake
mesh across dispatch impls, overlap chunking, the bf16 wire, and slim
inter bounds — while the wire counters split intra/inter and the
inter-node share shrinks below the flat exchange's bytes.

Host tests exercise the pure plan math (core/dispatch make_hier_agg /
ragged_recv_compact_hier / hier_chunk_plans), the transport gate, and the
LoadMonitor's adaptive bound; multi-device cases run in subprocesses via
tests/dist_utils.py (the main process keeps its single CPU device).
"""
import jax
import jax.numpy as jnp
import numpy as np

import dist_utils as du
from repro.core import comm
from repro.core import dispatch as D
from repro.core.monitor import LoadMonitor


# ---------------------------------------------------------------------------
# Host-level: the aggregation / compaction / chunk plan index math
# ---------------------------------------------------------------------------


def _agg_env(seed=0, n_nodes=2, n_inner=2, e_local=2, bound=4):
    rng = np.random.default_rng(seed)
    cnt = rng.integers(0, bound // e_local + 1, (n_nodes, n_inner, e_local))
    while cnt.sum(-1).max() > bound:  # per-(node, sibling) shard must fit
        cnt = rng.integers(0, bound, (n_nodes, n_inner, e_local))
    return jnp.asarray(cnt, jnp.int32)


def test_hier_agg_compacts_sibling_prefixes():
    """make_hier_agg: the forwarding agent packs its siblings' valid
    prefixes back to back per destination node — no inter-source padding
    crosses the node boundary."""
    cnt = _agg_env()
    n_nodes, n_inner, e_local = cnt.shape
    bound, ib = 4, int(cnt.sum(axis=(1, 2)).max())  # dropless inter bound
    plan = D.make_hier_agg(cnt, bound, ib)
    dest = np.asarray(plan.agg_dest).reshape(n_nodes, n_inner, bound)
    seg = np.asarray(cnt.sum(-1))
    for o in range(n_nodes):
        expect, pos = [], 0
        for s in range(n_inner):
            expect += list(range(o * ib + pos, o * ib + pos + seg[o, s]))
            pos += seg[o, s]
            # padding rows past the valid prefix are routed to the drop slot
            assert (dest[o, s, seg[o, s]:] == n_nodes * ib).all()
        got = [d for d in dest[o].ravel() if d < n_nodes * ib]
        assert got == expect, (o, got, expect)
    np.testing.assert_array_equal(np.asarray(plan.kept_counts), np.asarray(cnt))
    assert float(plan.dropped) == 0.0


def test_hier_agg_bound_drops_trailing_and_counts():
    """A sub-dropless inter bound truncates each node's trailing rows; the
    kept counts shrink expert-granular and the dropped total matches."""
    cnt = jnp.asarray([[[2, 1], [3, 0]],          # node 0: 6 rows
                       [[0, 2], [1, 1]]], jnp.int32)  # node 1: 4 rows
    plan = D.make_hier_agg(cnt, 4, 5)
    dest = np.asarray(plan.agg_dest).reshape(2, 2, 4)
    # node 0: sibling 0 keeps 3, sibling 1's 3 rows hit positions 3,4,(5=cut)
    assert [d for d in dest[0].ravel() if d < 10] == [0, 1, 2, 3, 4]
    kept = np.asarray(plan.kept_counts)
    np.testing.assert_array_equal(kept[0], [[2, 1], [2, 0]])  # last row cut
    np.testing.assert_array_equal(kept[1], np.asarray(cnt)[1])  # fits
    assert float(plan.dropped) == 1.0


def test_hier_recv_compact_matches_flat_order():
    """The receiver of the slim inter leg rebuilds the *exact* flat-path
    compact array: expert-major segments, source-rank-major inside (ranks
    node-major) — emulated in numpy against ragged_recv_compact."""
    rng = np.random.default_rng(1)
    n_nodes, n_inner, e_local, bound = 2, 3, 2, 5
    ib = n_inner * bound
    cnt = rng.integers(0, 3, (n_nodes, n_inner, e_local)).astype(np.int32)
    incoming = jnp.asarray(cnt)
    # slim buffers as the agents pack them: per node, sibling-major prefixes
    rows = []
    for o in range(n_nodes):
        node_rows = [(o * n_inner + s, e, r)
                     for s in range(n_inner) for e in range(e_local)
                     for r in range(cnt[o, s, e])]
        rows += node_rows + [(-1, -1, -1)] * (ib - len(node_rows))
    rows = np.asarray(rows)  # (n_nodes * ib, 3) tagged source rows
    cplan, gs = D.ragged_recv_compact_hier(incoming, ib)
    cplan = np.asarray(cplan)
    n_valid = int(cnt.sum())
    compact = np.full((n_nodes * ib + 1, 3), -1)
    compact[cplan] = rows
    compact = compact[:n_valid]
    # flat-path oracle: same rows through ragged_recv_compact on the
    # equivalent (mp, bound) shards
    flat_cnt = jnp.asarray(cnt.reshape(n_nodes * n_inner, e_local))
    fplan, fgs = D.ragged_recv_compact(flat_cnt, bound)
    np.testing.assert_array_equal(np.asarray(gs), np.asarray(fgs))
    frows = np.asarray([(p, e, r) for p in range(n_nodes * n_inner)
                        for e in range(e_local)
                        for r in range(cnt.reshape(-1, e_local)[p, e])]
                       + [(-1, -1, -1)] * 0)
    fcompact = np.full((n_nodes * n_inner * bound + 1, 3), -1)
    # flat send buffers: per peer, expert-major valid prefix then padding
    fsend = []
    for p in range(n_nodes * n_inner):
        peer = [(p, e, r) for e in range(e_local)
                for r in range(cnt.reshape(-1, e_local)[p, e])]
        fsend += peer + [(-1, -1, -1)] * (bound - len(peer))
    fcompact[np.asarray(fplan)] = np.asarray(fsend)
    np.testing.assert_array_equal(compact, fcompact[:n_valid])


def test_hier_chunk_plans_partition_the_full_plan():
    """Per-chunk mini-compactions cover every valid row exactly once and
    their group sizes sum to the full receive's group sizes."""
    rng = np.random.default_rng(2)
    n_nodes, n_inner, e_local = 2, 2, 2
    ib, n_chunks = 8, 4
    cnt = rng.integers(0, 3, (n_nodes, n_inner, e_local)).astype(np.int32)
    incoming = jnp.asarray(cnt)
    cdest, cgs = D.hier_chunk_plans(incoming, ib, n_chunks)
    _, gs = D.ragged_recv_compact_hier(incoming, ib)
    cdest, cgs = np.asarray(cdest), np.asarray(cgs)
    w = ib // n_chunks
    assert cdest.shape == (n_chunks, n_nodes * w)
    np.testing.assert_array_equal(cgs.sum(0), np.asarray(gs))
    for c in range(n_chunks):
        # each chunk's valid rows (invalid slots -> the n_nodes*w drop slot)
        # fill their own mini compact array exactly once
        valid = cdest[c][cdest[c] < n_nodes * w]
        assert len(valid) == cgs[c].sum()
        np.testing.assert_array_equal(np.sort(valid),
                                      np.arange(len(valid)))


def test_suggest_ragged_bound_adapts_and_guards():
    mon = LoadMonitor(8, ema=0.5)
    # un-warmed monitor: never-drop bound
    assert mon.suggest_ragged_bound(64, 2, 4) == 64 * 2
    # warm with a uniform load: peak peer share = 1/4
    load = np.ones(8)
    for _ in range(64):
        mon.update(type("M", (), {"load": load, "drop_frac": 0.0})())
    b = mon.suggest_ragged_bound(64, 2, 4)
    assert b == 40  # ceil(128 * 0.25 * 1.25) = 40, already a multiple of 8
    assert b % 8 == 0 and b < 128
    # skew every row onto peer 0: bound walks back toward dropless
    mon2 = LoadMonitor(8, ema=0.5)
    hot = np.asarray([8.0, 8, 0, 0, 0, 0, 0, 0])
    for _ in range(64):
        mon2.update(type("M", (), {"load": hot, "drop_frac": 0.0})())
    assert mon2.suggest_ragged_bound(64, 2, 4) == 128  # peak ~ 1.0, clamp n
    # drop guard: EMA evidence of clipping forces the never-drop bound
    mon.update(type("M", (), {"load": load, "drop_frac": 1.0})())
    assert mon.suggest_ragged_bound(64, 2, 4) == 128


def test_compat_shim_version_gate():
    """The ragged transport is chosen by the devices in use, not by the
    jax version: XLA:CPU leaves ragged-all-to-all unimplemented, so outside
    any mesh on this CPU process the dense bounded-shard branch is picked.
    (tests/test_chip_compile.py compiles the native branch for a described
    TPU, where the gate flips.)"""
    assert jax.default_backend() == "cpu"
    assert comm.native_ragged_all_to_all() is False


# ---------------------------------------------------------------------------
# Multi-device: flat vs two-level differential + counters + composition
# ---------------------------------------------------------------------------

_SETUP = """
    import numpy as np, jax, jax.numpy as jnp
    import dist_utils as du
    from repro.core import fmoe
    env = du.moe_env(dispatch="ragged", capacity_factor=1.25)
    mesh = du.make_mesh(1, 4, node=2)  # (data, node, model) = (1, 2, 4)
    AX = ("data", "node", "model")
    EXP = ("node", "model")
    flat = fmoe.DistConfig(mesh, AX, expert_axis=EXP)
    hier = flat._replace(node_axis="node")
"""


def test_hier_bit_exact_vs_flat_sweep():
    """Acceptance: the two-level exchange is bit-exact vs the flat ragged
    path — outputs AND grads — across impl x overlap x inter_bound on the
    2-node x 4-inner mesh (8 fake devices).  ib=32 < n_inner*B = 128
    exercises the slim (but still dropless for this routing, asserted)
    inter leg; oc=4 with pallas/fused exercises per-received-chunk expert
    compute."""
    out = du.run(_SETUP + """
    def loss(p, x, dist, impl):
        y, _ = fmoe.fmoe_apply(p, x, env.cfg, dist=dist, impl=impl)
        return (y ** 2).mean()

    def run(dist, impl):
        with mesh:
            fn = jax.jit(lambda p, x: (
                fmoe.fmoe_apply(p, x, env.cfg, dist=dist, impl=impl)[0],
                jax.grad(loss)(p, x, dist, impl)))
            y, g = fn(env.params, env.x)
        return np.asarray(y), g

    with mesh:
        _, m = jax.jit(lambda p, x: fmoe.fmoe_apply(
            p, x, env.cfg, dist=hier._replace(inter_bound=32)))(env.params,
                                                                env.x)
    assert float(m.obs.dropped) == 0.0, "slim leg must stay dropless here"
    corners = [(impl, oc, ib) for impl in ("einsum", "fused") for oc in (0, 4)
               for ib in (0, 32)] + [("pallas", 4, 32), ("pallas", 0, 0)]
    for impl, oc, ib in corners:
        y0, g0 = run(flat._replace(overlap_chunks=oc), impl)
        y1, g1 = run(hier._replace(overlap_chunks=oc, inter_bound=ib), impl)
        du.assert_bit_exact(y1, y0, msg=(impl, oc, ib))
        du.assert_grads_match(g1, g0)
    # bf16 wire: both levels cast; still bit-exact flat vs hier (identical
    # quantization points), and distinct from the f32-wire output
    yb0, _ = run(flat._replace(wire_dtype="bf16"), "fused")
    yb1, _ = run(hier._replace(wire_dtype="bf16", inter_bound=32), "fused")
    du.assert_bit_exact(yb1, yb0)
    y0, _ = run(flat, "fused")
    assert 0 < float(np.abs(yb0 - y0).max()) < 0.05
    print("hier bit-exact ok")
    """, devices=8)
    assert "hier bit-exact ok" in out


def test_hier_wire_counters_hand_math_hlo_and_shrink():
    """The split counters' contract: wire_bytes == intra + inter, both match
    the hand math AND the optimized HLO's collective bytes, flat counts
    everything as inter, and a slim inter_bound shrinks ONLY the inter-node
    share — below the flat exchange's bytes."""
    out = du.run(_SETUP + """
    from repro.launch.roofline import collective_bytes
    def run(dist):
        with mesh:
            fn = jax.jit(lambda p, x: fmoe.fmoe_apply(p, x, env.cfg,
                                                      dist=dist))
            y, m = fn(env.params, env.x)
            txt = fn.lower(env.params, env.x).compile().as_text()
        cb = collective_bytes(txt)
        return m, float(cb.get("all-to-all", 0)
                        + cb.get("collective-permute", 0))

    E, d, mp, n_inner, n_nodes = 8, 32, 8, 4, 2
    IB = 32  # slim inter bound, below n_inner * B = 128
    B = (128 // 8) * 2  # t_local * k = 32 rows per peer shard
    # flat on the node mesh: everything crosses as inter
    m, hlo = run(flat)
    b_flat = 4 * (2 * mp * B * d + E)
    assert float(m.obs.wire_bytes) == b_flat == hlo, (
        float(m.obs.wire_bytes), b_flat, hlo)
    assert float(m.obs.wire_bytes_intra) == 0.0
    assert float(m.obs.wire_bytes_inter) == b_flat

    # hier dropless (IB = n_inner * B): every row crosses both levels
    m, hlo = run(hier)
    b_intra = 4 * (2 * mp * B * d + E)
    b_inter = 4 * (2 * n_nodes * n_inner * B * d + E)
    assert float(m.obs.wire_bytes_intra) == b_intra
    assert float(m.obs.wire_bytes_inter) == b_inter
    assert float(m.obs.wire_bytes) == b_intra + b_inter == hlo, (
        float(m.obs.wire_bytes), b_intra + b_inter, hlo)

    # slim inter bound: the inter share (the slow links) shrinks below the
    # flat exchange's bytes; the intra share is untouched
    m24, hlo24 = run(hier._replace(inter_bound=IB))
    b_inter24 = 4 * (2 * n_nodes * IB * d + E)
    assert float(m24.obs.wire_bytes_intra) == b_intra
    assert float(m24.obs.wire_bytes_inter) == b_inter24
    assert b_inter24 < b_flat
    assert float(m24.obs.wire_bytes) == b_intra + b_inter24 == hlo24
    assert float(m24.drop_frac) == 0.0  # this routing still fits

    # decomposed (ppermute) hops: each level keeps its own (s-1)/s fraction
    md, hlod = run(hier._replace(overlap_chunks=4, inter_bound=IB))
    bi = 0.75 * b_intra
    be = 0.5 * b_inter24
    assert float(md.obs.wire_bytes_intra) == bi
    assert float(md.obs.wire_bytes_inter) == be
    assert float(md.obs.wire_bytes) == bi + be == hlod

    # bf16 wire: payloads halve on both levels, counts legs stay int32
    mb, hlob = run(hier._replace(wire_dtype="bf16", inter_bound=IB))
    assert float(mb.obs.wire_bytes_intra) == 2 * (2 * mp * B * d) + 4 * E
    assert float(mb.obs.wire_bytes_inter) == 2 * (2 * n_nodes * IB * d) + 4 * E
    assert float(mb.obs.wire_bytes) == hlob
    print("hier counters ok")
    """, devices=8)
    assert "hier counters ok" in out


def test_hier_skew_drops_and_shadow_compose():
    """Zipf-skewed routing under a too-slim inter bound: the forwarding
    agents' truncations land in drop_frac, outputs stay finite; shadowed
    hot experts compose with the two-level exchange (the shadow tail never
    enters either hop)."""
    out = du.run(_SETUP + """
    from repro.placement import from_logical
    skew = du.skew_router(env)  # all rows to experts {0, 1} = node 0
    y_ref, m_ref = du.oracle(skew, impl="fused")
    y, m = du.dist_apply(skew, mesh, hier, impl="fused")
    du.assert_close(y, y_ref, 1e-5)
    assert float(m.drop_frac) == 0.0  # dropless bounds
    load = np.asarray(m.load)
    np.testing.assert_allclose(load[:2], [0.5, 0.5], atol=1e-6)

    # slim the inter leg below the hot node's arrivals: every rank splits
    # its 32 rows between experts 0/1 (node 0's inner slots 0/1), so each
    # of the 4 forwarding agents involved aggregates 4 siblings x 16 = 64
    # rows for node 0 and IB=32 keeps half -> global drop_frac = 0.5
    yb, mb = du.dist_apply(skew, mesh, hier._replace(inter_bound=32),
                           impl="fused")
    np.testing.assert_allclose(float(mb.drop_frac), 0.5, atol=1e-6)
    assert np.isfinite(np.asarray(yb)).all()

    # shadow placement: hot experts replicated outside both hops (16
    # experts: shadowing 8 leaves 8 owned = 1 per rank)
    env16 = du.moe_env(dispatch="ragged", num_experts=16,
                       capacity_factor=1.25)
    y0, m0 = du.dist_apply(env16, mesh, hier)
    plan = du.hot_shadow_plan(np.asarray(m0.load), 8, 8)
    pp = from_logical(env16.params, plan)
    for oc in (0, 4):
        y1, m1 = du.dist_apply(env16, mesh, hier._replace(
            placement=plan, overlap_chunks=oc), params=pp)
        du.assert_close(y1, y0, 1e-5, msg=oc)
        np.testing.assert_allclose(np.asarray(m1.load), np.asarray(m0.load),
                                   atol=1e-6)
    print("hier skew+shadow ok")
    """, devices=8)
    assert "hier skew+shadow ok" in out


def test_compat_shim_branches_agree():
    """comm.ragged_all_to_all_shards on the CPU: the gate picks the dense
    bounded-shard branch, which is bit-identical to the plain tiled a2a of
    the zero-padded shards (the invariant the native TPU branch preserves
    too — tests/test_chip_compile.py compiles that one for a v5e)."""
    out = du.run("""
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.core import comm
    import dist_utils as du
    mesh = du.make_mesh(1, 4)
    mp, bound, d = 4, 6, 8
    rng = np.random.default_rng(0)
    sizes = np.asarray([[3, 1, 0, 6], [2, 2, 2, 2],
                        [0, 0, 1, 5], [6, 6, 6, 6]], np.int32)
    send = np.zeros((mp, mp, bound, d), np.float32)  # [rank, dest, row, d]
    for r in range(mp):
        for p in range(mp):
            send[r, p, :sizes[r, p]] = rng.normal(size=(sizes[r, p], d))

    picked = []
    def run(s, sz):
        picked.append(comm.native_ragged_all_to_all())
        recv_sz = jax.lax.all_to_all(sz[0].reshape(mp, 1), "model", 0, 0,
                                     tiled=True).reshape(mp)
        return comm.ragged_all_to_all_shards(s[0], sz[0], recv_sz,
                                             "model")[None]
    shim = jax.shard_map(run, mesh=mesh, in_specs=(P("model"), P("model")),
                         out_specs=P("model"), check_vma=False)
    # oracle: the plain tiled a2a of the padded shards
    plain = jax.shard_map(
        lambda s: jax.lax.all_to_all(s[0], "model", 0, 0, tiled=True)[None],
        mesh=mesh, in_specs=(P("model"),), out_specs=P("model"),
        check_vma=False)
    with mesh:
        got = np.asarray(shim(jnp.asarray(send), jnp.asarray(sizes)))
        ref = np.asarray(plain(jnp.asarray(send)))
    assert picked == [False], picked  # dense branch on XLA:CPU
    du.assert_bit_exact(got, ref)
    print("shim branches ok")
    """, devices=4)
    assert "shim branches ok" in out


def test_train_cli_runs_hier_mesh_with_auto_bounds():
    """launch/train.py accepts the 3-dim --mesh DATAxNODExMODEL plus
    --ragged_bound auto (LoadMonitor-calibrated bounds re-resolved at every
    placement replan) and takes optimizer steps."""
    out = du.run_cli(
        ["repro.launch.train", "--arch", "fastmoe-gpt", "--reduced",
         "--steps", "3", "--batch", "4", "--seq", "32", "--mesh", "1x2x4",
         "--dispatch", "ragged", "--impl", "fused", "--overlap_chunks", "2",
         "--ragged_bound", "auto", "--log_every", "1"], devices=8)
    assert "done: 3 steps" in out, out
