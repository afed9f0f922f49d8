"""Ragged (dropless) expert-parallel exchange — ISSUE 4 tentpole tests.

The distributed ragged path must be *the same function* as the single-rank
ragged path: per-row outputs bit-identical (the exchange only moves rows),
expert grads bit-identical on the acceptance mesh (1x4, fused impl — same
rows, same tile partitioning, same f32 accumulation order), and composed
correctly with the capacity a2a, Zipf skew (ranks receiving zero tokens),
the bf16 wire, overlap chunking, and bounded (dropping) shards.

Host tests exercise the pure index math of core/dispatch through the
multi-rank emulation oracle in tests/dist_utils.py; multi-device cases run
in subprocesses via the same harness (the main process keeps its single CPU
device).
"""
import jax.numpy as jnp
import numpy as np

import dist_utils as du
from repro.core import dispatch as D


# ---------------------------------------------------------------------------
# Host-level: the send/recv plan index math, emulated end to end in numpy
# ---------------------------------------------------------------------------


def test_xplan_recv_roundtrip():
    rng = np.random.default_rng(0)
    mp, e_local, t, k = 4, 2, 8, 2
    bound = t * k  # dropless
    rows, outs = du.emulate_ragged_exchange(rng, mp, e_local, t, k, bound)
    total_seen = 0
    for r, (compact, gs_local, incoming) in enumerate(outs):
        # group sizes = what every source assigned to this rank's experts
        want = np.zeros(e_local, np.int64)
        for s in range(mp):
            ids = rows[s][0]
            for e in range(e_local):
                want[e] += (ids == r * e_local + e).sum()
        np.testing.assert_array_equal(gs_local, want)
        # compact rows: expert segments contiguous, src-major inside, and
        # every row tagged with the expert that owns its segment
        off = 0
        for e in range(e_local):
            seg = compact[off:off + gs_local[e]]
            assert (seg[:, 0] >= 0).all(), "hole inside a valid segment"
            # src-major: source ranks non-decreasing within the segment
            assert (np.diff(seg[:, 0]) >= 0).all()
            for src, orig in seg:
                assert rows[src][0][orig] == r * e_local + e
            off += gs_local[e]
        assert (compact[off:, 0] == -1).all(), "rows past the valid prefix"
        total_seen += int(gs_local.sum())
    assert total_seen == mp * t * k  # dropless: every (token, slot) row lands


def test_xplan_bounded_drops_trailing_experts():
    # one peer overloaded: the bound truncates its trailing experts first
    gs = jnp.asarray([5, 4, 0, 1], jnp.int32)  # 2 peers x 2 experts, 10 rows
    xp = D.make_ragged_xplan(gs, 10, 4, 2, bound=6)
    np.testing.assert_array_equal(np.asarray(xp.peer_counts), [[5, 1], [0, 1]])
    assert int(xp.keep.sum()) == 7  # 3 of peer-0's expert-1 rows dropped
    assert int(xp.num_owned_rows) == 10
    dest = np.asarray(xp.send_dest)
    assert (np.sort(dest[dest < 12]) == np.r_[0:6, 6:7]).all()


def test_xplan_shadow_tail_stays_local():
    # experts [2, 4) shadowed: their rows must not enter the send buffer
    gs = jnp.asarray([2, 3, 4, 1], jnp.int32)
    xp = D.make_ragged_xplan(gs, 10, 2, 2, bound=10)
    assert int(xp.num_owned_rows) == 5
    dest = np.asarray(xp.send_dest)
    assert (dest[5:] == 20).all()  # shadow tail dropped from the exchange
    assert (dest[:5] < 20).all()


def test_recv_compact_zero_source():
    # a source rank that sends nothing: its whole shard is padding
    incoming = jnp.asarray([[0, 0], [3, 2]], jnp.int32)
    cplan, gs = D.ragged_recv_compact(incoming, bound=8)
    np.testing.assert_array_equal(np.asarray(gs), [3, 2])
    cp = np.asarray(cplan)
    assert (cp[:8] == 16).all()  # rank 0's shard entirely invalid
    np.testing.assert_array_equal(cp[8:13], [0, 1, 2, 3, 4])


# ---------------------------------------------------------------------------
# Multi-device: equality with the single-rank ragged path + composition
# ---------------------------------------------------------------------------

_SETUP = """
    import numpy as np, jax, jax.numpy as jnp
    import dist_utils as du
    from repro.core import fmoe
    env = du.moe_env(dispatch="ragged", capacity_factor=1.25)
    mesh = du.make_mesh()
"""


def test_ragged_a2a_matches_single_rank_and_capacity():
    out = du.run(_SETUP + """
    import dataclasses
    for impl in ("einsum", "pallas", "fused"):
        y_ref, m_ref = du.oracle(env, impl=impl)
        y, m = du.dist_apply(env, mesh,
                             fmoe.DistConfig(mesh, ("data", "model")),
                             impl=impl)
        du.assert_close(y, y_ref, 1e-5, msg=impl)
        np.testing.assert_allclose(np.asarray(m.load), np.asarray(m_ref.load),
                                   atol=1e-6)
        assert float(m.drop_frac) == 0.0  # dropless by construction
        # psum mode (tokens not sharded over the expert axis)
        yp, mp_ = du.dist_apply(env, mesh, fmoe.DistConfig(mesh, ("data",)),
                                impl=impl)
        du.assert_close(yp, y_ref, 1e-5, msg=impl)
        assert float(mp_.drop_frac) == 0.0
    # vs the capacity a2a under uniform-ish load (cf large enough: no drops)
    envc = du.moe_env(dispatch="capacity", capacity_factor=8.0)
    ycap, mcap = du.dist_apply(envc, mesh,
                               fmoe.DistConfig(mesh, ("data", "model")))
    yrag, _ = du.dist_apply(env, mesh, fmoe.DistConfig(mesh, ("data", "model")))
    assert float(mcap.drop_frac) == 0.0
    du.assert_close(ycap, yrag, 1e-5)
    print("ragged matches ok")
    """)
    assert "ragged matches ok" in out


def test_ragged_bit_exact_on_1x4_fused():
    """Acceptance: --dispatch ragged --impl fused --mesh 1x4 — forward
    outputs AND expert grads bit-identical to the single-rank ragged path
    (same rows, same tile layout, same f32 accumulation order).  The router
    grad is x^T @ dlogits at a different GEMM shape (t vs T rows), so it
    matches to f32 reassociation tolerance, not bitwise — that GEMM is
    outside the exchange."""
    out = du.run("""
    import numpy as np, jax, jax.numpy as jnp
    import dist_utils as du
    from repro.core import fmoe
    env = du.moe_env(dispatch="ragged", capacity_factor=1.25)
    mesh = du.make_mesh(1, 4)
    dist = fmoe.DistConfig(mesh, ("data", "model"))
    cfg = env.cfg

    def loss(p, x, dist):
        y, _ = fmoe.fmoe_apply(p, x, cfg, dist=dist, impl="fused")
        return (y ** 2).mean()

    def train(dist, steps=3, lr=0.1):
        # SGD on the expert weights (everything that crosses the exchange).
        # The router weight stays frozen: its grad is x^T @ dlogits at a
        # different GEMM shape per sharding, bitwise-equal only up to f32
        # reassociation, and feeding that ulp back would cascade.
        p = env.params
        step = jax.jit(lambda p, x: (
            fmoe.fmoe_apply(p, x, cfg, dist=dist, impl="fused")[0],
            jax.grad(loss)(p, x, dist)))
        ys, gr = [], None
        for _ in range(steps):
            with mesh:
                y, g = step(p, env.x)
            p = {**p, "experts": jax.tree.map(lambda a, b: a - lr * b,
                                              p["experts"], g["experts"])}
            ys.append(np.asarray(y))
            gr = g
        return ys, p, gr

    ys0, p0, g0 = train(None)
    ys1, p1, g1 = train(dist)
    for a, b in zip(ys0, ys1):
        du.assert_bit_exact(a, b)  # bitwise, every step
    for k in ("wi_gate", "wi_up", "wo"):
        du.assert_bit_exact(p0["experts"][k], p1["experts"][k])
    du.assert_grads_match(g0, g1)
    print("1x4 fused bit-exact ok")
    """, devices=4)
    assert "1x4 fused bit-exact ok" in out


def test_ragged_chunked_wire_and_skew():
    """overlap_chunks (ppermute micro-shards) and the bf16 wire compose with
    the ragged exchange; Zipf-style skew routing everything to two experts
    leaves half the ranks receiving zero tokens and still matches the
    single-rank path with zero drops."""
    out = du.run(_SETUP + """
    y0, m0 = du.dist_apply(env, mesh, fmoe.DistConfig(mesh, ("data", "model")))
    for nc in (2, 4, 3):
        y1, m1 = du.dist_apply(env, mesh, fmoe.DistConfig(
            mesh, ("data", "model"), overlap_chunks=nc))
        du.assert_bit_exact(y1, y0, msg=nc)
        np.testing.assert_array_equal(np.asarray(m0.load), np.asarray(m1.load))
    yb, _ = du.dist_apply(env, mesh, fmoe.DistConfig(mesh, ("data", "model"),
                                                     wire_dtype="bf16"))
    yb4, _ = du.dist_apply(env, mesh, fmoe.DistConfig(
        mesh, ("data", "model"), wire_dtype="bf16", overlap_chunks=4))
    err = float(jnp.abs(yb - y0).max())
    assert 0 < err < 0.05, err  # bf16 quantization, and the cast happened
    du.assert_bit_exact(yb4, yb)
    # skew: all tokens to experts {0, 1} -> ranks owning experts 4..7 get 0
    skew = du.skew_router(env)
    y_ref, m_ref = du.oracle(skew, impl="fused")
    y2, m2 = du.dist_apply(skew, mesh, fmoe.DistConfig(mesh, ("data", "model")),
                           impl="fused")
    du.assert_close(y2, y_ref, 1e-5)
    assert float(m2.drop_frac) == 0.0
    load = np.asarray(m2.load)
    np.testing.assert_allclose(load[:2], [0.5, 0.5], atol=1e-6)
    assert (load[2:] == 0).all()
    print("chunked+wire+skew ok")
    """)
    assert "chunked+wire+skew ok" in out


def test_ragged_composes_with_shadow_placement():
    """Shadowed hot experts are served locally outside the exchange: outputs
    identical, monitor load still in logical order, and the shadow filler
    composes with chunking."""
    out = du.run(_SETUP + """
    from repro.placement import from_logical
    y0, m0 = du.dist_apply(env, mesh, fmoe.DistConfig(mesh, ("data", "model")))
    load = np.asarray(m0.load)
    plan = du.hot_shadow_plan(load, 4, 4)
    pp = from_logical(env.params, plan)
    for nc in (0, 4):
        y1, m1 = du.dist_apply(env, mesh, fmoe.DistConfig(
            mesh, ("data", "model"), placement=plan, overlap_chunks=nc),
            params=pp)
        du.assert_close(y1, y0, 1e-5, msg=nc)
        np.testing.assert_allclose(np.asarray(m1.load), load, atol=1e-6)
    print("shadow compose ok")
    """)
    assert "shadow compose ok" in out


def test_ragged_bound_trades_drops():
    """A sub-dropless ragged_bound drops the over-bound rows (tracked in
    drop_frac) and still produces finite outputs; the default bound drops
    nothing on the same input."""
    out = du.run(_SETUP + """
    skew = du.skew_router(env)  # all rows to experts 0/1 = rank 0's shard
    _, m_full = du.dist_apply(skew, mesh,
                              fmoe.DistConfig(mesh, ("data", "model")))
    assert float(m_full.drop_frac) == 0.0
    yb, mb = du.dist_apply(skew, mesh, fmoe.DistConfig(
        mesh, ("data", "model"), ragged_bound=8))
    # per rank: 32 rows all to peer 0, bound 8 -> 24/32 dropped
    np.testing.assert_allclose(float(mb.drop_frac), 0.75, atol=1e-6)
    assert np.isfinite(np.asarray(yb)).all()
    print("bound drops ok")
    """)
    assert "bound drops ok" in out


def test_train_cli_runs_ragged_mesh():
    """launch/train.py accepts --dispatch ragged with --mesh (the ISSUE-4
    unlock) and takes optimizer steps."""
    out = du.run_cli(
        ["repro.launch.train", "--arch", "fastmoe-gpt", "--reduced",
         "--steps", "2", "--batch", "4", "--seq", "32", "--mesh", "1x4",
         "--dispatch", "ragged", "--impl", "fused", "--overlap_chunks", "2",
         "--log_every", "1"], devices=4)
    assert "done: 2 steps" in out, out
