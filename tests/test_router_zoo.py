"""Routing-zoo end-to-end tests (ISSUE 10 tentpole acceptance).

Every router in ``tests/dist_utils.ROUTERS`` must pass the same differential
sweep as the baseline top-k gate — bit-exact forward vs its single-rank
oracle on capacity AND ragged dispatch, with shadowing and overlap enabled,
grads included (no parallel test plumbing: the routers ride the existing
dist_utils oracle/assertion helpers as a new sweep axis).

Beyond the sweep:
* expert-choice gets a dense == dispatched differential (the second client
  of the dropless/ragged machinery), grads included;
* shared experts are proven absent from the exchange — device-side wire
  counters AND compiled-HLO all-to-all bytes unchanged vs a routed-only
  baseline of equal routed width;
* the DeepSeek-V2 config (shared + routed experts, MLA) runs a train step
  and a decode step end to end on a 1x4 mesh;
* expert-choice's by-construction flat load is recognized by the placement
  controller as a no-replan signal.
"""
import jax.numpy as jnp
import numpy as np
import pytest

import dist_utils as du


# ---------------------------------------------------------------------------
# The router sweep: dispatch x {plain, shadow+overlap} vs single-rank oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("router", du.ROUTERS)
def test_router_sweep_bit_exact_1x4(router):
    """Acceptance: every router, on both dispatch modes, reproduces its
    single-rank oracle bit-exactly on the 1x4 fused path — plain AND with
    shadowed hot experts + overlap chunking — including grads.

    Expert-choice routes per token shard under a2a (each rank's experts
    pick from the tokens that rank holds), so its oracle is the shard-wise
    local apply (dist_utils.oracle_sharded); every other router's routing
    is per-token and the plain oracle applies.  Grads use the aux-free loss
    (the sharded balance loss is a different function than the global one)
    and shadowed grads compare through the plan's physical permutation."""
    out = du.run(f"""
    import numpy as np, jax, jax.numpy as jnp
    import dist_utils as du
    from repro.core import fmoe
    from repro.placement import from_logical
    router = {router!r}
    mesh = du.make_mesh(1, 4)
    for dispatch in ("capacity", "ragged"):
        env = du.moe_env(dispatch=dispatch, router=router)
        if router == "expert_choice":
            y_ref, load_ref = du.oracle_sharded(env, 4, impl="fused")
        else:
            y_ref, m_ref = du.oracle(env, impl="fused")
            load_ref = m_ref.load
        dist0 = fmoe.DistConfig(mesh, ("data", "model"))
        y0, m0 = du.dist_apply(env, mesh, dist0, impl="fused")
        du.assert_bit_exact(y0, y_ref, msg=(dispatch, "plain"))
        np.testing.assert_allclose(np.asarray(m0.load),
                                   np.asarray(load_ref), atol=1e-6)
        # shadowing + overlap: same oracle, still bitwise
        pl = du.hot_shadow_plan(np.asarray(m0.load), 4, 4)
        pp = from_logical(env.params, pl)
        dist = fmoe.DistConfig(mesh, ("data", "model"), placement=pl,
                               overlap_chunks=2)
        y1, m1 = du.dist_apply(env, mesh, dist, params=pp, impl="fused")
        du.assert_bit_exact(y1, y_ref, msg=(dispatch, "shadow"))
        assert float(m1.drop_frac) == 0.0, (dispatch, "shadow drops")
        if router == "expert_choice":
            E = env.cfg.num_experts
            np.testing.assert_allclose(np.asarray(m1.load), 1.0 / E,
                                       atol=1e-6)  # flat by construction
            xs = env.x.reshape(-1, env.x.shape[-1])
            xs = xs.reshape(4, -1, env.x.shape[-1])
            def loss_ref(p):
                tot = 0.0
                for i in range(4):
                    y, _ = fmoe.fmoe_apply(p, xs[i], env.cfg, impl="fused")
                    tot = tot + (y ** 2).sum()
                return tot / env.x.size
            g_ref = jax.jit(jax.grad(loss_ref))(env.params)
        else:
            g_ref = du.layer_grads(env, None, impl="fused", aux_weight=0.0)
        if dispatch == "ragged":
            g_plain = du.layer_grads(env, dist0, mesh=mesh, impl="fused",
                                     aux_weight=0.0)
            du.assert_grads_match(g_ref, g_plain,
                                  bitwise_experts=router != "expert_choice")
        g_sh = du.layer_grads(env, dist, mesh=mesh, params=pp, impl="fused",
                              aux_weight=0.0)
        perm = jnp.asarray(list(pl.physical_to_logical))
        g_ref_p = {{**g_ref, "experts": {{k: v[perm] for k, v in
                                          g_ref["experts"].items()}}}}
        du.assert_grads_match(g_ref_p, g_sh, bitwise_experts=False)
    print("router sweep ok")
    """, devices=4)
    assert "router sweep ok" in out


# ---------------------------------------------------------------------------
# Expert-choice: dense reference == dispatched (capacity and ragged) + grads
# ---------------------------------------------------------------------------


def test_expert_choice_dense_equals_dispatched():
    """The dense single-worker expert-choice layer (core/gate
    expert_choice_moe) and the dispatched paths must agree: bit-exact on
    every cell except local ragged+einsum (XLA's ragged_dot lowering is
    group-structure-sensitive — the documented psum-docstring exception —
    so that one cell gets an ulp tolerance).  The psum mode on a 1x4 mesh
    replicates tokens over the expert axis, so dispatched global routing
    exactly equals the dense reference — grads included, bitwise."""
    out = du.run("""
    import numpy as np, jax, jax.numpy as jnp
    import dist_utils as du
    from repro.core import fmoe
    from repro.core.gate import expert_choice_moe
    mesh = du.make_mesh(1, 4)
    for dispatch in ("capacity", "ragged"):
        for impl in ("einsum", "fused"):
            env = du.moe_env(dispatch=dispatch, router="expert_choice",
                             capacity_factor=2.0)
            xf = env.x.reshape(-1, env.x.shape[-1])
            y_dense, _ = expert_choice_moe(env.params, xf, env.cfg,
                                           capacity_factor=2.0)
            y_loc, m_loc = du.oracle(env, impl=impl)
            if (dispatch, impl) == ("ragged", "einsum"):
                du.assert_close(y_loc.reshape(xf.shape), y_dense, 1e-5)
            else:
                du.assert_bit_exact(y_loc.reshape(xf.shape), y_dense,
                                    msg=(dispatch, impl, "local"))
            assert float(m_loc.drop_frac) == 0.0
            dist = fmoe.DistConfig(mesh, ("data",))
            assert dist.mode == "psum"
            y_ps, m_ps = du.dist_apply(env, mesh, dist, impl=impl)
            du.assert_bit_exact(y_ps.reshape(xf.shape), y_dense,
                                msg=(dispatch, impl, "psum"))
            np.testing.assert_allclose(np.asarray(m_ps.load),
                                       1.0 / env.cfg.num_experts, atol=1e-6)
            assert float(m_ps.drop_frac) == 0.0
            def loss_dense(p):
                y, _ = expert_choice_moe(p, xf, env.cfg, capacity_factor=2.0)
                return (y ** 2).mean()
            g_dense = jax.jit(jax.grad(loss_dense))(env.params)
            g_ps = du.layer_grads(env, dist, mesh=mesh, impl=impl,
                                  aux_weight=0.0)
            du.assert_grads_match(g_dense, g_ps, bitwise_experts=True,
                                  router_atol=1e-9)
    print("ec dense==dispatched ok")
    """, devices=4)
    assert "ec dense==dispatched ok" in out


# ---------------------------------------------------------------------------
# Shared experts: statically shadowed — zero wire traffic, HLO-verified
# ---------------------------------------------------------------------------


def test_shared_experts_absent_from_exchange():
    """Acceptance: with num_shared_experts > 0 the exchange moves exactly
    the bytes of the routed-only baseline of equal routed width — the
    device-side wire counters AND the compiled HLO's all-to-all byte totals
    are unchanged (shared experts replicate on every rank and bypass
    dispatch entirely)."""
    out = du.run("""
    import numpy as np, jax
    import dist_utils as du
    from repro.core import fmoe
    from repro.launch import roofline
    mesh = du.make_mesh()  # (2, 4)
    dist = fmoe.DistConfig(mesh, ("data", "model"))
    for dispatch in ("capacity", "ragged"):
        env0 = du.moe_env(dispatch=dispatch)
        env1 = du.moe_env(dispatch=dispatch, num_shared_experts=1)
        assert "shared" in env1.params and "shared" not in env0.params
        y0, m0 = du.dist_apply(env0, mesh, dist)
        y1, m1 = du.dist_apply(env1, mesh, dist)
        assert float(m0.obs.wire_elems) == float(m1.obs.wire_elems)
        assert float(m0.obs.wire_bytes) == float(m1.obs.wire_bytes)
        # the shared expert contributes compute (outputs differ) ...
        assert float(np.abs(np.asarray(y1) - np.asarray(y0)).max()) > 1e-3
        # ... but zero wire: HLO all-to-all bytes identical
        def a2a_bytes(env):
            with mesh:
                txt = jax.jit(lambda p, x: fmoe.fmoe_apply(
                    p, x, env.cfg, dist=dist)[0]).lower(
                        env.params, env.x).compile().as_text()
            return roofline.collective_bytes(txt).get("all-to-all", 0)
        b0, b1 = a2a_bytes(env0), a2a_bytes(env1)
        assert b0 == b1 and b0 > 0, (dispatch, b0, b1)
    print("shared zero-wire ok")
    """)
    assert "shared zero-wire ok" in out


# ---------------------------------------------------------------------------
# DeepSeek-V2: shared + routed experts end to end (train + decode)
# ---------------------------------------------------------------------------


def test_deepseek_v2_shared_and_routed_train_and_decode():
    """configs/deepseek_v2_236b.py (tiny-ified via reduced()) — MLA
    attention, routed top-k experts AND an always-on shared expert — runs a
    sharded train step and a psum-mode decode step on a 1x4 mesh."""
    out = du.run("""
    import numpy as np, jax, jax.numpy as jnp
    from repro.configs import get_config, reduced
    from repro.launch.mesh import make_local_mesh
    from repro.launch.serve import decode_dist
    from repro.launch.train import jit_train_step
    from repro.models import lm
    from repro.optim import AdamW
    cfg = reduced(get_config("deepseek-v2-236b"), num_layers=2, d_model=128)
    assert cfg.moe.num_shared_experts == 1  # reduced keeps a shared expert
    assert cfg.attention.kind == "mla"
    mesh = make_local_mesh(1, 4)
    opt = AdamW()
    B, S = 4, 32
    step_fn, pshard, oshard = jit_train_step(cfg, opt, mesh, B, S)
    params = jax.device_put(lm.init_params(jax.random.PRNGKey(0), cfg),
                            pshard)
    opt_state = jax.device_put(opt.init(params), oshard)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                          cfg.vocab_size)}
    with mesh:
        params, opt_state, m = step_fn(params, opt_state, batch,
                                       jnp.int32(0))
    loss = float(m["loss"])
    assert np.isfinite(loss) and 0 < loss < 20, loss
    dist = decode_dist(cfg, mesh, B)
    assert dist is not None and dist.mode == "psum"
    cache = lm.init_cache(cfg, B, 64)
    tok = jax.random.randint(jax.random.PRNGKey(2), (B, 1), 0,
                             cfg.vocab_size)
    with mesh:
        logits, cache, dm = jax.jit(lambda p, t, c: lm.decode_step(
            p, cfg, t, jnp.int32(0), c, dist=dist))(params, tok, cache)
    assert logits.shape == (B, 1, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())
    print("deepseek train+decode ok, loss", loss)
    """, devices=4)
    assert "deepseek train+decode ok" in out


# ---------------------------------------------------------------------------
# Flat load is a no-replan signal (expert-choice x placement controller)
# ---------------------------------------------------------------------------


def test_flat_load_skips_replan():
    """Expert-choice produces a perfectly flat load by construction; the
    placement controller must short-circuit the replan tick (no plan+cost
    pass, no migration) instead of proposing a pointless new layout."""
    from repro.core.balance import MoEMetrics
    from repro.core.monitor import LoadMonitor
    from repro.placement import PlacementController

    mon = LoadMonitor(8, ema=0.0)
    ctl = PlacementController(mon, 4, d_model=64, d_hidden=128, capacity=16,
                              every=10)
    mon.update(MoEMetrics(jnp.zeros(()), jnp.zeros(()),
                          jnp.full((8,), 0.125), jnp.zeros(())))
    assert ctl.maybe_replan(10) is None
    assert ctl.flat_skips == 1
    # near-flat within the tolerance still short-circuits
    near = np.full(8, 0.125)
    near[0] += 0.001
    near /= near.sum()
    mon.update(MoEMetrics(jnp.zeros(()), jnp.zeros(()), jnp.asarray(near),
                          jnp.zeros(())))
    assert ctl.maybe_replan(20) is None
    assert ctl.flat_skips == 2
    # a genuinely skewed load passes the gate and reaches the planner
    skew = np.array([0.5, 0.2, 0.1, 0.05, 0.05, 0.04, 0.03, 0.03])
    mon.update(MoEMetrics(jnp.zeros(()), jnp.zeros(()), jnp.asarray(skew),
                          jnp.zeros(())))
    ctl.maybe_replan(30)
    assert ctl.flat_skips == 2  # not flat-skipped


def test_flat_load_skips_replan_per_layer():
    """Per-layer mode: every layer flat => skip; one skewed layer is enough
    to run the planner."""
    from repro.core.balance import MoEMetrics
    from repro.core.monitor import LoadMonitor
    from repro.placement import PlacementController

    L, E = 2, 8
    mon = LoadMonitor(E, num_layers=L, ema=0.0)
    ctl = PlacementController(mon, 4, d_model=64, d_hidden=128, capacity=16,
                              every=10, num_layers=L)
    flat = np.full((L, E), 1.0 / E)
    mon.update(MoEMetrics(jnp.zeros(()), jnp.zeros(()), jnp.asarray(flat),
                          jnp.zeros(())))
    assert ctl.maybe_replan(10) is None
    assert ctl.flat_skips == 1
    skew = flat.copy()
    skew[1] = np.array([0.5, 0.2, 0.1, 0.05, 0.05, 0.04, 0.03, 0.03])
    mon.update(MoEMetrics(jnp.zeros(()), jnp.zeros(()), jnp.asarray(skew),
                          jnp.zeros(())))
    ctl.maybe_replan(20)
    assert ctl.flat_skips == 1  # layer 1's skew reached the planner
