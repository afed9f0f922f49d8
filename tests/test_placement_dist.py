"""Distributed placement/shadowing tests — subprocesses with fake devices
(tests/dist_utils.py is the consolidated harness; the main process keeps its
single CPU device).

ISSUE-5 acceptance lives here: per-layer plans are bit-exact vs the
shared-plan path when every layer sees the same load, a skewed (L, E) load
yields genuinely distinct per-layer physical layouts, and the decode (psum)
path with shadowed hot experts is bit-exact vs the unshadowed decode.
"""
import dist_utils as du

_SETUP = """
    import numpy as np, jax, jax.numpy as jnp
    import dist_utils as du
    from repro.core import fmoe
    from repro.placement import from_logical
    env = du.moe_env()
    mesh = du.make_mesh()
    dist0 = fmoe.DistConfig(mesh, ("data", "model"))
    y0, m0 = du.dist_apply(env, mesh, dist0)
    load = np.asarray(m0.load)
"""


def test_shadowed_a2a_matches_unshadowed():
    """Acceptance (PR 1): shadowing is numerically equivalent to the baseline
    a2a, for both a pure permutation (S=0) and replicated hot experts."""
    out = du.run(_SETUP + """
    from repro.core import naive
    y_ref = naive.moe_loop_masked(env.params, env.x, env.cfg)
    du.assert_close(y0, y_ref, 1e-5)
    for S in (0, 4):
        pl = du.hot_shadow_plan(load, 4, S)
        pp = from_logical(env.params, pl)
        dist = fmoe.DistConfig(mesh, ("data", "model"), placement=pl)
        y1, m1 = du.dist_apply(env, mesh, dist, params=pp)
        du.assert_close(y1, y0, 1e-5, msg=S)
        assert np.allclose(np.asarray(m1.load), load), S  # logical order
    print("shadow equivalence ok")
    """)
    assert "shadow equivalence ok" in out


def test_shadowed_a2a_shrinks_exchange_bytes():
    """Acceptance (PR 1): replication degree > 1 reduces the exchanged buffer."""
    out = du.run(_SETUP + """
    from repro.launch import roofline
    def a2a_bytes(dist, p):
        with mesh:
            txt = jax.jit(lambda pa, xx: fmoe.fmoe_apply(
                pa, xx, env.cfg, dist=dist)[0]).lower(p, env.x).compile().as_text()
        return roofline.collective_bytes(txt).get("all-to-all", 0)
    b0 = a2a_bytes(dist0, env.params)
    pl = du.hot_shadow_plan(load, 4, 4)
    assert int(pl.replication.max()) == 4  # degree > 1 on the shadowed set
    b1 = a2a_bytes(fmoe.DistConfig(mesh, ("data", "model"), placement=pl),
                   from_logical(env.params, pl))
    assert 0 < b1 < b0, (b0, b1)
    print("a2a bytes", b0, "->", b1)
    """)
    assert "a2a bytes" in out


def test_shadowed_gradients_flow_and_sync():
    """Replicated shadow-expert grads must be identical across ranks (the
    all-reduce the cost model charges for); owned-expert grads stay sharded."""
    print(du.run(_SETUP + """
    pl = du.hot_shadow_plan(load, 4, 4)
    pp = from_logical(env.params, pl)
    dist = fmoe.DistConfig(mesh, ("data", "model"), placement=pl)
    g = du.layer_grads(env, dist, mesh=mesh, params=pp)
    assert all(np.isfinite(np.asarray(l, np.float32)).all()
               for l in jax.tree.leaves(g))
    # grads exist for every expert (shadowed included)
    gw = np.asarray(g["experts"]["wi_gate"], np.float32)
    assert (np.abs(gw).sum(axis=(1, 2)) > 0).all()
    print("shadow grads ok")
    """))


def test_capacity_shrink_equivalent_when_no_drops():
    """capacity_scale < 1 must stay numerically equivalent while capacity
    still covers the actual load (cf is generous here)."""
    print(du.run(_SETUP + """
    pl = du.hot_shadow_plan(load, 4, 4, capacity_scale=0.5)
    pp = from_logical(env.params, pl)
    dist = fmoe.DistConfig(mesh, ("data", "model"), placement=pl)
    y1, m1 = du.dist_apply(env, mesh, dist, params=pp)
    du.assert_close(y1, y0, 1e-5)
    assert float(m1.drop_frac) == float(m0.drop_frac)
    print("capacity shrink ok")
    """))


# ---------------------------------------------------------------------------
# Per-layer plans (ISSUE 5 tentpole acceptance)
# ---------------------------------------------------------------------------


_LM_SETUP = """
    import dataclasses
    import numpy as np, jax, jax.numpy as jnp
    import dist_utils as du
    from repro.configs import get_config, reduced
    from repro.core.fmoe import DistConfig
    from repro.models import lm
    from repro.placement import (from_logical, plan_placement,
                                 plan_placement_per_layer)
    cfg = reduced(get_config("fastmoe-gpt"), num_layers=2, d_model=64)
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, num_experts=8, capacity_factor=8.0))
    E, L = 8, cfg.num_layers
    mesh = du.make_mesh(1, 4)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                              cfg.vocab_size)
    dist0 = DistConfig(mesh, ("data", "model"))
    with mesh:
        logits0, m0, loads = jax.jit(lambda p, t: lm.forward(
            p, cfg, t, dist=dist0, layer_loads=True))(params, toks)
    kw = dict(d_model=cfg.d_model, d_hidden=cfg.moe.d_expert_hidden,
              capacity=4096)
    def run_placed(plan):
        pp = from_logical(params, plan)
        dist = DistConfig(mesh, ("data", "model"), placement=plan)
        with mesh:
            return jax.jit(lambda p, t: lm.forward(p, cfg, t,
                                                   dist=dist))(pp, toks)
"""


def test_per_layer_identical_load_bit_exact_vs_shared():
    """Acceptance: with every layer given the same load, the per-layer path
    degenerates to the shared plan — logits bitwise-identical."""
    out = du.run(_LM_SETUP + """
    row = np.asarray(loads[0])
    plp = plan_placement_per_layer(np.stack([row] * L), 4, **kw)
    shared = plan_placement(row, 4, **kw)
    assert all(p == shared for p in plp.layers)
    ys, _ = run_placed(shared)
    yp, _ = run_placed(plp)
    du.assert_bit_exact(ys, yp)
    print("per-layer degenerate bit-exact ok")
    """, devices=4)
    assert "per-layer degenerate bit-exact ok" in out


def test_per_layer_skewed_load_distinct_layouts():
    """Acceptance: a skewed (L, E) load produces >= 2 distinct per-layer
    physical layouts, and the placed forward still matches the baseline."""
    out = du.run(_LM_SETUP + """
    rng = np.random.default_rng(0)
    zipf = 1.0 / (np.arange(E) + 1) ** 1.5
    skew = np.stack([zipf[rng.permutation(E)] for _ in range(L)])
    plp = plan_placement_per_layer(skew, 4, **kw)
    layouts = {p.physical_to_logical for p in plp.layers}
    assert len(layouts) >= 2, layouts
    yp, mp_ = run_placed(plp)
    du.assert_close(yp, logits0, 2e-3)
    print("per-layer distinct layouts ok:", len(layouts),
          "shadow:", plp.num_shadow)
    """, devices=4)
    assert "per-layer distinct layouts ok" in out


def test_per_layer_grads_and_monitor_order():
    """Grads flow through the per-layer tables; the load monitor output
    stays in logical expert order for every layer."""
    print(du.run(_LM_SETUP + """
    rng = np.random.default_rng(1)
    zipf = 1.0 / (np.arange(E) + 1) ** 1.5
    plp = plan_placement_per_layer(
        np.stack([zipf[rng.permutation(E)] for _ in range(L)]), 4, **kw)
    pp = from_logical(params, plp)
    dist = DistConfig(mesh, ("data", "model"), placement=plp)
    def loss(p):
        return lm.loss_fn(p, cfg, {"tokens": toks}, dist=dist)[0]
    with mesh:
        g = jax.jit(jax.grad(loss))(pp)
        _, aux = jax.jit(lambda p: lm.loss_fn(p, cfg, {"tokens": toks},
                                              dist=dist))(pp)
    assert all(np.isfinite(np.asarray(l, np.float32)).all()
               for l in jax.tree.leaves(g))
    # per-layer loads in logical order == the unplaced baseline's
    np.testing.assert_allclose(np.asarray(aux["load_layers"]),
                               np.asarray(loads), atol=1e-6)
    print("per-layer grads + monitor order ok")
    """, devices=4))


# ---------------------------------------------------------------------------
# Decode (psum) shadowing — the serving half of the tentpole
# ---------------------------------------------------------------------------


def test_psum_decode_shadowing_bit_exact():
    """Acceptance: psum decode with shadowed hot experts == the unshadowed
    decode, bitwise, on both dispatch modes (1x4 fake-device mesh).

    The unshadowed control is the SAME physical layout with num_shadow=0
    (identical migrated params — the only variable is shadowing), and the
    S=0 permuted plan must in turn match the plain unplaced decode to
    combine-rounding tolerance (the plain path keeps the k-fold-cheaper
    combined psum; placed runs use the slot-wise reduction).

    Bitwise holds on every (dispatch, impl) cell except ragged+einsum: the
    slot-wise combine reduces across ranks before the fixed-order k-sum
    (dispatch.combine_capacity_slots), and the Pallas grouped kernels
    accumulate group-relative (pad_to_tiles), so nothing observes WHERE an
    expert's rows sit — but XLA's ragged_dot lowering is group-structure-
    sensitive (a 1-group call simplifies differently than a 2-group call),
    so that one cell gets an ulp-tolerance instead.
    """
    out = du.run("""
    import numpy as np, jax
    import dist_utils as du
    from repro.core import fmoe
    from repro.placement import from_logical
    mesh = du.make_mesh(1, 4)
    for dispatch, impl in [("capacity", "einsum"), ("capacity", "fused"),
                           ("ragged", "fused"), ("ragged", "pallas"),
                           ("ragged", "einsum")]:
        env = du.moe_env(dispatch=dispatch)
        dist0 = fmoe.DistConfig(mesh, ("data",))
        assert dist0.mode == "psum"
        y0, m0 = du.dist_apply(env, mesh, dist0, impl=impl)
        load = np.asarray(m0.load)
        pl4 = du.hot_shadow_plan(load, 4, 4)
        pl0 = pl4._replace(num_shadow=0)  # same layout, shadowing off
        # capacity_scale=0.5 must be a no-op here: psum has no a2a buffer
        # to shrink, so the plan's shrink must not introduce decode drops
        pl4s = pl4._replace(capacity_scale=0.5)
        pp = from_logical(env.params, pl4)  # same physical order for all
        def run(pl):
            dist = fmoe.DistConfig(mesh, ("data",), placement=pl)
            return du.dist_apply(env, mesh, dist, params=pp, impl=impl)
        y_un, m_un = run(pl0)
        du.assert_close(y_un, y0, 1e-5, msg=(dispatch, impl, "perm"))
        for tag, pl in (("S4", pl4), ("S4-shrunk", pl4s)):
            y1, m1 = run(pl)
            if (dispatch, impl) == ("ragged", "einsum"):
                du.assert_close(y1, y_un, 1e-5, msg=(dispatch, impl, tag))
            else:
                du.assert_bit_exact(y1, y_un, msg=(dispatch, impl, tag))
            assert np.allclose(np.asarray(m1.load), load), (dispatch, tag)
            assert float(m1.drop_frac) == float(m_un.drop_frac), tag
    print("psum shadow bit-exact ok")
    """, devices=4)
    assert "psum shadow bit-exact ok" in out


# ---------------------------------------------------------------------------
# Replan hook end to end (shared + per-layer)
# ---------------------------------------------------------------------------


_HOOK_SETUP = """
    import dataclasses
    import numpy as np, jax, jax.numpy as jnp
    from repro.configs import get_config, reduced
    from repro.launch.mesh import make_local_mesh
    from repro.launch.train import ReplanHook, jit_train_step
    from repro.models import lm
    from repro.optim import AdamW
    cfg = reduced(get_config("fastmoe-gpt"), num_layers=2, d_model=64)
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe,
                                                           num_experts=16))
    mesh = make_local_mesh(1, 4)
    opt = AdamW()
    B, S = 8, 32
    step_fn, pshard, oshard = jit_train_step(cfg, opt, mesh, B, S)
    params = jax.device_put(lm.init_params(jax.random.PRNGKey(0), cfg), pshard)
    opt_state = jax.device_put(opt.init(params), oshard)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                          cfg.vocab_size)}
    def drive(hook, fake_metrics, steps=6):
        global step_fn, params, opt_state
        hook.controller.min_gain = -10.0  # force accept to exercise migration
        replans, losses = 0, []
        for step in range(steps):
            with mesh:
                params, opt_state, m = step_fn(params, opt_state, batch,
                                               jnp.int32(step))
            losses.append(float(m["loss"]))
            params, opt_state, new_fn = hook.observe(
                step, fake_metrics, params, opt_state)
            if new_fn is not None:
                step_fn = new_fn
                replans += 1
        return replans, losses
"""


def test_replan_hook_migrates_live_training():
    """End-to-end: train on a mesh, force a replan, keep training — loss
    stays finite and the migrated layout keeps learning."""
    print(du.run(_HOOK_SETUP + """
    hook = ReplanHook(cfg, opt, mesh, B, S, every=2)
    skew = 1.0 / (np.arange(16) + 1) ** 1.5
    replans, losses = drive(hook, {"load": skew, "drop_frac": 0.0})
    assert replans >= 1, "replan never fired"
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0] + 0.5, losses  # still learning post-migration
    print("replan hook ok", replans, [round(l, 3) for l in losses])
    """, devices=4))


def test_replan_hook_per_layer_migrates_live_training():
    """Per-layer mode: the hook plans from (L, E) loads, migrates each
    layer's slice independently, and the re-jitted step keeps training."""
    print(du.run(_HOOK_SETUP + """
    from repro.placement import PerLayerPlacement
    hook = ReplanHook(cfg, opt, mesh, B, S, every=2, per_layer=True)
    rng = np.random.default_rng(0)
    zipf = 1.0 / (np.arange(16) + 1) ** 1.5
    skew = np.stack([zipf[rng.permutation(16)] for _ in range(cfg.num_layers)])
    replans, losses = drive(hook, {"load_layers": skew, "drop_frac": 0.0})
    assert replans >= 1, "per-layer replan never fired"
    assert isinstance(hook.placement, PerLayerPlacement)
    assert len({p.physical_to_logical for p in hook.placement.layers}) >= 2
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0] + 0.5, losses
    print("per-layer replan hook ok", replans, [round(l, 3) for l in losses])
    """, devices=4))
