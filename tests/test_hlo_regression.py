"""HLO-level regressions (ISSUE 3): the properties the kernels/schedule buy
must survive XLA's optimizer, not just the jaxpr.

* A jitted fused fwd+bwd step compiles to HLO with no (M, H)-shaped
  intermediate — the hidden activation/gradient live only as VMEM tiles
  inside the three pallas_calls.  The two-pass program is the oracle that
  the check itself can see the hidden when it IS materialized.
* With ``overlap_chunks > 1`` the distributed MoE layer's HLO contains no
  blocking ``all-to-all`` at all (payload AND counts exchanges are
  ppermute-decomposed), only async-schedulable ``collective-permute``s.

Everything lowers on CPU via ``.lower().compile().as_text()``; the
multi-device case runs in a subprocess with fake devices (same pattern as
tests/test_distributed.py).
"""
import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

E, K, H, N, BM, BH = 4, 16, 40, 24, 8, 16


def _setup(seed=0):
    rng = np.random.default_rng(seed)
    gs = np.asarray([30, 26, 20, 20], np.int32)
    x = jnp.asarray(rng.normal(size=(int(gs.sum()), K)), jnp.float32)
    ws = tuple(jnp.asarray(rng.normal(size=(E, K, H)) * 0.2, jnp.float32)
               for _ in range(2))
    wo = jnp.asarray(rng.normal(size=(E, H, N)) * 0.2, jnp.float32)
    return x, ws, wo, jnp.asarray(gs)


def _hidden_rows(hlo: str) -> list[int]:
    """Row counts of every 2-D (rows, H) tensor in the HLO text."""
    return [int(m.group(1)) for m in re.finditer(rf"\[(\d+),{H}\]", hlo)]


def _compiled(loss, x, ws, wo):
    step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    return step.lower(x, ws, wo).compile().as_text()


def test_fused_step_hlo_has_no_hidden_intermediate():
    x, ws, wo, gs = _setup()
    M = x.shape[0]
    hlo = _compiled(lambda x, ws, wo: (ops.fused_grouped_ffn(
        x, ws, wo, gs, "swiglu", BM, BH) ** 2).sum(), x, ws, wo)
    rows = [r for r in _hidden_rows(hlo) if r >= M]
    assert not rows, f"(M, H)-shaped intermediates in optimized HLO: {rows}"
    # oracle: the two-pass step DOES materialize (M_padded, H) — proves the
    # check can see a hidden intermediate when one exists
    hlo2 = _compiled(lambda x, ws, wo: (ops.ffn_two_pass(
        x, ws, wo, gs, "swiglu", "pallas", BM) ** 2).sum(), x, ws, wo)
    assert any(r >= M for r in _hidden_rows(hlo2)), "oracle lost the hidden"


def test_ragged_moe_hlo_no_blocking_a2a_no_hidden():
    """The ragged (dropless) exchange inherits both HLO properties:

    * overlap_chunks > 1 -> counts AND payload exchanges are ppermute-
      decomposed, no blocking ``all-to-all`` survives XLA;
    * impl="fused" -> the per-rank fwd+bwd step materializes no 2-D
      (rows, H) tensor at the exchange-buffer row count (mp*bound) or
      above — hidden tiles stay (bm, bh) with bm=128 < mp*bound here.
      The two-pass program is the oracle that the check can see one.
    """
    script = """
        import re
        import jax
        from repro.configs.base import MoEConfig
        from repro.core import fmoe
        from repro.launch.mesh import make_local_mesh
        mesh = make_local_mesh(1, 4)
        H = 40
        cfg = MoEConfig(num_experts=8, top_k=2, d_expert_hidden=H,
                        dispatch="ragged")
        params = fmoe.fmoe_init(jax.random.PRNGKey(0), 16, cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 16))
        MB = 4 * 32 * 2  # mp * t_local * top_k = exchange-buffer rows
        serial = fmoe.DistConfig(mesh, ("data", "model"))
        piped = fmoe.DistConfig(mesh, ("data", "model"), overlap_chunks=2)
        def hlo(dist, impl, grad=False):
            f = lambda p, x_: fmoe.fmoe_apply(p, x_, cfg, dist=dist,
                                              impl=impl)[0]
            if grad:
                f = jax.grad(lambda p, x_: (fmoe.fmoe_apply(
                    p, x_, cfg, dist=dist, impl=impl)[0] ** 2).sum())
            with mesh:
                return jax.jit(f).lower(params, x).compile().as_text()
        t_piped = hlo(piped, "fused")
        t_serial = hlo(serial, "fused")
        assert "all-to-all" in t_serial, "oracle: serial ragged path must a2a"
        assert "all-to-all" not in t_piped, "blocking all-to-all survived"
        assert "collective-permute" in t_piped
        rows = lambda t: [int(m.group(1))
                          for m in re.finditer(r"\\[(\\d+),%d\\]" % H, t)]
        big = [r for r in rows(hlo(serial, "fused", grad=True)) if r >= MB]
        assert not big, f"(rows, H) intermediates in fused ragged HLO: {big}"
        big2 = [r for r in rows(hlo(serial, "pallas", grad=True)) if r >= MB]
        assert big2, "oracle lost the two-pass hidden"
        print("RAGGED_HLO_OK")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         capture_output=True, text=True, env=env, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "RAGGED_HLO_OK" in out.stdout


def test_pipelined_hlo_collectives_bracket_expert_gemms():
    """ROADMAP follow-on (ISSUE 5): the §5.2 schedule's value is exchange /
    compute overlap, so the *structure* of the optimized HLO must show it —
    collective-permutes must actually bracket the expert GEMM fusions, not
    merely replace the blocking all-to-all.

    On backends that async-schedule (TPU), every chunk's expert GEMM must
    sit between a ``collective-permute-start`` and its matching ``-done``.
    XLA:CPU lowers synchronous ``collective-permute``s, where the same
    interleaving shows as op order: with overlap_chunks=2 the instruction
    stream must contain >= 2 separate expert-GEMM runs each flanked by
    collective-permutes on both sides (S0 | S1 C0 R0 | C1 R1)."""
    import dist_utils as du

    out = du.run("""
        import re
        import jax
        from repro.configs.base import MoEConfig
        from repro.core import fmoe
        from repro.launch.mesh import make_local_mesh
        mesh = make_local_mesh(1, 4)
        cfg = MoEConfig(num_experts=8, top_k=2, d_expert_hidden=32,
                        capacity_factor=2.0)
        params = fmoe.fmoe_init(jax.random.PRNGKey(0), 16, cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 16))
        piped = fmoe.DistConfig(mesh, ("data", "model"), overlap_chunks=2)
        with mesh:
            txt = jax.jit(lambda p, x: fmoe.fmoe_apply(
                p, x, cfg, dist=piped)[0]).lower(params, x).compile().as_text()
        lines = txt.splitlines()
        # expert GEMMs: batched (E_local, rows, ·) dots — 3-D outputs.  The
        # router GEMM and combine einsum are 2-D, so they don't count.
        gemm = [i for i, l in enumerate(lines)
                if re.search(r"= \\S+\\[\\d+,\\d+,\\d+\\]\\S* dot\\(", l)]
        assert gemm, "no expert GEMMs found in optimized HLO"
        starts = [i for i, l in enumerate(lines)
                  if "collective-permute-start" in l]
        if starts:  # async backend: GEMMs inside a start/done window
            dones = [i for i, l in enumerate(lines)
                     if "collective-permute-done" in l]
            assert any(s < g < d for g in gemm
                       for s, d in zip(starts, dones)), \\
                "no expert GEMM scheduled inside a start/done window"
        else:  # sync lowering: bracket structure via instruction order
            cp = [i for i, l in enumerate(lines)
                  if re.search(r"= \\S+ collective-permute\\(", l)]
            assert cp, "no collective-permutes in pipelined HLO"
            # count maximal GEMM runs with a collective-permute on both sides
            events = sorted([(i, "cp") for i in cp] + [(i, "g") for i in gemm])
            runs, seen_cp, in_run, bracketed = 0, False, False, 0
            for _, kind in events:
                if kind == "cp":
                    if in_run:
                        bracketed += 1
                        in_run = False
                    seen_cp = True
                elif seen_cp:
                    in_run = True
            assert bracketed >= 2, (
                f"expected >= 2 expert-GEMM runs bracketed by collective-"
                f"permutes (overlap_chunks=2), found {bracketed}")
        print("BRACKET_OK")
    """, devices=4)
    assert "BRACKET_OK" in out


def test_hier_inter_node_collective_only_on_node_axis():
    """ISSUE 7 tentpole property, at the HLO level: on the (data, node,
    model) mesh the two-level ragged exchange must keep the full-size
    payload on the node-local axis — the only collectives whose replica
    groups cross the node boundary are the slim inter legs, and their
    bytes are exactly the counter's wire_bytes_inter.  The flat exchange
    on the same mesh is the oracle: one 8-wide group, everything crosses.
    """
    import dist_utils as du

    out = du.run("""
    import re
    import jax
    import dist_utils as du
    from repro.core import fmoe
    from repro.launch.roofline import collective_bytes
    env = du.moe_env(dispatch="ragged", capacity_factor=1.25)
    mesh = du.make_mesh(1, 4, node=2)  # ranks node-major: node = rank // 4
    flat = fmoe.DistConfig(mesh, ("data", "node", "model"),
                           expert_axis=("node", "model"))
    hier = flat._replace(node_axis="node", inter_bound=24)

    def wire_defs(dist):
        with mesh:
            fn = jax.jit(lambda p, x: fmoe.fmoe_apply(p, x, env.cfg,
                                                      dist=dist))
            txt = fn.lower(env.params, env.x).compile().as_text()
        return [l for l in txt.splitlines()
                if re.search(r" (all-to-all|collective-permute)\\(", l)]

    INNER = "replica_groups={{0,1,2,3},{4,5,6,7}}"   # node-local axis
    NODE = "replica_groups={{0,4},{1,5},{2,6},{3,7}}"  # crosses nodes
    lines = wire_defs(hier)
    assert lines and all((INNER in l) or (NODE in l) for l in lines), (
        "exchange collective on neither mesh axis:\\n" + "\\n".join(lines))
    cross = [l for l in lines if NODE in l]
    got = sum(collective_bytes(l).get("all-to-all", 0) for l in cross)
    # slim legs only: 2 payload legs x n_nodes*IB rows x d f32 + the
    # 8-int32 counts leg == the device counter's wire_bytes_inter
    with mesh:
        _, m = jax.jit(lambda p, x: fmoe.fmoe_apply(
            p, x, env.cfg, dist=hier))(env.params, env.x)
    want = 4 * (2 * 2 * 24 * 32 + 8)
    assert got == want == float(m.obs.wire_bytes_inter), (got, want)
    # oracle: the flat exchange's every payload crosses in one 8-wide group
    fl = wire_defs(flat)
    assert fl and all("replica_groups={{0,1,2,3,4,5,6,7}}" in l for l in fl)
    print("HIER_HLO_OK")
    """, devices=8)
    assert "HIER_HLO_OK" in out


def test_pipelined_moe_hlo_has_no_blocking_all_to_all():
    script = """
        import jax
        from repro.configs.base import MoEConfig
        from repro.core import fmoe
        from repro.launch.mesh import make_local_mesh
        mesh = make_local_mesh(1, 4)
        cfg = MoEConfig(num_experts=8, top_k=2, d_expert_hidden=32,
                        capacity_factor=2.0)
        params = fmoe.fmoe_init(jax.random.PRNGKey(0), 16, cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 16))
        serial = fmoe.DistConfig(mesh, ("data", "model"))
        piped = fmoe.DistConfig(mesh, ("data", "model"), overlap_chunks=2)
        def hlo(dist):
            with mesh:
                return jax.jit(lambda p, x: fmoe.fmoe_apply(
                    p, x, cfg, dist=dist)[0]).lower(params, x).compile().as_text()
        t_piped, t_serial = hlo(piped), hlo(serial)
        assert "all-to-all" in t_serial, "oracle: serial path must a2a"
        assert "all-to-all" not in t_piped, "blocking all-to-all survived"
        assert "collective-permute" in t_piped
        print("PIPELINED_HLO_OK")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         capture_output=True, text=True, env=env, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "PIPELINED_HLO_OK" in out.stdout
