"""Consolidated multi-rank differential-test harness (ISSUE 5 satellite).

The single source of truth for everything the distributed tests used to
duplicate per module:

* :func:`run` — the subprocess runner.  Multi-device tests execute scripts
  under ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` in a child
  process so the main pytest process keeps its single CPU device (the
  dry-run contract in tests/conftest.py).  The child's ``PYTHONPATH``
  includes this directory, so scripts ``import dist_utils`` and reuse the
  helpers below *inside* the subprocess.
* mesh / MoE-layer builders — :func:`make_mesh`, :func:`moe_env`.
* the single-rank oracle — :func:`oracle` (``fmoe_apply`` without ``dist``):
  every distributed mode must reproduce it, the ragged/fused ones bitwise.
* differential assertions — :func:`assert_close`, :func:`assert_bit_exact`,
  and :func:`assert_grads_match` (expert grads bitwise, router grad to f32
  reassociation tolerance — its GEMM shape differs per sharding).
* the host-level ragged-exchange emulation (:func:`emulate_ragged_exchange`)
  exercising core/dispatch's plan index math without devices.
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TESTS = os.path.join(ROOT, "tests")


def run(script: str, devices: int = 8, timeout: int = 560) -> str:
    """Run ``script`` in a subprocess with ``devices`` fake host devices."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.pathsep.join([os.path.join(ROOT, "src"), TESTS])
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def run_cli(argv: list, devices: int = 4, timeout: int = 560, env=None,
            check: bool = True):
    """Run a ``python -m`` CLI (e.g. repro.launch.train) on fake devices.

    ``env`` adds/overrides child environment vars (e.g. ``REPRO_FAULTS``
    for the resilience drills).  ``check=False`` returns the
    CompletedProcess instead of asserting exit 0 — crash drills assert a
    *specific* non-zero code (faults.CRASH_EXIT_CODE)."""
    child = dict(os.environ)
    child["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    child["PYTHONPATH"] = os.path.join(ROOT, "src")
    if env:
        child.update(env)
    out = subprocess.run([sys.executable, "-m"] + argv, capture_output=True,
                         text=True, env=child, timeout=timeout, cwd=ROOT)
    if not check:
        return out
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


# ---------------------------------------------------------------------------
# Script-side builders (used inside the subprocess; need the fake devices)
# ---------------------------------------------------------------------------


def make_mesh(data: int = 2, model: int = 4, node: int = 0):
    """Flat (data, model) mesh, or the (data, node, model) node-major mesh
    of the two-level hierarchy when ``node`` is given — the program's own
    meshes (launch/mesh.make_local_mesh), with the tests' default shape."""
    from repro.launch.mesh import make_local_mesh

    return make_local_mesh(data, model, node=node or 1)


def moe_env(*, num_experts: int = 8, top_k: int = 2, d_hidden: int = 64,
            d_model: int = 32, tokens=(8, 16), dispatch: str = "capacity",
            capacity_factor: float = 8.0, seed: int = 0,
            **cfg_kw) -> SimpleNamespace:
    """One MoE layer + inputs: the shared fixture of every differential test.

    Defaults match the historical test setup (generous capacity_factor so
    the capacity modes don't drop and stay comparable to dropless paths).
    """
    from repro.configs.base import MoEConfig
    from repro.core import fmoe

    cfg = MoEConfig(num_experts=num_experts, top_k=top_k,
                    d_expert_hidden=d_hidden, capacity_factor=capacity_factor,
                    dispatch=dispatch, **cfg_kw)
    params = fmoe.fmoe_init(jax.random.PRNGKey(seed), d_model, cfg)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (*tokens, d_model))
    return SimpleNamespace(cfg=cfg, params=params, x=x)


def skew_router(env, hot=(10.0, 5.0)) -> SimpleNamespace:
    """The env with a router forced to route every (positive) token to the
    first len(hot) experts — the Zipf-skew / zero-token-rank case."""
    w = np.zeros((env.x.shape[-1], env.cfg.num_experts), np.float32)
    for e, v in enumerate(hot):
        w[:, e] = v
    params = {**env.params,
              "router": {**env.params["router"], "w": jnp.asarray(w)}}
    return SimpleNamespace(cfg=env.cfg, params=params,
                           x=jnp.abs(env.x) + 0.1)


# the router sweep axis: every variant must pass the same dispatch x impl x
# dist x overlap differential sweep (single-rank oracle, same assertions)
ROUTERS = ("topk", "noisy_topk", "gumbel", "expert_choice", "frozen")


def oracle(env, impl: str = "einsum", params=None, x=None):
    """The single-rank reference: fmoe_apply with no dist."""
    from repro.core import fmoe

    return fmoe.fmoe_apply(params if params is not None else env.params,
                           x if x is not None else env.x, env.cfg, impl=impl)


def oracle_sharded(env, n_shards: int, impl: str = "einsum", params=None,
                   x=None):
    """Shard-wise single-rank reference: fmoe_apply per token shard,
    concatenated back.  This is the oracle for routers whose decision
    depends on the token *population* — expert-choice picks each expert's
    top-C from the tokens it can see, so under token sharding the reference
    routes each shard independently (n_shards = the product of the dist's
    token axes).  With n_shards=1 it degenerates to :func:`oracle`."""
    from repro.core import fmoe

    p = params if params is not None else env.params
    xv = x if x is not None else env.x
    xf = xv.reshape(-1, xv.shape[-1])
    assert xf.shape[0] % n_shards == 0
    shards = xf.reshape(n_shards, -1, xv.shape[-1])
    ys, loads = [], []
    for i in range(n_shards):
        y, m = fmoe.fmoe_apply(p, shards[i], env.cfg, impl=impl)
        ys.append(y)
        loads.append(m.load)
    return (jnp.concatenate(ys, 0).reshape(xv.shape),
            jnp.stack(loads).mean(0))


def dist_apply(env, mesh, dist, params=None, x=None, impl: str = "einsum"):
    """Jitted distributed apply under ``mesh`` (the differential side)."""
    from repro.core import fmoe

    with mesh:
        return jax.jit(lambda p, x_: fmoe.fmoe_apply(
            p, x_, env.cfg, dist=dist, impl=impl))(
                params if params is not None else env.params,
                x if x is not None else env.x)


def layer_grads(env, dist, mesh=None, params=None, impl: str = "einsum",
                aux_weight: float = 0.01):
    """Grads of a scalar loss through the layer ((y**2).mean() + aux).

    ``aux_weight=0.0`` drops the aux term — the bitwise grad comparisons
    use it because the sharded balance loss (pmean of per-shard f·P) is a
    *different function* than the single-rank global one, so its grads
    legitimately diverge beyond rounding."""
    from repro.core import fmoe

    def loss(p):
        y, m = fmoe.fmoe_apply(p, env.x, env.cfg, dist=dist, impl=impl)
        return (y ** 2).mean() + aux_weight * m.aux_loss

    p = params if params is not None else env.params
    if mesh is None:
        return jax.jit(jax.grad(loss))(p)
    with mesh:
        return jax.jit(jax.grad(loss))(p)


def hot_shadow_plan(load, num_ranks: int, num_shadow: int,
                    capacity_scale: float = 1.0):
    """The canonical test plan: shadow the S hottest experts (physical tail),
    keep the owned experts sorted ascending in the front block."""
    from repro.placement import ExpertPlacement

    load = np.asarray(load)
    hot = np.argsort(-load)
    S = num_shadow
    phys = (tuple(int(e) for e in np.sort(hot[S:]))
            + tuple(int(e) for e in hot[:S]))
    return ExpertPlacement(load.size, num_ranks, phys, num_shadow=S,
                           capacity_scale=capacity_scale)


# ---------------------------------------------------------------------------
# Differential assertions
# ---------------------------------------------------------------------------


def assert_close(a, b, tol: float = 1e-5, msg=""):
    err = float(jnp.abs(jnp.asarray(a) - jnp.asarray(b)).max())
    assert err < tol, (msg, err)


def assert_bit_exact(a, b, msg=""):
    a, b = np.asarray(a), np.asarray(b)
    assert (a == b).all(), (msg, float(np.abs(a - b).max()))


def assert_grads_match(g_ref, g_dist, *, bitwise_experts: bool = True,
                       router_atol: float = 1e-6):
    """Expert grads bitwise (same rows, same tile partitioning, same f32
    accumulation order across the exchange); router grad to reassociation
    tolerance (x^T @ dlogits runs at a different GEMM shape per sharding)."""
    for k, v in g_ref["experts"].items():
        a, b = np.asarray(v), np.asarray(g_dist["experts"][k])
        if bitwise_experts:
            np.testing.assert_array_equal(a, b, err_msg=f"experts/{k}")
        else:
            np.testing.assert_allclose(a, b, atol=router_atol,
                                       err_msg=f"experts/{k}")
    for rk in g_ref["router"]:  # w, plus w_noise / w_frozen per router
        np.testing.assert_allclose(np.asarray(g_ref["router"][rk]),
                                   np.asarray(g_dist["router"][rk]),
                                   atol=router_atol, err_msg=f"router/{rk}")
    for l_ref, l_dist in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_dist)):
        assert np.isfinite(np.asarray(l_ref, np.float32)).all()
        assert np.isfinite(np.asarray(l_dist, np.float32)).all()


# ---------------------------------------------------------------------------
# Host-level ragged-exchange emulation (no devices; pure index math)
# ---------------------------------------------------------------------------


def emulate_ragged_exchange(rng, mp, e_local, t, k, bound):
    """Run the full send→exchange→compact pipeline for mp fake ranks on the
    host and return, per rank, the compacted rows + group sizes it computes.

    (The multi-rank *oracle* for core/dispatch's cross-rank plan index math:
    payload rows are (source rank, original row) tags, so tests can verify
    segment structure without running any collective.)
    """
    from repro.core import dispatch as D

    E = mp * e_local
    sends, counts, rows = [], [], []
    for r in range(mp):
        ids = rng.integers(0, E, size=(t * k,))
        order = np.argsort(ids, kind="stable")
        gs = np.bincount(ids, minlength=E)
        xp = D.make_ragged_xplan(jnp.asarray(gs, jnp.int32), t * k, E, mp,
                                 bound)
        # payload rows are (rank, original row index) tags
        payload = np.stack([np.full(t * k, r), order], 1)
        buf = np.full((mp * bound, 2), -1)
        dest = np.asarray(xp.send_dest)
        ok = dest < mp * bound
        buf[dest[ok]] = payload[ok]
        sends.append(buf.reshape(mp, bound, 2))
        counts.append(np.asarray(xp.peer_counts))
        rows.append((ids, order, np.asarray(xp.keep)))
    outs = []
    for r in range(mp):  # the all-to-all: shard s of rank r's recv = rank
        recv = np.stack([sends[s][r] for s in range(mp)])  # s's shard r
        incoming = np.stack([counts[s][r] for s in range(mp)])
        cplan, gs_local = D.ragged_recv_compact(jnp.asarray(incoming,
                                                            jnp.int32), bound)
        compact = np.full((mp * bound, 2), -1)
        cp = np.asarray(cplan)
        ok = cp < mp * bound
        compact[cp[ok]] = recv.reshape(mp * bound, 2)[ok]
        outs.append((compact, np.asarray(gs_local), incoming))
    return rows, outs
