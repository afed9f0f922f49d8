"""Fused expert-FFN backward (ISSUE 3): the dX / grouped-dW Pallas kernels
wired into ``ops.fused_grouped_ffn``'s custom_vjp.

Acceptance: jax.grad through the fused op matches a per-expert einsum oracle
for all four activations, tail hidden tiles (H % bh != 0), variable ragged
group sizes (incl. empty groups) and bf16 inputs — with no two-pass
recompute: the whole fwd+bwd is three pallas_calls and materializes no
(M, H) intermediate.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MoEConfig
from repro.core import fmoe
from repro.kernels import ops

ACTS = [("swiglu", True), ("gelu", False), ("rwkv", False), ("silu", False)]


def _setup(E, K, H, N, gated, dtype=jnp.float32, seed=0, gs=None, total=96):
    rng = np.random.default_rng(seed)
    if gs is None:
        gs = rng.multinomial(total, np.ones(E) / E)
    gs = np.asarray(gs, np.int32)
    x = jnp.asarray(rng.normal(size=(int(gs.sum()), K)), dtype)
    ws = tuple(jnp.asarray(rng.normal(size=(E, K, H)) * 0.2, dtype)
               for _ in range(2 if gated else 1))
    wo = jnp.asarray(rng.normal(size=(E, H, N)) * 0.2, dtype)
    return x, ws, wo, gs


def _oracle(x, ws, wo, gs, act):
    """Per-expert dense einsum in f32 — the ground truth the kernels chase.

    ``gs`` is a concrete numpy array, so the group slices are static.
    """
    outs, o = [], 0
    for e, n in enumerate(gs):
        xe = x[o:o + int(n)].astype(jnp.float32)
        if act == "swiglu":
            h = jax.nn.silu(xe @ ws[0][e].astype(jnp.float32))
            h = h * (xe @ ws[1][e].astype(jnp.float32))
        else:
            h = fmoe._act(xe @ ws[0][e].astype(jnp.float32), act)
        outs.append(h @ wo[e].astype(jnp.float32))
        o += int(n)
    return jnp.concatenate(outs, axis=0)


def _grads(loss, x, ws, wo):
    return jax.tree.leaves(jax.grad(loss, argnums=(0, 1, 2))(x, ws, wo))


def _check_grads(x, ws, wo, gs, act, *, bm=8, bh=16, rtol=2e-4, atol=2e-4):
    gs_j = jnp.asarray(gs)

    def l_fused(x, ws, wo):
        return (ops.fused_grouped_ffn(x, ws, wo, gs_j, act, bm, bh) ** 2).sum()

    def l_ref(x, ws, wo):
        return (_oracle(x, ws, wo, gs, act) ** 2).sum()

    for a, b in zip(_grads(l_fused, x, ws, wo), _grads(l_ref, x, ws, wo)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=rtol, atol=atol)


@pytest.mark.parametrize("act,gated", ACTS)
def test_grad_matches_einsum_oracle(act, gated):
    x, ws, wo, gs = _setup(4, 16, 32, 24, gated, seed=1)
    _check_grads(x, ws, wo, gs, act)


@pytest.mark.parametrize("act,gated", ACTS)
def test_grad_tail_hidden_tile(act, gated):
    """H % bh != 0: the masked tail tile must not poison any of dX/dW."""
    x, ws, wo, gs = _setup(4, 16, 40, 24, gated, seed=2)  # 40 % 16 == 8
    _check_grads(x, ws, wo, gs, act)


def test_grad_ragged_group_sizes():
    """Variable sizes with empty groups: empty experts get exactly zero dW."""
    gs = np.asarray([0, 37, 0, 5, 22], np.int32)
    x, ws, wo, _ = _setup(5, 16, 32, 24, True, seed=3, gs=gs)
    _check_grads(x, ws, wo, gs, "swiglu")
    g = jax.grad(lambda ws: (ops.fused_grouped_ffn(
        x, ws, wo, jnp.asarray(gs), "swiglu", 8, 16) ** 2).sum())(ws)
    for dw in g:
        assert np.all(np.asarray(dw[0]) == 0) and np.all(np.asarray(dw[2]) == 0)


def test_grad_bf16_inputs_f32_acc():
    x, ws, wo, gs = _setup(3, 16, 32, 16, True, dtype=jnp.bfloat16, seed=4,
                           total=64)
    gs_j = jnp.asarray(gs)
    g = jax.grad(lambda x, ws, wo: (ops.fused_grouped_ffn(
        x, ws, wo, gs_j, "swiglu", 8, 16).astype(jnp.float32) ** 2).sum(),
        argnums=(0, 1, 2))(x, ws, wo)
    for a in jax.tree.leaves(g):
        assert a.dtype == jnp.bfloat16, a.dtype  # grads land at param dtype
    xf, wsf, wof = (x.astype(jnp.float32),
                    tuple(w.astype(jnp.float32) for w in ws),
                    wo.astype(jnp.float32))
    ref = _grads(lambda x, ws, wo: (_oracle(x, ws, wo, gs, "swiglu") ** 2).sum(),
                 xf, wsf, wof)
    for a, b in zip(jax.tree.leaves(g), ref):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b),
                                   rtol=1e-1, atol=1e-1)


def test_no_two_pass_recompute_in_backward():
    """fwd+bwd = exactly three pallas_calls (fwd, dX, dW) and no (M, H)
    intermediate — the two-pass fallback (5 grouped GEMMs + ragged_dots)
    is gone from the backward."""
    E, K, H, N = 4, 16, 40, 24
    x, ws, wo, gs = _setup(E, K, H, N, True, seed=5)
    M = x.shape[0]
    gs_j = jnp.asarray(gs)
    jaxpr = jax.make_jaxpr(jax.grad(lambda x, ws, wo: (ops.fused_grouped_ffn(
        x, ws, wo, gs_j, "swiglu", 8, 16) ** 2).sum(), argnums=(0, 1, 2)))(
        x, ws, wo)
    assert str(jaxpr).count("pallas_call") == 3
    assert "ragged_dot" not in str(jaxpr)
    hidden = {tuple(v.aval.shape) for eqn in jaxpr.jaxpr.eqns
              for v in eqn.outvars if hasattr(v.aval, "shape")
              and len(v.aval.shape) == 2 and v.aval.shape[1] == H
              and v.aval.shape[0] >= M}
    assert not hidden, hidden


def test_aligned_skips_pad_gather_round_trip():
    """Equal tile-aligned groups: same numbers, no (M, .) gather/scatter in
    the jaxpr (the pad_to_tiles/dest round-trip is skipped)."""
    E, n, K, H, N = 3, 16, 16, 32, 16  # n % bm == 0
    rng = np.random.default_rng(6)
    gs = jnp.full((E,), n, jnp.int32)
    x = jnp.asarray(rng.normal(size=(E * n, K)), jnp.float32)
    ws = tuple(jnp.asarray(rng.normal(size=(E, K, H)) * 0.2, jnp.float32)
               for _ in range(2))
    wo = jnp.asarray(rng.normal(size=(E, H, N)) * 0.2, jnp.float32)

    def loss(aligned):
        return lambda x, ws, wo: (ops.fused_grouped_ffn(
            x, ws, wo, gs, "swiglu", 8, 16, aligned) ** 2).sum()

    np.testing.assert_allclose(np.asarray(loss(True)(x, ws, wo)),
                               np.asarray(loss(False)(x, ws, wo)), rtol=1e-5)
    for a, b in zip(_grads(loss(True), x, ws, wo),
                    _grads(loss(False), x, ws, wo)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-5)
    txt = str(jax.make_jaxpr(jax.grad(loss(True), argnums=(0, 1, 2)))(x, ws, wo))
    assert "gather" not in txt and "scatter" not in txt
    # grouped_matmul honors the same flag
    ya = ops.grouped_matmul(x, ws[0], gs, "pallas", 8, True)
    yu = ops.grouped_matmul(x, ws[0], gs, "pallas", 8, False)
    np.testing.assert_allclose(np.asarray(ya), np.asarray(yu), rtol=1e-6)


@pytest.mark.parametrize("dispatch", ["ragged", "capacity"])
def test_fused_impl_grads_in_moe_layer(dispatch):
    """impl="fused" through fmoe_apply (ragged AND capacity dispatch):
    forward and parameter grads match the einsum expert_fn."""
    cfg = MoEConfig(num_experts=4, top_k=2, d_expert_hidden=48,
                    dispatch=dispatch)
    p = fmoe.fmoe_init(jax.random.PRNGKey(0), 32, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 32))

    def loss(impl):
        return lambda p: (fmoe.fmoe_apply(p, x, cfg, impl=impl)[0] ** 2).sum()

    y0, _ = fmoe.fmoe_apply(p, x, cfg, impl="einsum")
    y1, _ = fmoe.fmoe_apply(p, x, cfg, impl="fused")
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), rtol=2e-5,
                               atol=2e-5)
    g0 = jax.grad(loss("einsum"))(p)
    g1 = jax.grad(loss("fused"))(p)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-4)


def test_dw_hidden_tile_fitted_to_vmem(monkeypatch):
    """``fused_ffn_bwd._dw_block_h`` halves dW's hidden tile when the
    double-buffered working set would overflow scoped VMEM: at fastmoe-gpt
    width (d_model 1024) f32 weights need bh=256, bf16 keep bh=512.  The
    shrunk tile gives the same gradients (a zero budget forces the shrink
    at test size: bh 512 -> 128, four hidden tiles)."""
    from repro.kernels import fused_ffn_bwd as fb

    assert fb._dw_block_h(128, 1024, 1024, 512, 1, 4, 4) == 256
    assert fb._dw_block_h(128, 1024, 1024, 512, 1, 2, 2) == 512
    monkeypatch.setattr(fb, "_VMEM_BUDGET", 0)
    assert fb._dw_block_h(8, 16, 24, 512, 1, 4, 4) == 128
    x, ws, wo, gs = _setup(4, 16, 512, 24, False, seed=5)
    _check_grads(x, ws, wo, gs, "gelu", bh=512)
