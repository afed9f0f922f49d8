"""DeepSeek-V2-Lite in the program: YaRN at the published numbers, MLA's
latent norm through prefill and both decode caches, the held share of an
expert layer, and the new fields left at their defaults changing nothing
for the other registered configs."""
import dataclasses
import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config, reduced
from repro.configs.base import MoEConfig
from repro.core import dispatch as D
from repro.core import fmoe
from repro.models import attention as A
from repro.models import lm
from repro.models.layers import rope_freqs

# sha256 (first 16 hex) of the sorted (path, shape, dtype) leaves of each
# registered config's ``init_params`` tree, taken before leading dense
# layers, held experts and YaRN existed.  deepseek-v2-236b's is taken after
# MLA gained its latent norm (``layers/attn/kv_norm/scale``), on purpose.
TREES = {
    "arctic-480b": "81aaf2518a686d7e",
    "deepseek-v2-236b": "74f06b15aad47b4c",
    "fastmoe-gpt": "ee16501ed06a433c",
    "fastmoe-gpt-dense": "3a9dec656ff1f4bb",
    "granite-3-2b": "8fb02f75d8645ab5",
    "hymba-1.5b": "ca4e21d24261f47c",
    "internvl2-76b": "4c1fa50c974d9fa2",
    "qwen2-72b": "8b86601743b9eff5",
    "rwkv6-7b": "139503d744a6576d",
    "smollm-360m": "d66b69d4251aa7e8",
    "starcoder2-15b": "2c12d929344da018",
    "switch-base-128": "906287913bdb2310",
    "whisper-tiny": "0f36fed5f0190a4f",
}


def _tree_digest(cfg) -> str:
    t = jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0), cfg))
    leaves = sorted((jax.tree_util.keystr(p), tuple(a.shape), str(a.dtype))
                    for p, a in jax.tree_util.tree_leaves_with_path(t))
    return hashlib.sha256(repr(leaves).encode()).hexdigest()[:16]


@pytest.mark.parametrize("name", sorted(set(ARCHS) - {"deepseek-v2-lite"}))
def test_existing_configs_keep_their_tree(name):
    assert _tree_digest(get_config(name)) == TREES[name]


def test_lite_tree_and_counts():
    cfg = get_config("deepseek-v2-lite")
    assert (cfg.num_layers, cfg.num_dense_layers, cfg.stack_layers) == (27, 1, 26)
    t = jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0), cfg))
    assert t["dense_layers"]["ffn"]["wi_gate"].shape == (1, 2048, 10944)
    assert t["layers"]["ffn"]["experts"]["wi_gate"].shape == (26, 64, 2048, 1408)
    assert t["layers"]["ffn"]["shared"]["wo"].shape == (26, 2816, 2048)
    assert t["layers"]["ffn"]["router"]["w"].shape == (26, 2048, 64)
    assert t["layers"]["attn"]["kv_norm"]["scale"].shape == (26, 512)
    assert "w_q" in t["layers"]["attn"] and "w_dq" not in t["layers"]["attn"]
    n = sum(math.prod(a.shape) for a in jax.tree.leaves(t))
    assert n == cfg.param_count()
    assert round(n / 1e9, 1) == 15.7  # DeepSeek-V2-Lite's published 15.7B
    held = dataclasses.replace(
        cfg, num_layers=6, vocab_size=12800,
        moe=dataclasses.replace(cfg.moe, experts_held=8))
    t = jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0), held))
    assert t["layers"]["ffn"]["experts"]["wo"].shape == (5, 8, 1408, 2048)
    assert t["layers"]["ffn"]["router"]["w"].shape == (5, 2048, 64)
    # 635 M: the chip's share the benchmark cell trains
    assert round(sum(math.prod(a.shape) for a in jax.tree.leaves(t)) / 1e6) == 635


def test_yarn_at_published_numbers():
    """rope_theta 1e4 over the 64-wide rope dims, factor 40, original 4096,
    beta 32/1: dims 0-9 keep their frequency, 23-31 are divided by 40, a
    linear ramp joins them; the softmax scale gains (0.1*0.707*ln 40 + 1)^2
    and cos/sin keep scale 1 (mscale == mscale_all_dim)."""
    a = get_config("deepseek-v2-lite").attention
    factor, original, fast, slow, cos_scale = a.yarn
    assert (factor, original, fast, slow, cos_scale) == (40.0, 4096, 32.0,
                                                         1.0, 1.0)
    base = 1e4 ** (-np.arange(0, 64, 2) / 64)
    f = np.asarray(rope_freqs(64, 1e4, a.yarn), np.float64)
    np.testing.assert_allclose(f[:11], base[:11], rtol=1e-6)
    np.testing.assert_allclose(f[23:], base[23:] / 40, rtol=1e-6)
    ramp = (np.arange(32) - 10) / 13
    mid = base * (1 - ramp) + base / 40 * ramp
    np.testing.assert_allclose(f[10:24], mid[10:24], rtol=1e-6)
    assert a.softmax_scale_factor == pytest.approx(1.5897, abs=1e-4)
    assert a.softmax_scale_factor == pytest.approx(
        (0.1 * 0.707 * math.log(40) + 1) ** 2, rel=1e-12)
    plain = get_config("deepseek-v2-236b").attention
    assert plain.yarn is None and plain.softmax_scale_factor == 1.0
    np.testing.assert_array_equal(np.asarray(rope_freqs(64, 1e4, None)),
                                  np.asarray(rope_freqs(64, 1e4)))


def _lite_serve_cfg():
    """A small DeepSeek-V2-Lite with YaRN and the latent norm, without the
    leading dense layer (serving covers one layer stack), capacity that
    drops nothing so a token's routing is the same alone or in a batch."""
    cfg = reduced(get_config("deepseek-v2-lite"), num_layers=2, d_model=64)
    return dataclasses.replace(
        cfg, num_dense_layers=0,
        attention=dataclasses.replace(cfg.attention,
                                      yarn_original_max_position=8),
        moe=dataclasses.replace(cfg.moe, capacity_factor=16.0))


def _with_latent_scale(params, key):
    """A latent norm scale far from 1, so a path that skips it shows."""
    a = params["layers"]["attn"]
    scale = 1.0 + jax.random.uniform(key, a["kv_norm"]["scale"].shape)
    return {**params, "layers": {**params["layers"], "attn": {
        **a, "kv_norm": {"scale": scale}}}}


@pytest.mark.parametrize("paged", [False, True], ids=["ring", "paged"])
def test_mla_prefill_then_decode_matches_forward(paged):
    """Prefill 6 tokens, decode 4 more through the ring or the paged latent
    cache: every position's logits equal the full forward pass's.  float32
    end to end; the tolerance covers the absorbed decode form's other
    summation order."""
    cfg = _lite_serve_cfg()
    assert cfg.attention.yarn is not None and cfg.attention.kind == "mla"
    params = _with_latent_scale(lm.init_params(jax.random.PRNGKey(0), cfg),
                                jax.random.PRNGKey(5))
    B, S, P = 2, 10, 6
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size)
    full, _ = lm.forward(params, cfg, toks)
    kw = {}
    if paged:
        bs, nb = 4, 4
        cache = lm.init_paged_cache(cfg, A.RESERVED_BLOCKS + B * nb, bs)
        kw["block_tables"] = jnp.asarray(
            A.RESERVED_BLOCKS + np.arange(B * nb).reshape(B, nb), jnp.int32)
        for t in range(P):  # the paged pool fills token by token
            logits, cache, _ = lm.decode_step(params, cfg, toks[:, t:t + 1],
                                              jnp.int32(t), cache, **kw)
            np.testing.assert_allclose(np.asarray(logits[:, 0]),
                                       np.asarray(full[:, t]), atol=2e-4)
    else:
        cache = lm.init_cache(cfg, B, 16)
        logits, cache, _ = lm.prefill(params, cfg, toks[:, :P], cache)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(full[:, :P]), atol=2e-4)
    for t in range(P, S):
        logits, cache, _ = lm.decode_step(params, cfg, toks[:, t:t + 1],
                                          jnp.int32(t), cache, **kw)
        np.testing.assert_allclose(np.asarray(logits[:, 0]),
                                   np.asarray(full[:, t]), atol=2e-4)


def test_latent_norm_is_applied():
    """The forward pass reads the latent norm's scale: changing it changes
    the logits (a path that skipped the norm would not notice)."""
    cfg = _lite_serve_cfg()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, cfg.vocab_size)
    a, _ = lm.forward(params, cfg, toks)
    b, _ = lm.forward(_with_latent_scale(params, jax.random.PRNGKey(5)), cfg,
                      toks)
    assert float(jnp.abs(a - b).max()) > 1e-3


def test_serving_refuses_leading_dense_layers():
    cfg = reduced(get_config("deepseek-v2-lite"), num_layers=2, d_model=64)
    assert cfg.num_dense_layers == 1
    with pytest.raises(NotImplementedError, match="dense layer"):
        lm.init_cache(cfg, 1, 8)
    with pytest.raises(NotImplementedError, match="dense layer"):
        lm.init_paged_cache(cfg, 4, 4)


def _layer(E=16, held=0, k=6, cf=1.0):
    cfg = MoEConfig(num_experts=E, top_k=k, d_expert_hidden=24,
                    num_shared_experts=2, capacity_factor=cf,
                    renormalize=False, experts_held=held)
    params = fmoe.fmoe_init(jax.random.PRNGKey(0),
                            32, dataclasses.replace(cfg, experts_held=0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 48, 32))
    return cfg, params, x


def _share(params, lo, n):
    """Chip ``lo // n``'s share: its experts moved to the front (the router
    columns with them), as that chip's layer holds them."""
    E = params["router"]["w"].shape[-1]
    perm = np.r_[lo:E, 0:lo]
    return {"router": {"w": params["router"]["w"][:, perm]},
            "experts": {k: v[perm[:n]] for k, v in params["experts"].items()},
            "shared": params["shared"]}


def test_held_shares_add_up_to_the_uncut_layer():
    """Four chips each holding 4 of 16 experts: their outputs, with the
    shared experts counted once, sum to the uncut layer's, which drops
    tokens (capacity factor 0.5), so the shares keep the uncut slots; the
    shares' held fractions sum to 1 and each share's balance loss is the
    uncut one."""
    cfg, params, x = _layer(cf=0.5)
    y, m = fmoe.fmoe_apply(params, x, cfg)
    assert float(m.drop_frac) > 0.05  # premise: the uncut layer drops
    shared = fmoe.dense_ffn(params["shared"], x, "swiglu")
    held = dataclasses.replace(cfg, experts_held=4)
    total, fracs, dropped = shared, [], 0.0
    for lo in range(0, 16, 4):
        ys, ms = fmoe.fmoe_apply(_share(params, lo, 4), x, held)
        total = total + ys - shared
        fracs.append(float(ms.held_frac))
        dropped += float(ms.drop_frac) * float(ms.held_frac)
        np.testing.assert_allclose(float(ms.aux_loss), float(m.aux_loss),
                                   rtol=1e-5)
    np.testing.assert_allclose(np.asarray(total), np.asarray(y), atol=1e-5)
    assert sum(fracs) == pytest.approx(1.0, abs=1e-6)
    assert dropped == pytest.approx(float(m.drop_frac), abs=1e-5)


def test_default_fields_trace_no_held_selection():
    """At the defaults (experts_held 0: all) the layer traces what an
    explicit experts_held == num_experts traces, no held-expert masking; a
    held share adds the masking and holds fewer experts' weights."""
    cfg, params, x = _layer()

    def jaxpr(c, p):
        return jax.make_jaxpr(lambda p, x: fmoe.fmoe_apply(p, x, c))(p, x)

    default = jaxpr(cfg, params)
    assert cfg.experts_held == 0 and cfg.held == 16
    explicit = jaxpr(dataclasses.replace(cfg, experts_held=16), params)
    assert str(default) == str(explicit)
    share = jaxpr(dataclasses.replace(cfg, experts_held=4),
                  _share(params, 0, 4))
    assert len(share.eqns) > len(default.eqns)
    C = D.expert_capacity(96, 16, 6, 1.0)  # the same in both
    assert f"f32[16,{C},32]" in str(default)  # (E, C, d): every expert
    assert f"f32[16,{C},32]" not in str(share)
    assert f"f32[4,{C},32]" in str(share)  # the held experts' buffer


def test_distributed_paths_refuse_a_held_share():
    cfg, params, x = _layer(held=4)
    dist = fmoe.DistConfig(object(), ("data",))
    with pytest.raises(NotImplementedError, match="experts_held"):
        fmoe.fmoe_apply(_share(params, 0, 4), x, cfg, dist=dist)
    ragged = dataclasses.replace(cfg, dispatch="ragged")
    with pytest.raises(NotImplementedError, match="holding 4 of 16"):
        fmoe.fmoe_apply(_share(params, 0, 4), x, ragged)


def test_loss_averages_moe_terms_over_moe_layers():
    """loss = ce + (a_b balance + a_z z) / L_moe, and held_frac and
    drop_frac are per-MoE-layer averages: the dense layer does not count."""
    cfg = reduced(get_config("deepseek-v2-lite"), num_layers=3, d_model=64)
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, z_loss_weight=0.01))
    assert (cfg.num_dense_layers, cfg.stack_layers) == (1, 2)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                              cfg.vocab_size)
    loss, aux = lm.loss_fn(params, cfg, {"tokens": toks})
    want = aux["ce"] + (cfg.moe.balance_loss_weight * aux["aux_loss"]
                        + 0.01 * aux["z_loss"]) / 2
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-6)
    assert float(aux["held_frac"]) == pytest.approx(1.0)
    assert aux["load_layers"].shape == (2, cfg.moe.num_experts)


@pytest.mark.parametrize("recompute", [False, True])
def test_chunk_recompute_keeps_gradients(recompute):
    """``recompute`` (the model's ``remat == "full"``) checkpoints each KV
    chunk of the attention scan: the gradient is the plain scan's, and only
    the recomputing scan's backward holds a checkpoint."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (1, 32, 4, 8))
    k = jax.random.normal(ks[1], (1, 32, 2, 8))
    v = jax.random.normal(ks[2], (1, 32, 2, 8))

    def loss(q, k, v, rc):
        y = A.blockwise_attention(q, k, v, window=1 << 30, chunk=8,
                                  recompute=rc)
        return (y ** 2).sum()

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v, recompute)
    g0 = jax.grad(loss, argnums=(0, 1, 2))(q, k, v, False)
    for a, b in zip(g, g0):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
    jaxpr = str(jax.make_jaxpr(jax.grad(loss), static_argnums=3)(
        q, k, v, recompute))
    assert ("checkpoint" in jaxpr or "remat" in jaxpr) == recompute
