"""Plain float32 reference of a decoder-only mixture-of-experts LM's training.

Written from the layer equations alone (no import from the program):

    x  = E[tokens]
    per layer:  x += Wo . attn(rope(Wq n1(x)), rope(Wk n1(x)), Wv n1(x))
                x += moe(n2(x))
    loss = CE(n_f(x) . W_head, next token) + (a_b . balance + a_z . z) / L

Attention is causal softmax attention with rotary positions (rotate-half,
theta ** (-2i / head_dim)).  The MoE layer routes each token to its top-k
experts (``softmax_topk``: top-k of the softmax; ``topk_softmax``: softmax
over the top-k logits; optional renormalisation), fills per-expert buffers
of capacity ``C = round_up_8(ceil(T k f / E))`` slot-major (every token's
first choice before any second choice, in token order) and drops what
overflows.  Routing groups: each microbatch's tokens, split into ``groups``
equal contiguous parts when the experts are spread over that many chips
(each chip routes its own tokens).  Experts are ``gelu_tanh(x Wi) Wo``.
balance = E sum_e f_e P_e (f: top-1 share, P: mean probability) and
z = mean(logsumexp(logits)^2), each averaged over groups and summed over
layers.  Training: the mean over microbatches of loss and gradients, then
AdamW (global-norm clipping, bias correction, decoupled weight decay on
every leaf) with linear warm-up and cosine decay to a tenth.

Every matmul runs in float32 at ``Precision.HIGHEST``.  ``quant`` replaces
that with a control: every matmul's operands (and, in the backward pass,
its cotangents) rounded to float8 e4m3 or to int8, each under a per-tensor
scale.

A reference module owns its model's description; the harness names no leaf,
attention kind or MoE option and takes these from it:

- ``param_shapes(conf)``: the tree of leaf shapes in the program's layout;
- ``layer_leaves(conf)``: the leaves whose axis 0 counts layers (their norms
  are read per layer);
- ``expert_leaves(conf)``: each leaf with an expert axis, and that axis, along
  which it is sharded when the reference runs on several chips;
- ``covers(cfg, conf, chips, dist)``: raises where the program's registered
  config, its defaults or its expert distribution lie outside what this
  reference computes;
- ``flops_per_token(conf, seq_len)``: model FLOPs per trained token;
- ``make_dot``, ``QUANT``, ``make_train_step`` and ``lr_scale``.
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops, model

HIGHEST = jax.lax.Precision.HIGHEST

# The keys a configuration file for this reference states: the sizes and
# options it reads, and the precision and tying the program runs with.
REQUIRED = ("num_layers", "d_model", "vocab_size", "norm", "act",
         "tie_embeddings", "dtype", "param_dtype", "num_heads",
         "num_kv_heads", "head_dim", "rope_theta", "num_experts", "top_k",
         "d_expert_hidden", "capacity_factor", "gate_policy", "renormalize",
         "balance_loss_weight", "z_loss_weight")


def param_shapes(conf: dict) -> dict:
    """Leaf shapes of the parameter tree, program layout."""
    L, d, V = conf["num_layers"], conf["d_model"], conf["vocab_size"]
    H, KV, hd = conf["num_heads"], conf["num_kv_heads"], conf["head_dim"]
    E, Hx = conf["num_experts"], conf["d_expert_hidden"]
    norm = {"scale": (L, d)}
    if conf["norm"] == "layernorm":
        norm["bias"] = (L, d)
    final = {k: v[1:] for k, v in norm.items()}
    return {
        "embed": {"table": (V, d)},
        "layers": {
            "norm1": dict(norm), "norm2": dict(norm),
            "attn": {"wq": {"w": (L, d, H * hd)}, "wk": {"w": (L, d, KV * hd)},
                     "wv": {"w": (L, d, KV * hd)}, "wo": {"w": (L, H * hd, d)}},
            "ffn": {"router": {"w": (L, d, E)},
                    "experts": {"wi": (L, E, d, Hx), "wo": (L, E, Hx, d)}},
        },
        "final_norm": final,
        "lm_head": {"w": (d, V)},
    }


def layer_leaves(conf: dict) -> list:
    """Leaves stacked over layers along axis 0: everything under ``layers``."""
    return [p for p in model.flatten(param_shapes(conf))
            if p.startswith("layers/")]


def expert_leaves(conf: dict) -> dict:
    """Leaves with an expert axis, and that axis: the experts' two
    matrices, stacked over layers, so axis 1."""
    return {"layers/ffn/experts/wi": 1, "layers/ffn/experts/wo": 1}


def covers(cfg, conf: dict, chips: int = 1, dist=None) -> None:
    """Raise where the program's config ``cfg``, its defaults or its expert
    distribution ``dist`` over ``chips`` chips lie outside this reference:
    a decoder-only GQA model with gelu top-k experts in every layer, routed
    in float32 with capacity dispatch, no shared or dense residual experts,
    and on several chips the expert all-to-all with tokens over every chip.
    """
    name = conf["name"]
    missing = [k for k in REQUIRED if k not in conf]
    if missing:
        raise ValueError(f"{name}: the configuration file does not state "
                         f"{missing}, which the moe_lm reference needs")
    attn = cfg.attention
    if (cfg.family != "moe" or attn is None or attn.kind != "gqa"
            or cfg.frontend != "none"):
        raise ValueError(f"{name}: the moe_lm reference covers "
                         f"decoder-only GQA MoE models only")
    if cfg.act != "gelu" or attn.qkv_bias or attn.sliding_window is not None:
        raise ValueError(f"{name}: the moe_lm reference has gelu experts and "
                         f"full attention without biases")
    moe = cfg.moe
    if moe.router != "topk" or moe.dispatch != "capacity":
        raise ValueError(f"the moe_lm reference covers top-k capacity "
                         f"routing; the program's default is router="
                         f"{moe.router!r} dispatch={moe.dispatch!r}")
    if moe.num_shared_experts or moe.dense_residual or moe.router_dtype != "float32":
        raise ValueError("the moe_lm reference has no shared or dense "
                         "residual experts and routes in float32")
    if chips > 1 and (dist is None or dist.mode != "a2a"
                      or len(dist.token_axes) != 2):
        raise ValueError(f"the moe_lm reference covers the all-to-all expert "
                         f"path with tokens over every chip; the program "
                         f"chose {dist}")


def flops_per_token(conf: dict, seq_len: int) -> int:
    """PaLM's count (``bench/flops.py``): attention projections, the top-k
    experts, the router and the head, and the attention scores."""
    return flops.flops_per_token(conf, seq_len)


def fp8_e4m3(a: jax.Array) -> jax.Array:
    """Round to float8 e4m3 under a per-tensor scale (amax -> 448)."""
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def int8_sym(a: jax.Array) -> jax.Array:
    """Round to symmetric int8 under a per-tensor scale (amax -> 127)."""
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 127.0
    return jnp.clip(jnp.round(a / scale), -127, 127) * scale


QUANT = {"fp8": fp8_e4m3, "int8": int8_sym}


def make_dot(quant: Optional[Callable] = None) -> Callable:
    """``dot(spec, a, b)``: an einsum in float32, or in the control's
    quantized arithmetic when ``quant`` is given."""
    def plain(spec, a, b):
        return jnp.einsum(spec, a, b, precision=HIGHEST,
                          preferred_element_type=jnp.float32)
    if quant is None:
        return plain

    @functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
    def dot(spec, a, b):
        return plain(spec, quant(a), quant(b))

    def fwd(spec, a, b):
        qa, qb = quant(a), quant(b)
        return plain(spec, qa, qb), (qa, qb)

    def bwd(spec, res, g):
        _, vjp = jax.vjp(functools.partial(plain, spec), *res)
        return vjp(quant(g))

    dot.defvjp(fwd, bwd)
    return dot


def capacity(tokens: int, experts: int, k: int, factor: float) -> int:
    c = math.ceil(tokens * k * factor / experts)
    return max(8, math.ceil(c / 8) * 8)


def _norm(p: dict, x: jax.Array, kind: str) -> jax.Array:
    eps = 1e-6
    if kind == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * p["scale"]


def _gelu(x: jax.Array) -> jax.Array:
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """x (B, S, H, D): rotate the halves (x1, x2) by position * freq_i."""
    S, D = x.shape[1], x.shape[-1]
    freq = 1.0 / theta ** (np.arange(0, D, 2, dtype=np.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freq  # (S, D/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(p: dict, x: jax.Array, conf: dict, dot) -> jax.Array:
    """Causal multi-head attention over x (B, S, d), a few rows at a time."""
    B, S, d = x.shape
    H, KV, D = conf["num_heads"], conf["num_kv_heads"], conf["head_dim"]
    rows = max(1, min(B, (64 << 20) // (H * S * S * 4)))
    while B % rows:
        rows -= 1

    @jax.checkpoint
    def block(xb):
        b = xb.shape[0]
        q = dot("bsd,dk->bsk", xb, p["wq"]["w"]).reshape(b, S, H, D)
        k = dot("bsd,dk->bsk", xb, p["wk"]["w"]).reshape(b, S, KV, D)
        v = dot("bsd,dk->bsk", xb, p["wv"]["w"]).reshape(b, S, KV, D)
        q, k = _rope(q, conf["rope_theta"]), _rope(k, conf["rope_theta"])
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
        s = dot("bqhd,bkhd->bhqk", q, k) * D ** -0.5
        causal = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(causal, s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        o = dot("bhqk,bkhd->bqhd", a, v).reshape(b, S, H * D)
        return dot("bsk,kd->bsd", o, p["wo"]["w"])

    out = jax.lax.map(block, x.reshape(B // rows, rows, S, d))
    return out.reshape(B, S, d)


def _route_group(p: dict, x: jax.Array, conf: dict, dot, constrain):
    """One routing group x (T, d) -> (y (T, d), balance, z)."""
    T, d = x.shape
    E, k = conf["num_experts"], conf["top_k"]
    logits = dot("td,de->te", x, p["router"]["w"])
    probs = jax.nn.softmax(logits, axis=-1)
    if conf["gate_policy"] == "softmax_topk":
        w, ids = jax.lax.top_k(probs, k)
    elif conf["gate_policy"] == "topk_softmax":
        top, ids = jax.lax.top_k(logits, k)
        w = jax.nn.softmax(top, axis=-1)
    else:
        raise ValueError(conf["gate_policy"])
    if conf["renormalize"]:
        w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    C = capacity(T, E, k, conf["capacity_factor"])
    # slot-major arrival order: all first choices, then all second choices
    order = ids.T.reshape(-1)  # (k T,)
    token = jnp.tile(jnp.arange(T), k)
    seen = jnp.cumsum(jax.nn.one_hot(order, E, dtype=jnp.int32), axis=0)
    slot = jnp.take_along_axis(seen, order[:, None], axis=1)[:, 0] - 1
    kept = slot < C
    table = jnp.full((E, C), T, jnp.int32).at[
        order, jnp.where(kept, slot, C)].set(token, mode="drop")
    xpad = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)])
    buf = constrain(xpad[table])  # (E, C, d); empty slots read zeros
    h = _gelu(dot("ecd,edh->ech", buf, p["experts"]["wi"]))
    out = constrain(dot("ech,ehd->ecd", h, p["experts"]["wo"]))
    got = out[order, jnp.minimum(slot, C - 1)]  # (k T, d)
    gate = (w.T.reshape(-1) * kept)[:, None]
    y = (got * gate).reshape(k, T, d).sum(0)
    f = jax.nn.one_hot(ids[:, 0], E).mean(0)
    balance = E * jnp.sum(f * probs.mean(0))
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return y, balance, z


def _moe(p: dict, x: jax.Array, conf: dict, groups: int, dot, constrain):
    T, d = x.shape
    y, bal, z = jax.vmap(
        lambda xg: _route_group(p, xg, conf, dot, constrain))(
            x.reshape(groups, T // groups, d))
    return y.reshape(T, d), bal.mean(), z.mean()


def _head_loss(x: jax.Array, w: jax.Array, targets: jax.Array, dot):
    """Mean next-token cross-entropy over (B, S-1) positions, a few
    sequences of logits at a time."""
    B, S1, d = x.shape
    V = w.shape[1]
    rows = max(1, min(B, (256 << 20) // (S1 * V * 4)))
    while B % rows:
        rows -= 1

    @jax.checkpoint
    def block(args):
        xb, tb = args
        logits = dot("bsd,dv->bsv", xb, w)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, tb[..., None], axis=-1)[..., 0]
        return jnp.sum(lse - tgt)

    n = B // rows
    total = jax.lax.map(block, (x.reshape(n, rows, S1, d),
                                targets.reshape(n, rows, S1))).sum()
    return total / (B * S1)


def loss_fn(params: dict, tokens: jax.Array, conf: dict, groups: int, dot,
            constrain=lambda a: a) -> jax.Array:
    """Loss of one microbatch ``tokens`` (B, S)."""
    L = conf["num_layers"]
    x = params["embed"]["table"][tokens]
    B, S, d = x.shape

    @jax.checkpoint
    def layer(p, x):
        x = x + _attention(p["attn"], _norm(p["norm1"], x, conf["norm"]),
                           conf, dot)
        y, bal, z = _moe(p["ffn"],
                         _norm(p["norm2"], x, conf["norm"]).reshape(-1, d),
                         conf, groups, dot, constrain)
        return x + y.reshape(B, S, d), bal, z

    aux = jnp.zeros(())
    for i in range(L):
        p = jax.tree.map(lambda a: a[i], params["layers"])
        x, bal, z = layer(p, x)
        aux = aux + conf["balance_loss_weight"] * bal + conf["z_loss_weight"] * z
    x = _norm(params["final_norm"], x, conf["norm"])
    ce = _head_loss(x[:, :-1], params["lm_head"]["w"], tokens[:, 1:], dot)
    return ce + aux / L


def lr_scale(step: int, warmup: int, total: int, floor: float = 0.1) -> float:
    """Linear warm-up then cosine decay to ``floor``; step 0 trains."""
    s = step + 1.0
    warm = min(s / max(warmup, 1), 1.0)
    frac = min(max((s - warmup) / max(total - warmup, 1), 0.0), 1.0)
    return warm * (floor + (1 - floor) * 0.5 * (1 + math.cos(math.pi * frac)))


def make_train_step(conf: dict, microbatches: int, groups: int, dot,
                    constrain=lambda a: a):
    """(params, mu, nu, batch (B, S), t, lr) -> (params, mu, nu, loss,
    clipped gradient).  ``t`` counts from 1."""
    o = conf["optimizer"]

    def step(params, mu, nu, batch, t, lr):
        micro = batch.reshape(microbatches, -1, batch.shape[-1])

        def body(acc, mb):
            loss, g = jax.value_and_grad(loss_fn)(params, mb, conf, groups,
                                                  dot, constrain)
            return jax.tree.map(jnp.add, acc, (loss, g)), None

        zero = (jnp.zeros(()), jax.tree.map(jnp.zeros_like, params))
        (loss, grads), _ = jax.lax.scan(body, zero, micro)
        loss = loss / microbatches
        grads = jax.tree.map(lambda g: g / microbatches, grads)
        gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
        clip = jnp.minimum(1.0, o["clip_norm"] / jnp.maximum(gnorm, 1e-9))
        grads = jax.tree.map(lambda g: g * clip, grads)
        b1, b2 = o["b1"], o["b2"]
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
        nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
        c1 = 1 - b1 ** t.astype(jnp.float32)
        c2 = 1 - b2 ** t.astype(jnp.float32)
        params = jax.tree.map(
            lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + o["eps"])
                                      + o["weight_decay"] * p),
            params, mu, nu)
        return params, mu, nu, loss, grads

    return step
