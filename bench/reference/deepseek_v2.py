"""Plain float32 reference of DeepSeek-V2's training at one chip's share of
its expert-parallel layers.

Written from the layer equations alone (no import from the program):

    x = E[tokens]
    leading dense layers:  x += MLA(n1(x));  x += W_o (silu(W_g n2(x)) * W_u n2(x))
    MoE layers:            x += MLA(n1(x));  x += moe(n2(x))
    loss = CE(n_f(x) . W_head, next token) + (a_b . balance + a_z . z) / L_moe

Norms are RMSNorm (eps 1e-6).  MLA, per head h, with no query compression:

    q = W_q x = [q_nope, q_rope];  c = n_kv(W_dkv x)  (the latent, RMS-normed)
    k = [W_uk,h c, rope(W_kr x)];  v = W_uv,h c;  rope(q_rope) and the one
    rope key shared by every head; out = W_o concat_h softmax(s q.k) v

with causal masking and softmax scale s = (d_nope + d_rope)^-1/2 . m^2,
m = 0.1 . mscale_all_dim . ln(factor) + 1 (YaRN's temperature).  Rope
rotates the halves (x1, x2) by position . f_i, where YaRN blends each
inverse frequency f_i = theta^(-2i/D) with f_i / factor along a linear ramp
between the dims that turn beta_fast and beta_slow times over the original
context (dims that turn faster keep f_i), and scales cos and sin by
m(mscale) / m(mscale_all_dim).

The MoE layer routes each token over all E experts (softmax, greedy top-k,
no renormalisation, routed scaling 1), fills per-expert buffers of capacity
``C = round_up_8(ceil(T k f / E))`` slot-major (every token's first choice
before any second choice, in token order) and drops what overflows, as the
uncut layer does; this chip then computes only experts 0 .. held-1 and
their part of the output, and adds the shared experts (one SwiGLU of width
num_shared . d_expert) for every token.  What the other chips' experts would
add is left out, as in the program.  balance = E sum_e f_e P_e (f: top-1
share, P: mean probability over all E) and z = mean(logsumexp(logits)^2),
summed over MoE layers.  Training: AdamW (global-norm clipping, bias
correction, decoupled weight decay on every leaf) with linear warm-up and
cosine decay to a tenth; microbatches averaged.

Departures from DeepSeek-V2, each shared with the program:

- the balance loss is the repo's batch-level Switch form, where DeepSeek-V2
  uses a sequence-wise top-K form (``seq_aux``) with its own weights;
- capacity factor 1.25 with drops, where DeepSeek-V2 drops by device
  capacity at training time and never at inference;
- rope rotates halves, which equals DeepSeek's interleaved layout up to a
  fixed permutation of the (random) rope weights' columns.

Every matmul runs in float32 at ``Precision.HIGHEST``, attention a block of
query rows at a time so that two 4,096-token sequences fit.  ``quant``
replaces that with the control, as in ``moe_lm``.  The module contract is
``moe_lm``'s (``param_shapes``, ``layer_leaves``, ``expert_leaves``,
``covers``, ``flops_per_token``, ``make_dot``, ``QUANT``,
``make_train_step``, ``lr_scale``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import model
from bench.reference.moe_lm import QUANT, capacity, lr_scale, make_dot  # noqa: F401

# The keys a configuration file for this reference states.
REQUIRED = ("num_layers", "num_dense_layers", "d_model", "d_ff", "vocab_size",
            "norm", "act", "tie_embeddings", "dtype", "param_dtype", "kind",
            "num_heads", "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "rope_theta", "yarn_factor",
            "yarn_original_max_position", "yarn_beta_fast", "yarn_beta_slow",
            "yarn_mscale", "yarn_mscale_all_dim", "num_experts",
            "experts_held", "top_k", "d_expert_hidden", "num_shared_experts",
            "capacity_factor", "router", "gate_policy", "renormalize",
            "router_dtype", "dispatch", "balance_loss_weight",
            "z_loss_weight")
EPS = 1e-6


def _attn_shapes(conf: dict, L: int) -> dict:
    d, H, r = conf["d_model"], conf["num_heads"], conf["kv_lora_rank"]
    nope, rope, vd = (conf["qk_nope_head_dim"], conf["qk_rope_head_dim"],
                      conf["v_head_dim"])
    return {"w_q": {"w": (L, d, H * (nope + rope))},
            "w_dkv": {"w": (L, d, r)}, "kv_norm": {"scale": (L, r)},
            "w_kr": {"w": (L, d, rope)}, "w_uk": (L, H, r, nope),
            "w_uv": (L, H, r, vd), "wo": {"w": (L, H * vd, d)}}


def _swiglu_shapes(d: int, h: int, lead: tuple) -> dict:
    return {"wi_gate": lead + (d, h), "wi_up": lead + (d, h),
            "wo": lead + (h, d)}


def param_shapes(conf: dict) -> dict:
    """Leaf shapes of the parameter tree, program layout: ``dense_layers``
    and the MoE ``layers``, each stacked over its layers on axis 0."""
    d, V = conf["d_model"], conf["vocab_size"]
    nd = conf["num_dense_layers"]
    L = conf["num_layers"] - nd
    hx = conf["d_expert_hidden"]
    tree = {
        "embed": {"table": (V, d)},
        "layers": {
            "norm1": {"scale": (L, d)}, "norm2": {"scale": (L, d)},
            "attn": _attn_shapes(conf, L),
            "ffn": {"router": {"w": (L, d, conf["num_experts"])},
                    "experts": _swiglu_shapes(d, hx, (L, conf["experts_held"])),
                    "shared": _swiglu_shapes(
                        d, conf["num_shared_experts"] * hx, (L,))},
        },
        "final_norm": {"scale": (d,)},
        "lm_head": {"w": (d, V)},
    }
    if nd:
        tree["dense_layers"] = {
            "norm1": {"scale": (nd, d)}, "norm2": {"scale": (nd, d)},
            "attn": _attn_shapes(conf, nd),
            "ffn": _swiglu_shapes(d, conf["d_ff"], (nd,))}
    return tree


def layer_leaves(conf: dict) -> list:
    """Leaves stacked over layers along axis 0: everything under ``layers``
    and ``dense_layers``."""
    return [p for p in model.flatten(param_shapes(conf))
            if p.startswith(("layers/", "dense_layers/"))]


def expert_leaves(conf: dict) -> dict:
    """The held experts' matrices, stacked over layers, so axis 1."""
    return {f"layers/ffn/experts/{k}": 1 for k in ("wi_gate", "wi_up", "wo")}


def covers(cfg, conf: dict, chips: int = 1, dist=None) -> None:
    """Raise where the program's config ``cfg`` or its defaults lie outside
    this reference: DeepSeek-V2's MLA (no query compression) with the latent
    norm and YaRN, leading dense SwiGLU layers, then SwiGLU MoE layers with
    shared experts, softmax top-k routing without renormalisation in float32,
    capacity dispatch, one chip holding experts 0 .. held-1 of every layer."""
    name = conf["name"]
    missing = [k for k in REQUIRED if k not in conf]
    if missing:
        raise ValueError(f"{name}: the configuration file does not state "
                         f"{missing}, which the deepseek_v2 reference needs")
    if chips != 1 or dist is not None:
        raise ValueError(f"{name}: the deepseek_v2 reference runs one chip's "
                         f"share of a layer, without its exchange; the cell "
                         f"asks for {chips} chips")
    attn, moe = cfg.attention, cfg.moe
    if (cfg.family != "moe" or attn is None or attn.kind != "mla"
            or attn.q_lora_rank or attn.sliding_window is not None
            or cfg.frontend != "none"):
        raise ValueError(f"{name}: the deepseek_v2 reference covers "
                         f"decoder-only MLA models without query compression")
    if cfg.act != "swiglu" or cfg.norm != "rmsnorm" or cfg.tie_embeddings:
        raise ValueError(f"{name}: the deepseek_v2 reference has SwiGLU "
                         f"FFNs, RMSNorm and an untied head")
    if moe is None or moe.router != "topk" or moe.dispatch != "capacity":
        raise ValueError(f"{name}: the deepseek_v2 reference covers top-k "
                         f"capacity routing")
    if (moe.gate_policy != "softmax_topk" or moe.renormalize
            or moe.router_dtype != "float32" or moe.dense_residual):
        raise ValueError(f"{name}: the deepseek_v2 reference gates by the "
                         f"softmax over all experts, unrenormalised, in "
                         f"float32, with no dense residual")
    if not 0 < moe.held <= moe.num_experts or moe.held != conf["experts_held"]:
        raise ValueError(f"{name}: the program holds {moe.held} of "
                         f"{moe.num_experts} experts, the configuration file "
                         f"states {conf['experts_held']}")


def flops_per_token(conf: dict, seq_len: int) -> int:
    """PaLM's count (arXiv:2204.02311 App. B) of what this chip computes,
    6 N + 6 L H (d_qk + d_v) T: N counts the MLA projections, the dense
    layers' FFN, the router over all experts, the shared experts, the
    ``top_k . held / num_experts`` routed expert FFNs a token has here and
    the head; the scores are a (d_nope + d_rope)-wide product and a
    d_v-wide one."""
    d, H, r = conf["d_model"], conf["num_heads"], conf["kv_lora_rank"]
    nope, rope, vd = (conf["qk_nope_head_dim"], conf["qk_rope_head_dim"],
                      conf["v_head_dim"])
    L, nd = conf["num_layers"], conf["num_dense_layers"]
    attn = (d * H * (nope + rope) + d * r + d * rope + r * H * (nope + vd)
            + H * vd * d)
    swiglu = 3 * d
    moe = (d * conf["num_experts"]
           + swiglu * conf["num_shared_experts"] * conf["d_expert_hidden"]
           + swiglu * conf["d_expert_hidden"] * conf["top_k"]
           * conf["experts_held"] // conf["num_experts"])
    n = (L * attn + nd * swiglu * conf["d_ff"] + (L - nd) * moe
         + d * conf["vocab_size"])
    return 6 * n + 6 * L * H * (nope + rope + vd) * seq_len


# -- the model --


def _rms(scale: jax.Array, x: jax.Array) -> jax.Array:
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + EPS) * scale


def _silu(x: jax.Array) -> jax.Array:
    return x / (1.0 + jnp.exp(-x))


def _temperature(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def rope_inv_freq(conf: dict) -> np.ndarray:
    """(d_rope/2,) YaRN-blended inverse frequencies, float64."""
    D, theta = conf["qk_rope_head_dim"], float(conf["rope_theta"])
    base = theta ** (-np.arange(0, D, 2, dtype=np.float64) / D)
    factor = conf["yarn_factor"]
    if factor <= 1.0:
        return base
    original = conf["yarn_original_max_position"]

    def dim(turns):  # the dim whose wave turns ``turns`` times over original
        return D * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim(conf["yarn_beta_fast"])), 0)
    high = min(math.ceil(dim(conf["yarn_beta_slow"])), D - 1)
    ramp = np.clip((np.arange(D // 2) - low) / max(high - low, 1e-3), 0, 1)
    return base * (1 - ramp) + base / factor * ramp


def softmax_scale(conf: dict) -> float:
    s = (conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"]) ** -0.5
    if conf["yarn_mscale_all_dim"]:
        s *= _temperature(conf["yarn_factor"], conf["yarn_mscale_all_dim"]) ** 2
    return s


def _rope(x: jax.Array, conf: dict) -> jax.Array:
    """x (B, S, h, D): rotate the halves (x1, x2) by position . f_i."""
    S, D = x.shape[1], x.shape[-1]
    f = conf["yarn_factor"]
    m = (_temperature(f, conf["yarn_mscale"])
         / _temperature(f, conf["yarn_mscale_all_dim"]))
    ang = np.arange(S, dtype=np.float64)[:, None] * rope_inv_freq(conf)
    cos = jnp.asarray(np.cos(ang) * m, jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang) * m, jnp.float32)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _mla(p: dict, x: jax.Array, conf: dict, dot) -> jax.Array:
    """Causal MLA over x (B, S, d), a block of query rows at a time."""
    B, S, d = x.shape
    H = conf["num_heads"]
    nope, rope = conf["qk_nope_head_dim"], conf["qk_rope_head_dim"]
    q = dot("bsd,dk->bsk", x, p["w_q"]["w"]).reshape(B, S, H, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], conf)], -1)
    c = _rms(p["kv_norm"]["scale"], dot("bsd,dr->bsr", x, p["w_dkv"]["w"]))
    kr = _rope(dot("bsd,dk->bsk", x, p["w_kr"]["w"])[:, :, None, :], conf)
    k = jnp.concatenate([dot("bsr,hrd->bshd", c, p["w_uk"]),
                         jnp.broadcast_to(kr, (B, S, H, rope))], -1)
    v = dot("bsr,hrd->bshd", c, p["w_uv"])
    scale = softmax_scale(conf)
    rows = max(1, min(S, (64 << 20) // (B * H * S * 4)))
    while S % rows:
        rows -= 1

    @jax.checkpoint
    def block(args):
        qb, i0 = args  # (B, rows, H, D), first query position
        s = dot("bqhd,bkhd->bhqk", qb, k) * scale
        causal = (i0 + jnp.arange(rows))[:, None] >= jnp.arange(S)[None, :]
        a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return dot("bhqk,bkhd->bqhd", a, v)

    n = S // rows
    out = jax.lax.map(block, (q.reshape(B, n, rows, H, nope + rope)
                              .swapaxes(0, 1), jnp.arange(n) * rows))
    out = out.swapaxes(0, 1).reshape(B, S, H * conf["v_head_dim"])
    return dot("bsk,kd->bsd", out, p["wo"]["w"])


def _swiglu(p: dict, x: jax.Array, dot, spec: str = "td,dh->th",
            out: str = "th,hd->td") -> jax.Array:
    h = _silu(dot(spec, x, p["wi_gate"])) * dot(spec, x, p["wi_up"])
    return dot(out, h, p["wo"])


def _moe(p: dict, x: jax.Array, conf: dict, dot):
    """x (T, d), every token one routing group -> (y (T, d), balance, z)."""
    T, d = x.shape
    E, k, held = conf["num_experts"], conf["top_k"], conf["experts_held"]
    logits = dot("td,de->te", x, p["router"]["w"])
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = jax.lax.top_k(probs, k)  # no renormalisation
    C = capacity(T, E, k, conf["capacity_factor"])
    # slot-major arrival order over all E experts, as in the uncut layer
    order = ids.T.reshape(-1)  # (k T,)
    token = jnp.tile(jnp.arange(T), k)
    seen = jnp.cumsum(jax.nn.one_hot(order, E, dtype=jnp.int32), axis=0)
    slot = jnp.take_along_axis(seen, order[:, None], axis=1)[:, 0] - 1
    kept = (slot < C) & (order < held)  # this chip's experts only
    table = jnp.full((held, C), T, jnp.int32).at[
        jnp.where(kept, order, held), jnp.where(kept, slot, C)].set(
            token, mode="drop")
    xpad = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)])
    out = _swiglu(p["experts"], xpad[table], dot, "ecd,edh->ech",
                  "ech,ehd->ecd")  # (held, C, d); empty slots read zeros
    got = out[jnp.minimum(order, held - 1), jnp.minimum(slot, C - 1)]
    gate = (w.T.reshape(-1) * kept)[:, None]
    y = (got * gate).reshape(k, T, d).sum(0) + _swiglu(p["shared"], x, dot)
    f = jax.nn.one_hot(ids[:, 0], E).mean(0)
    balance = E * jnp.sum(f * probs.mean(0))
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return y, balance, z


def _head_loss(x: jax.Array, w: jax.Array, targets: jax.Array, dot):
    """Mean next-token cross-entropy over (B, S-1), a sequence at a time."""
    B, S1, d = x.shape

    @jax.checkpoint
    def block(args):
        xb, tb = args
        logits = dot("sd,dv->sv", xb, w)
        tgt = jnp.take_along_axis(logits, tb[:, None], axis=-1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - tgt)

    return jax.lax.map(block, (x, targets)).sum() / (B * S1)


def loss_fn(params: dict, tokens: jax.Array, conf: dict, dot) -> jax.Array:
    """Loss of one microbatch ``tokens`` (B, S)."""
    nd = conf["num_dense_layers"]
    L = conf["num_layers"] - nd
    x = params["embed"]["table"][tokens]
    B, S, d = x.shape

    @jax.checkpoint
    def dense(p, x):
        x = x + _mla(p["attn"], _rms(p["norm1"]["scale"], x), conf, dot)
        h = _rms(p["norm2"]["scale"], x).reshape(-1, d)
        return x + _swiglu(p["ffn"], h, dot).reshape(B, S, d)

    @jax.checkpoint
    def moe(p, x):
        x = x + _mla(p["attn"], _rms(p["norm1"]["scale"], x), conf, dot)
        y, bal, z = _moe(p["ffn"], _rms(p["norm2"]["scale"], x).reshape(-1, d),
                         conf, dot)
        return x + y.reshape(B, S, d), bal, z

    for i in range(nd):
        x = dense(jax.tree.map(lambda a: a[i], params["dense_layers"]), x)
    aux = jnp.zeros(())
    for i in range(L):
        x, bal, z = moe(jax.tree.map(lambda a: a[i], params["layers"]), x)
        aux = aux + conf["balance_loss_weight"] * bal + conf["z_loss_weight"] * z
    x = _rms(params["final_norm"]["scale"], x)
    ce = _head_loss(x[:, :-1], params["lm_head"]["w"], tokens[:, 1:], dot)
    return ce + aux / L


def make_train_step(conf: dict, microbatches: int, groups: int, dot,
                    constrain=lambda a: a):
    """(params, mu, nu, batch (B, S), t, lr) -> (params, mu, nu, loss,
    clipped gradient).  ``t`` counts from 1.  One chip routes every token of
    a microbatch as one group, so ``groups`` is 1 and ``constrain`` unused."""
    if groups != 1:
        raise ValueError("the deepseek_v2 reference routes on one chip")
    o = conf["optimizer"]

    def step(params, mu, nu, batch, t, lr):
        micro = batch.reshape(microbatches, -1, batch.shape[-1])

        def body(acc, mb):
            loss, g = jax.value_and_grad(loss_fn)(params, mb, conf, dot)
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
