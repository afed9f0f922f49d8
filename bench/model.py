"""A configuration file -> the program's ModelConfig, and the cell's weights.

The weights are the benchmark's own: made from the seed on the device in one
jitted call, laid out as the program's parameter tree (checked against the
program's own ``init_params`` shapes), so the plain reference starts from the
same numbers without taking anything the program made.
"""
from __future__ import annotations

import dataclasses

import numpy as np

# file key -> (ModelConfig group, field); None = a top-level field
PROGRAM_KEYS = {
    "num_layers": (None, "num_layers"),
    "d_model": (None, "d_model"),
    "vocab_size": (None, "vocab_size"),
    "norm": (None, "norm"),
    "act": (None, "act"),
    "tie_embeddings": (None, "tie_embeddings"),
    "dtype": (None, "dtype"),
    "param_dtype": (None, "param_dtype"),
    "num_heads": ("attention", "num_heads"),
    "num_kv_heads": ("attention", "num_kv_heads"),
    "head_dim": ("attention", "head_dim"),
    "rope_theta": ("attention", "rope_theta"),
    "num_experts": ("moe", "num_experts"),
    "top_k": ("moe", "top_k"),
    "d_expert_hidden": ("moe", "d_expert_hidden"),
    "capacity_factor": ("moe", "capacity_factor"),
    "gate_policy": ("moe", "gate_policy"),
    "renormalize": ("moe", "renormalize"),
    "balance_loss_weight": ("moe", "balance_loss_weight"),
    "z_loss_weight": ("moe", "z_loss_weight"),
}


def program_config(conf: dict):
    """The program's registered config with the file's ``reduced`` keys
    applied; every other key the file states must already agree."""
    from repro.configs import get_config

    cfg = get_config(conf["program_arch"])
    groups: dict = {}
    top: dict = {}
    for key, (group, field) in PROGRAM_KEYS.items():
        if key in conf["reduced"]:
            (groups.setdefault(group, {}) if group else top)[field] = conf[key]
    for group, fields in groups.items():
        top[group] = dataclasses.replace(getattr(cfg, group), **fields)
    cfg = dataclasses.replace(cfg, **top)
    for key, (group, field) in PROGRAM_KEYS.items():
        have = getattr(getattr(cfg, group) if group else cfg, field)
        if have != conf[key]:
            raise ValueError(f"{conf['name']}: the program's "
                             f"{conf['program_arch']} has {key}={have!r}, "
                             f"the configuration file states {conf[key]!r}")
    if cfg.family != "moe" or cfg.attention.kind != "gqa" or cfg.frontend != "none":
        raise ValueError(f"{conf['name']}: the moe_lm reference covers "
                         f"decoder-only GQA MoE models only")
    return cfg


def check_program_defaults(cfg, conf: dict) -> None:
    """The reference follows the routing and optimizer the cell runs."""
    import inspect

    from repro.launch.train import make_train_step
    moe = cfg.moe
    if moe.router != "topk" or moe.dispatch != "capacity":
        raise ValueError(f"the moe_lm reference covers top-k capacity "
                         f"routing; the program's default is router="
                         f"{moe.router!r} dispatch={moe.dispatch!r}")
    if moe.num_shared_experts or moe.dense_residual or moe.router_dtype != "float32":
        raise ValueError("the moe_lm reference has no shared or dense "
                         "residual experts and routes in float32")
    sig = inspect.signature(make_train_step).parameters
    o = conf["optimizer"]
    for k in ("warmup", "total_steps"):
        if sig[k].default != o[k]:
            raise ValueError(f"make_train_step's {k} default is "
                             f"{sig[k].default}, the configuration states "
                             f"{o[k]}")


def make_optimizer(conf: dict):
    from repro.optim import AdamW

    o = conf["optimizer"]
    return AdamW(lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                 weight_decay=o["weight_decay"], clip_norm=o["clip_norm"])


def seed_key(seed: int):
    """A jax key from a seed of any size (more than 32 bits hold)."""
    import jax

    seed = int(seed)
    key = jax.random.PRNGKey(np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))


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


def _leaf_init(path: str, shape: tuple, key):
    """Normal weights at the program's scales; norm scales 1, biases 0."""
    import jax
    import jax.numpy as jnp

    if path.endswith("/scale"):
        return jnp.ones(shape, jnp.float32)
    if path.endswith("/bias"):
        return jnp.zeros(shape, jnp.float32)
    fan_in = shape[-2]
    std = 0.02 if path == "embed/table" else fan_in ** -0.5
    return jax.random.normal(key, shape, jnp.float32) * std


def init_params(conf: dict, key) -> dict:
    """The cell's float32 weights from ``key``; trace it under ``jax.jit``."""
    import jax

    shapes = param_shapes(conf)
    flat = flatten(shapes)
    leaves = {p: _leaf_init(p, s, jax.random.fold_in(key, i))
              for i, (p, s) in enumerate(sorted(flat.items()))}
    return _unflatten(leaves)


def check_tree(conf: dict, cfg) -> None:
    """The benchmark's tree matches the program's ``init_params`` exactly."""
    import jax
    import jax.numpy as jnp

    from repro.models import lm
    want = jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0), cfg))
    want = {p: (tuple(a.shape), a.dtype) for p, a in flatten(want).items()}
    have = {p: (tuple(s), jnp.dtype(jnp.float32))
            for p, s in flatten(param_shapes(conf)).items()}
    if want != have:
        raise ValueError(f"parameter tree differs from the program's: "
                         f"{sorted(set(want.items()) ^ set(have.items()))}")


def flatten(tree, prefix: str = "") -> dict:
    """{'a/b/c': leaf} of a nested dict."""
    out = {}
    for k, v in tree.items():
        p = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flatten(v, p + "/"))
        else:
            out[p] = v
    return out


def _unflatten(flat: dict) -> dict:
    tree: dict = {}
    for path, v in flat.items():
        node = tree
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = v
    return tree
