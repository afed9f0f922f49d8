"""A configuration file -> the program's ModelConfig, and the cell's weights.

Nothing here knows a model: the tree of leaf shapes, what the reference
covers and the FLOPs per token come from the configuration's reference
module (``bench/reference/<reference>.py``).  The weights are the
benchmark's own: made from the seed on the device in one jitted call, laid
out as that tree (checked against the program's own ``init_params``
shapes), so the plain reference starts from the same numbers without taking
anything the program made.
"""
from __future__ import annotations

import dataclasses

import numpy as np

# Keys of a configuration file that describe the file, not the program.
METADATA_KEYS = frozenset({"name", "source", "program_arch", "reference",
                           "deployment", "reduced", "assumed", "optimizer"})


def _program_fields(cfg, keys) -> dict:
    """{key: group} of each of ``keys`` that names a program field: a field
    of ModelConfig itself (group None) or of a sub-config the registered
    config has.  A key that names fields of two groups is an error."""
    groups = [None] + [f.name for f in dataclasses.fields(cfg)
                       if dataclasses.is_dataclass(getattr(cfg, f.name))]
    owners: dict = {}
    for group in groups:
        for f in dataclasses.fields(getattr(cfg, group) if group else cfg):
            if f.name not in groups and f.name not in METADATA_KEYS:
                owners.setdefault(f.name, []).append(group)
    twice = sorted(k for k in keys if len(owners.get(k, ())) > 1)
    if twice:
        raise ValueError(f"{cfg.name}: {twice} each name a field of more "
                         f"than one group of the program's config")
    return {k: owners[k][0] for k in keys if k in owners}


def _field(cfg, group, name):
    return getattr(getattr(cfg, group) if group else cfg, name)


def _as_program(value):
    return tuple(value) if isinstance(value, list) else value


def program_config(conf: dict):
    """The program's registered config with the file's ``reduced`` keys
    applied; every other program field the file states must agree.

    A program field is a field of ``ModelConfig`` or of a sub-config the
    registered config has (``attention``, ``moe``, ...), by name; the file's
    other keys (``METADATA_KEYS``, notes) are not compared.  A ``reduced``
    key that names no program field is an error."""
    from repro.configs import get_config

    cfg = get_config(conf["program_arch"])
    fields = _program_fields(cfg, set(conf) | set(conf["reduced"]))
    stray = sorted(set(conf["reduced"]) - set(fields))
    if stray:
        raise ValueError(f"{conf['name']}: reduced keys {stray} name no "
                         f"field of the program's {conf['program_arch']}")
    groups: dict = {}
    top: dict = {}
    for key in conf["reduced"]:
        group = fields[key]
        (groups.setdefault(group, {}) if group else top)[key] = \
            _as_program(conf[key])
    for group, changed in groups.items():
        top[group] = dataclasses.replace(getattr(cfg, group), **changed)
    cfg = dataclasses.replace(cfg, **top)
    for key in sorted(set(conf) & set(fields)):
        have = _field(cfg, fields[key], key)
        if have != _as_program(conf[key]):
            raise ValueError(f"{conf['name']}: the program's "
                             f"{conf['program_arch']} has {key}={have!r}, "
                             f"the configuration file states {conf[key]!r}")
    return cfg


def check_optimizer_defaults(conf: dict) -> None:
    """The reference follows the schedule the program's step runs."""
    import inspect

    from repro.launch.train import make_train_step
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


def init_params(shapes: dict, key) -> dict:
    """Float32 weights of the tree of leaf shapes ``shapes`` from ``key``;
    trace it under ``jax.jit``."""
    import jax

    flat = flatten(shapes)
    leaves = {p: _leaf_init(p, s, jax.random.fold_in(key, i))
              for i, (p, s) in enumerate(sorted(flat.items()))}
    return unflatten(leaves)


def check_tree(shapes: dict, cfg) -> None:
    """The reference's tree of leaf shapes matches the program's
    ``init_params`` exactly."""
    import jax
    import jax.numpy as jnp

    from repro.models import lm
    want = jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0), cfg))
    want = {p: (tuple(a.shape), a.dtype) for p, a in flatten(want).items()}
    have = {p: (tuple(s), jnp.dtype(jnp.float32))
            for p, s in flatten(shapes).items()}
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


def unflatten(flat: dict) -> dict:
    tree: dict = {}
    for path, v in flat.items():
        node = tree
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = v
    return tree
