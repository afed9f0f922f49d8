"""Logical-axis sharding rules (MaxText-style) realizing the paper's §3.2
gradient-sync tag semantics (see repro.core.sync and DESIGN.md §2/§5).

Every parameter path maps to logical axes via the first matching rule; the
logical->mesh table turns them into PartitionSpecs, with a divisibility guard
that falls back to replication when a dim doesn't split evenly.

Tag realization: router/norms match no sharded rule -> fully replicated
("world"); TP projections shard over "model" ("dp"); expert tensors shard
their expert dim over "model" ("none").
"""
from __future__ import annotations

import re
from typing import Any

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import InputShape, ModelConfig
from repro.launch.mesh import data_axes

# (path regex, logical axes per dim) — first match wins.  Paths are
# '/'-joined; stacked layer params keep their in-layer path (the leading L
# dim gets None prepended automatically).
RULES: list[tuple[str, tuple]] = [
    (r"embed/table$", ("vocab", "embed")),
    (r"lm_head/w$", ("embed", "vocab")),
    # router ("world" tag): replicated everywhere
    (r"router/w$", (None, None)),
    # experts ("none" tag): expert dim over the expert axis, hidden dim over
    # the data axis (FSDP bytes identical to d-sharding, but the layout
    # coincides with expert-internal TP so enabling it needs no resharding)
    (r"experts/wi(_gate|_up)?$", ("expert", None, "embed")),
    (r"experts/wo$", ("expert", "embed", None)),
    # attention (tag "dp"): heads over model
    (r"attn/w[qkv]/w$", ("embed", "heads")),
    (r"attn/w[qkv]/b$", ("heads",)),
    (r"attn/wo/w$", ("heads", "embed")),
    # MLA
    (r"attn/w_dq/w$", ("embed", None)),
    (r"attn/w_uq/w$", (None, "heads")),
    (r"attn/w_dkv/w$", ("embed", None)),
    (r"attn/w_kr/w$", ("embed", None)),
    (r"attn/w_u[kv]$", ("heads", None, None)),
    # cross attention (whisper decoder)
    (r"cross_attn/w[qkv]/w$", ("embed", "heads")),
    (r"cross_attn/wo/w$", ("heads", "embed")),
    # dense FFN / shared experts / dense residual
    (r"(ffn|shared|dense)/wi(_gate|_up)?/?w?$", ("embed", "ffn")),
    (r"(ffn|shared|dense)/wo/?w?$", ("ffn", "embed")),
    # rwkv6 time-mix
    (r"rwkv/w[rkvg]/w$", ("embed", "heads")),
    (r"rwkv/wo/w$", ("heads", "embed")),
    (r"rwkv/ts_w1$", ("embed", None)),
    (r"rwkv/ts_w2$", (None, None, "embed")),
    (r"rwkv/decay_w1$", ("embed", None)),
    (r"rwkv/decay_w2$", (None, "embed")),
    (r"rwkv/cm_k/w$", ("embed", "ffn")),
    (r"rwkv/cm_v/w$", ("ffn", "embed")),
    (r"rwkv/cm_r/w$", ("embed", "heads")),
    # mamba (hymba)
    (r"mamba/in_proj/w$", ("embed", "ffn")),
    (r"mamba/out_proj/w$", ("ffn", "embed")),
    (r"mamba/conv_w$", (None, "ffn")),
    (r"mamba/conv_b$", ("ffn",)),
    (r"mamba/x_proj/w$", ("ffn", None)),
    (r"mamba/dt_proj/w$", (None, "ffn")),
    (r"mamba/dt_proj/b$", ("ffn",)),
    (r"mamba/A_log$", ("ffn", None)),
    (r"mamba/D$", ("ffn",)),
]

LOGICAL_TO_MESH = {
    "batch": ("pod", "data"),
    "embed": ("data",),  # FSDP
    "heads": ("model",),
    "ffn": ("model",),
    "expert": ("model",),  # the paper's expert parallelism
    "vocab": ("model",),
}

# Serving keeps weights TP-resident: no optimizer states at inference, so the
# bf16 weights fit without FSDP and the per-layer weight all-gathers vanish
# (§Perf, decode hillclimb).
LOGICAL_TO_MESH_SERVE = dict(LOGICAL_TO_MESH, embed=())

# §Perf multi-pod: experts sharded over (pod, model) instead of model —
# removes the cross-pod expert-gradient all-reduce that makes multi-pod MoE
# training collective-bound (MoE carries ~E/k x params per active FLOP, so
# replicating experts across pods is disproportionately expensive).
# Overridable cell so the paper-faithful baseline stays the default.
EXPERT_AXES: list = [("model",)]


# §Perf multi-pod: force-replicate MLA up-projections over the model axis.
# SPMD hits an involuntary full-batch replication (21.7 GB f32 AR/layer on
# deepseek 2x16x16) when MLA heads are model-sharded with batch over
# (pod, data); replication costs only the FSDP gathers.
MLA_REPLICATE: list = [False]


def _cell_override(cell: list, value):
    import contextlib

    @contextlib.contextmanager
    def _cm():
        old = cell[0]
        cell[0] = value
        try:
            yield
        finally:
            cell[0] = old
    return _cm()


def expert_axes_override(axes: tuple):
    return _cell_override(EXPERT_AXES, axes)


def option_overrides(opts: dict, mesh):
    """ExitStack applying every §Perf sharding override requested in opts."""
    import contextlib
    stack = contextlib.ExitStack()
    opts = opts or {}
    if opts.get("expert_pod") and "pod" in getattr(mesh, "axis_names", ()):
        stack.enter_context(expert_axes_override(("pod", "model")))
    if "node" in getattr(mesh, "axis_names", ()):
        # hierarchical mesh: the expert dim spans (node, model), node-major —
        # the rank order DistConfig.node_axis's two-level exchange assumes
        stack.enter_context(expert_axes_override(("node", "model")))
    if opts.get("mla_replicate"):
        stack.enter_context(_cell_override(MLA_REPLICATE, True))
    return stack


def _mesh_axes_for(logical, mesh, table=None) -> Any:
    if logical is None:
        return None
    table = table or LOGICAL_TO_MESH
    src = EXPERT_AXES[0] if logical == "expert" else table[logical]
    axes = tuple(a for a in src if a in mesh.axis_names)
    if not axes:
        return None
    return axes if len(axes) > 1 else axes[0]


def _axis_size(entry, mesh) -> int:
    if entry is None:
        return 1
    if isinstance(entry, tuple):
        out = 1
        for a in entry:
            out *= mesh.shape[a]
        return out
    return mesh.shape[entry]


def rules_for(cfg, mesh) -> list:
    """RULES, prefixed with arch-aware attention overrides.

    Sharding a flat (d, H*hd) projection over the model axis implicitly
    splits *heads*; when H (or KV) doesn't divide the axis, SPMD cannot keep
    the per-head layout through the (B,S,H,hd) reshape and falls back to
    replicating whole attention activations (a ~30 GB f32 all-reduce per
    layer on arctic's H=56).  Replicating the offending projections over
    model instead costs only the FSDP gather and keeps everything local.
    """
    if cfg is None or getattr(cfg, "attention", None) is None:
        return RULES
    mp = mesh.shape.get("model", 1) if hasattr(mesh.shape, "get") else 1
    a = cfg.attention
    extra = []
    if a.kind == "gqa" and a.num_kv_heads % mp:
        extra += [(r"(cross_)?attn/w[kv]/w$", ("embed", None)),
                  (r"(cross_)?attn/w[kv]/b$", (None,))]
    if a.kind == "gqa" and a.num_heads % mp:
        extra += [(r"(cross_)?attn/wq/w$", ("embed", None)),
                  (r"(cross_)?attn/wq/b$", (None,)),
                  (r"(cross_)?attn/wo/w$", (None, "embed"))]
    if a.kind == "mla" and (a.num_heads % mp or MLA_REPLICATE[0]):
        extra += [(r"attn/w_u[kq]", ("embed", None)),
                  (r"attn/w_uv$", (None, None, None)),
                  (r"attn/wo/w$", (None, "embed"))]
    return extra + RULES


def spec_for(path: str, shape: tuple, mesh, *, stacked: bool,
             mode: str = "train", rules: list | None = None) -> P:
    table = LOGICAL_TO_MESH_SERVE if mode == "serve" else LOGICAL_TO_MESH
    for pattern, logical in (rules or RULES):
        if re.search(pattern, path):
            dims = [_mesh_axes_for(l, mesh, table) for l in logical]
            break
    else:
        dims = [None] * (len(shape) - (1 if stacked else 0))
    if stacked:
        dims = [None] + dims
    dims = dims[:len(shape)]
    dims += [None] * (len(shape) - len(dims))
    # divisibility guard: replicate any dim that doesn't split evenly
    dims = [d if shape[i] % _axis_size(d, mesh) == 0 else None
            for i, d in enumerate(dims)]
    return P(*dims)


def _flat_paths(tree, prefix=""):
    if isinstance(tree, P):  # old-jax PartitionSpec subclasses tuple: a leaf
        yield prefix[:-1], tree
    elif isinstance(tree, dict):
        for k, v in tree.items():
            yield from _flat_paths(v, f"{prefix}{k}/")
    elif hasattr(tree, "_fields"):
        for k in tree._fields:
            yield from _flat_paths(getattr(tree, k), f"{prefix}{k}/")
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            yield from _flat_paths(v, f"{prefix}{i}/")
    else:
        yield prefix[:-1], tree


# A leaf is stacked by layer wherever a ``layers``/``enc_layers``/
# ``dense_layers`` segment sits in its path, so a tree that nests the params
# (AdamW's ``mu``/``nu``) gets the params' own specs; a moment in another
# layout is resharded inside every optimizer update.
_STACKED = re.compile(r"(^|/)(enc_|dense_)?layers/")


def tree_specs(tree, mesh, mode: str = "train", cfg=None) -> Any:
    """PartitionSpec pytree mirroring ``tree`` (abstract or concrete)."""
    flat = dict(_flat_paths(tree))
    rules = rules_for(cfg, mesh) if cfg is not None else None
    specs = {p: spec_for(p, v.shape, mesh, mode=mode, rules=rules,
                         stacked=bool(_STACKED.search(p)))
             for p, v in flat.items()}
    return _rebuild(tree, specs, "")


def _rebuild(like, specs, prefix):
    if isinstance(like, dict):
        return {k: _rebuild(v, specs, f"{prefix}{k}/") for k, v in like.items()}
    if hasattr(like, "_fields"):
        return type(like)(*(_rebuild(getattr(like, k), specs, f"{prefix}{k}/")
                            for k in like._fields))
    if isinstance(like, (list, tuple)):
        return type(like)(_rebuild(v, specs, f"{prefix}{i}/")
                          for i, v in enumerate(like))
    return specs[prefix[:-1]]


def tree_shardings(tree, mesh, mode: str = "train", cfg=None):
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        tree_specs(tree, mesh, mode, cfg),
                        is_leaf=lambda s: isinstance(s, P))


# ---------------------------------------------------------------------------
# Activation / input specs
# ---------------------------------------------------------------------------


def batch_spec(batch_size: int, mesh, extra_dims: int = 1) -> P:
    """Shard the batch dim over (pod, data) where divisible."""
    axes = data_axes(mesh)
    if not axes or batch_size % _axis_size(axes if len(axes) > 1 else axes[0], mesh):
        axes = None
    elif len(axes) == 1:
        axes = axes[0]
    return P(axes, *([None] * extra_dims))


def cache_specs(cache_tree, mesh, batch_size: int,
                seq_shard: bool = False, paged: bool = False) -> Any:
    """Decode-cache specs: batch over data axes; the big dim over model.

    Default: trailing feature dim (head_dim / latent) over model.
    ``seq_shard``: the ring/window dim over model instead — decode attention
    then reduces over the sharded window via small psums rather than
    all-gathering the cache every layer (§Perf, decode hillclimb).

    ``paged``: the tree is a paged block pool (lm.init_paged_cache — leaves
    (L, P, bs, ...), no batch dim).  Blocks are shared across decode slots,
    so the pool replicates over the data axes and only the trailing feature
    dim (head_dim / latent) shards over model; block tables stay host-side.
    """
    bs = batch_spec(batch_size, mesh, 0)[0]
    mp = mesh.shape["model"] if "model" in mesh.axis_names else 1

    def pool_spec(path, leaf):
        ndim = len(leaf.shape)
        dims = [None] * ndim
        final = path.split("/")[-1]
        if (final in ("k", "v", "ckv", "kr") and ndim >= 4 and mp > 1
                and leaf.shape[-1] % mp == 0):
            dims[-1] = "model"
        return P(*dims)

    def leaf_spec(path, leaf):
        if paged:
            return pool_spec(path, leaf)
        ndim = len(leaf.shape)
        dims = [None] * ndim
        # batch dim: index 1 for stacked (L, B, ...) leaves, 0 otherwise
        if ndim >= 2 and leaf.shape[1] == batch_size:
            b_idx = 1
        elif leaf.shape and leaf.shape[0] == batch_size:
            b_idx = 0
        else:
            b_idx = None
        if b_idx is not None:
            dims[b_idx] = bs
        final = path.split("/")[-1]
        ring = final in ("k", "v", "ckv", "kr", "positions")
        w_idx = (b_idx + 1) if (ring and b_idx is not None
                                and ndim > b_idx + 1) else None
        if (seq_shard and mp > 1 and w_idx is not None
                and leaf.shape[w_idx] % mp == 0
                and leaf.shape[w_idx] >= mp * 2048):
            # window-sharded ring (§Perf decode) — only when each shard keeps
            # >=2048 entries; smaller rings (long_500k's 8k SWA cap) pay more
            # in softmax-reduction collectives than the gathers they save
            dims[w_idx] = "model"
            return P(*dims)
        if (ring and final != "positions" and w_idx is not None
                and ndim >= w_idx + 2 and mp > 1
                and leaf.shape[-1] % mp == 0):
            dims[-1] = "model"  # head_dim/latent-sharded (default)
        return P(*dims)

    flat = dict(_flat_paths(cache_tree))
    specs = {p: leaf_spec(p, v) for p, v in flat.items()}
    return _rebuild(cache_tree, specs, "")
