"""Model FLOPs per trained token, counted from a configuration's shapes.

PaLM (arXiv:2204.02311) App. B: ``6 N + 12 L H Q T``, where N counts the
active non-embedding matmul parameters (attention projections, the top-k
experts a token visits, the router and the output head), L the layers, H the
heads, Q the head size and T the sequence length.  Recomputed FLOPs (remat)
do not count, nor do embedding lookups, norms or the optimizer.
"""
from __future__ import annotations

import json
import os

PEAKS_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def active_matmul_params(cfg: dict) -> int:
    """N: active non-embedding matmul parameters per token."""
    d = cfg["d_model"]
    attn = d * (cfg["num_heads"] + 2 * cfg["num_kv_heads"]) * cfg["head_dim"]
    attn += cfg["num_heads"] * cfg["head_dim"] * d
    proj = 3 if cfg["act"] == "swiglu" else 2
    experts = cfg["top_k"] * proj * d * cfg["d_expert_hidden"]
    router = d * cfg["num_experts"]
    head = d * cfg["vocab_size"]
    return cfg["num_layers"] * (attn + experts + router) + head


def flops_per_token(cfg: dict, seq_len: int) -> int:
    attn = 12 * cfg["num_layers"] * cfg["num_heads"] * cfg["head_dim"] * seq_len
    return 6 * active_matmul_params(cfg) + attn


def peaks(device_kind: str, path: str = PEAKS_PATH) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an error."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}; "
                       f"known: {sorted(table)}")
    return table[device_kind]
