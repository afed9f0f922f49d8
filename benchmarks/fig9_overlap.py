"""Beyond-paper Fig 9: §5.2 smart-schedule overlap under the fig8 Zipf skew.

Serial baseline (one blocking all-to-all each way around the expert FFN) vs
the pipelined path (``DistConfig.overlap_chunks``: the exchange split into
capacity micro-shards, each a ppermute-decomposed all-to-all, expert compute
interleaved — repro/core/pipeline.py).  Same data-induced skew as fig8:
tokens drawn from per-expert Zipf-frequency cluster centers with the router
weight matrix as the center matrix.

Reported per row: median forward us serial vs pipelined, the pipeline depth,
and the exchange/compute interleaving evidence from compiled HLO — the
serial path's blocking ``all-to-all`` count vs the pipelined path's
``collective-permute`` count (the op XLA schedules asynchronously).  The
pipelined output must be bit-exact vs serial (acceptance criterion); the
subprocess asserts it before printing.

On the fake-device CPU mesh the timing delta is noise — collectives are
memcpys and XLA:CPU doesn't overlap them — so the numbers demonstrate the
schedule's *structure*; the win shows up on real ICI links.
"""
from __future__ import annotations

import json

from benchmarks.common import FAKE_DEVICE_PLATFORM, emit, run_on_fake_devices

W = 4  # expert-parallel ranks (fake devices)
NB, DM, DH, K, E = 4096, 64, 128, 2, 16
ZIPF_A = 1.2
CHUNKS = 4

_SCRIPT = """
import time
import numpy as np
import jax, jax.numpy as jnp
from repro.configs.base import MoEConfig
from repro.core import fmoe
from repro.core.dispatch import expert_capacity

w, E, NB, DM, DH, K, CH = {w}, {e}, {nb}, {dm}, {dh}, {k}, {chunks}
cfg = MoEConfig(num_experts=E, top_k=K, d_expert_hidden=DH,
                capacity_factor=2.0)
rng = np.random.RandomState(0)

# Zipf-clustered tokens: router columns = cluster centers (fig8 setup)
centers = rng.normal(size=(E, DM)).astype(np.float32)
centers /= np.linalg.norm(centers, axis=1, keepdims=True)
p = 1.0 / (np.arange(E) + 1) ** {zipf_a}
p /= p.sum()
z = rng.choice(E, size=NB, p=p)
x = jnp.asarray(centers[z] + 0.3 * rng.normal(size=(NB, DM)).astype(np.float32))
params = fmoe.fmoe_init(jax.random.PRNGKey(0), DM, cfg)
params["router"]["w"] = jnp.asarray(centers.T * 4.0)

from repro.launch.mesh import make_local_mesh
mesh = make_local_mesh(1, w)
dist0 = fmoe.DistConfig(mesh, ("data", "model"))
dist1 = fmoe.DistConfig(mesh, ("data", "model"), overlap_chunks=CH)
dist_b = fmoe.DistConfig(mesh, ("data", "model"), wire_dtype="bf16")

def bench(dist):
    fn = jax.jit(lambda p_, x_: fmoe.fmoe_apply(p_, x_, cfg, dist=dist))
    with mesh:
        for _ in range(3):
            jax.block_until_ready(fn(params, x))
        ts = []
        for _ in range(16):
            t0 = time.perf_counter()
            y, m = fn(params, x)
            jax.block_until_ready(y)
            ts.append(time.perf_counter() - t0)
        # lower the FULL (y, metrics) program: the wire-byte comparison must
        # see the counts exchange too (a [0]-only lowering would DCE it)
        txt = fn.lower(params, x).compile().as_text()
    return float(np.median(ts) * 1e6), np.asarray(y), m, txt

from repro.launch.roofline import collective_bytes

def hlo_wire(txt):
    cb = collective_bytes(txt)
    return float(cb.get("all-to-all", 0) + cb.get("collective-permute", 0))

us0, y0, m0, hlo0 = bench(dist0)
us1, y1, m1, hlo1 = bench(dist1)
us_b, _, m_b, hlo_b = bench(dist_b)
assert (y0 == y1).all(), "pipelined path must be bit-exact vs serial"
# measured (device counter) vs modeled (optimized-HLO exchange output bytes)
# must agree: the counter is the same quantity computed at trace time
pairs = {{"serial": (float(m0.obs.wire_bytes), hlo_wire(hlo0)),
          "pipelined": (float(m1.obs.wire_bytes), hlo_wire(hlo1)),
          "bf16": (float(m_b.obs.wire_bytes), hlo_wire(hlo_b))}}
for name, (meas, model) in pairs.items():
    assert abs(meas - model) <= 0.10 * max(model, 1.0), (
        f"{{name}}: counter {{meas}} vs HLO {{model}}")
assert 0.4 <= pairs["bf16"][0] / pairs["serial"][0] <= 0.6, (
    "bf16 wire must be ~half of f32")
a2a0 = hlo0.count("all-to-all")
cp1 = hlo1.count("collective-permute")
cap = expert_capacity(NB // w, E, K, cfg.capacity_factor)
chunk_elems = (E * (cap // CH)) * DM  # per-chunk payload per rank, one way

# ---- two-level (hierarchical) ragged exchange under Zipf skew ----
# Same cluster construction, but the Zipf ranks interleave across the two
# nodes (hot experts alternate), so the *actual* per-node load sits well
# below the dropless worst case — the adaptive bounds turn that measured
# headroom into fewer inter-node wire bytes.
from types import SimpleNamespace
from repro.core.monitor import LoadMonitor

cfg_r = MoEConfig(num_experts=E, top_k=K, d_expert_hidden=DH,
                  dispatch="ragged", capacity_factor=2.0)
n_nodes, n_inner = 2, w // 2
mesh_h = make_local_mesh(1, n_inner, node=n_nodes)
AXH = ("data", "node", "model")
zr = np.empty(E, np.int64)  # expert -> interleaved Zipf rank
zr[:E // 2], zr[E // 2:] = 2 * np.arange(E // 2), 2 * np.arange(E // 2) + 1
ph = (1.0 / (zr + 1) ** {zipf_a}); ph /= ph.sum()
zh = rng.choice(E, size=NB, p=ph)
xh = jnp.asarray(centers[zh]
                 + 0.3 * rng.normal(size=(NB, DM)).astype(np.float32))

def bench_h(dist):
    fn = jax.jit(lambda p_, x_: fmoe.fmoe_apply(p_, x_, cfg_r, dist=dist))
    with mesh_h:
        for _ in range(3):
            jax.block_until_ready(fn(params, xh))
        ts = []
        for _ in range(16):
            t0 = time.perf_counter()
            y, m = fn(params, xh)
            jax.block_until_ready(y)
            ts.append(time.perf_counter() - t0)
        txt = fn.lower(params, xh).compile().as_text()
    return float(np.median(ts) * 1e6), np.asarray(y), m, txt

flat_r = fmoe.DistConfig(mesh_h, AXH, expert_axis=("node", "model"))
us_f, y_f, m_f, hlo_f = bench_h(flat_r)
us_h, y_h, m_h, hlo_h = bench_h(flat_r._replace(node_axis="node"))
assert (y_f == y_h).all(), "two-level exchange must be bit-exact vs flat"

# --ragged_bound auto, by hand: calibrate both bounds from the measured
# per-expert load (one exact-load update; ema=0 keeps it undamped)
mon = LoadMonitor(E, ema=0.0)
mon.update(SimpleNamespace(load=np.asarray(m_f.load), drop_frac=0.0))
mp_h, t_local = n_nodes * n_inner, NB // w
rb = mon.suggest_ragged_bound(t_local, K, mp_h)
ib = mon.suggest_ragged_bound(t_local * n_inner, K, mp_h)
assert rb < t_local * K and ib < n_inner * t_local * K, (
    "adaptive bounds must sit below the dropless worst case")
us_s, y_s, m_s, hlo_s = bench_h(flat_r._replace(
    node_axis="node", ragged_bound=rb, inter_bound=ib))
assert float(m_s.drop_frac) <= 0.01, float(m_s.drop_frac)
assert float(m_s.obs.wire_bytes_inter) < float(m_h.obs.wire_bytes_inter)
assert float(m_s.obs.wire_bytes_inter) < float(m_f.obs.wire_bytes_inter)
hier_pairs = {{"hier_flat": (float(m_f.obs.wire_bytes), hlo_wire(hlo_f)),
               "hier": (float(m_h.obs.wire_bytes), hlo_wire(hlo_h)),
               "hier_auto": (float(m_s.obs.wire_bytes), hlo_wire(hlo_s))}}
for name, (meas, model) in hier_pairs.items():
    assert abs(meas - model) <= 0.10 * max(model, 1.0), (
        f"{{name}}: counter {{meas}} vs HLO {{model}}")

import json
print("RESULTJSON " + json.dumps({{
    "us0": us0, "us1": us1, "ch": CH, "a2a0": a2a0, "cp1": cp1,
    "chunk_elems": chunk_elems,
    "wire_bytes_serial": pairs["serial"][0],
    "hlo_bytes_serial": pairs["serial"][1],
    "wire_bytes_pipelined": pairs["pipelined"][0],
    "hlo_bytes_pipelined": pairs["pipelined"][1],
    "wire_bytes_bf16": pairs["bf16"][0],
    "hlo_bytes_bf16": pairs["bf16"][1],
    "hier": {{
        "us_flat": us_f, "us_hier": us_h, "us_hier_auto": us_s,
        "ragged_bound_auto": rb, "inter_bound_auto": ib,
        "dropless_bound": t_local * K,
        "dropless_inter_bound": n_inner * t_local * K,
        "drop_frac_auto": float(m_s.drop_frac), "bit_exact": True,
        "wire_bytes_flat_inter": float(m_f.obs.wire_bytes_inter),
        "wire_bytes_hier_intra": float(m_h.obs.wire_bytes_intra),
        "wire_bytes_hier_inter": float(m_h.obs.wire_bytes_inter),
        "wire_bytes_auto_intra": float(m_s.obs.wire_bytes_intra),
        "wire_bytes_auto_inter": float(m_s.obs.wire_bytes_inter),
        "wire_bytes_flat": hier_pairs["hier_flat"][0],
        "hlo_bytes_flat": hier_pairs["hier_flat"][1],
        "wire_bytes_hier": hier_pairs["hier"][0],
        "hlo_bytes_hier": hier_pairs["hier"][1],
        "wire_bytes_auto": hier_pairs["hier_auto"][0],
        "hlo_bytes_auto": hier_pairs["hier_auto"][1]}}}}))
"""


def run(quick: bool = False) -> list[dict]:
    nb = NB // 2 if quick else NB
    script = _SCRIPT.format(w=W, e=E, nb=nb, dm=DM, dh=DH, k=K,
                            zipf_a=ZIPF_A, chunks=CHUNKS)
    out = run_on_fake_devices(script, W)
    vals = json.loads(out.strip().split("RESULTJSON ")[1].splitlines()[0])
    row = {
        "us_serial": vals["us0"], "us_pipelined": vals["us1"],
        "n_chunks": vals["ch"], "hlo_all_to_all_serial": vals["a2a0"],
        "hlo_collective_permute_pipelined": vals["cp1"],
        "chunk_elems": vals["chunk_elems"], "bit_exact": True,
        # wire-byte evidence: device-side counter vs optimized-HLO exchange
        # bytes (asserted within 10% in-subprocess before printing)
        "wire_bytes_serial": vals["wire_bytes_serial"],
        "hlo_bytes_serial": vals["hlo_bytes_serial"],
        "wire_bytes_pipelined": vals["wire_bytes_pipelined"],
        "hlo_bytes_pipelined": vals["hlo_bytes_pipelined"],
        "wire_bytes_bf16": vals["wire_bytes_bf16"],
        "hlo_bytes_bf16": vals["hlo_bytes_bf16"],
        # two-level ragged exchange on the (1, 2, 2) node mesh under the
        # interleaved Zipf skew, with LoadMonitor-calibrated bounds
        "hier": vals["hier"],
        # the backend tag gates cost-model calibration (placement/calibrate)
        "backend": FAKE_DEVICE_PLATFORM,
    }
    emit("fig9_serial", row["us_serial"],
         f"all_to_all_ops={row['hlo_all_to_all_serial']} "
         f"wire_bytes={row['wire_bytes_serial']:.0f}")
    emit("fig9_pipelined", row["us_pipelined"],
         f"chunks={row['n_chunks']} "
         f"collective_permutes={row['hlo_collective_permute_pipelined']} "
         f"chunk_elems={row['chunk_elems']} bit_exact=True "
         f"wire_bytes={row['wire_bytes_pipelined']:.0f}")
    h = vals["hier"]
    emit("fig9_hier_flat", h["us_flat"],
         f"inter_bytes={h['wire_bytes_flat_inter']:.0f} (flat: all inter)")
    emit("fig9_hier", h["us_hier"],
         f"bit_exact=True intra={h['wire_bytes_hier_intra']:.0f} "
         f"inter={h['wire_bytes_hier_inter']:.0f}")
    emit("fig9_hier_auto", h["us_hier_auto"],
         f"bound={h['ragged_bound_auto']}/{h['dropless_bound']} "
         f"inter_bound={h['inter_bound_auto']}/{h['dropless_inter_bound']} "
         f"inter={h['wire_bytes_auto_inter']:.0f} "
         f"drop={h['drop_frac_auto']:.3f}")
    return [row]
