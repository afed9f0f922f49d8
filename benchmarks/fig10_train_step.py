"""Beyond-paper Fig 10: full train-step (fwd+bwd) time for the MoE layer —
two-pass vs fused expert kernels, capacity vs ragged (dropless) dispatch.

The fused path's claim is a *training* claim: with the fused backward
(repro/kernels/fused_ffn_bwd.py) a value_and_grad step never materializes
the (M, H) hidden activation — or its gradient — in HBM on any dispatch
mode.  Each row reports the measured step time plus the structural evidence
from the jaxpr: whether any (rows >= M, H)-shaped intermediate exists in
the differentiated program.

On CPU the Pallas kernels run in interpret mode, so absolute times favor
the XLA two-pass path; the HBM-traffic win shows on real TPUs.  The
``materializes_mh`` column is the backend-independent evidence.
"""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp

from benchmarks.common import (FAKE_DEVICE_PLATFORM, emit,
                               run_on_fake_devices, timeit)

T, DM, DH, E, K = 256, 64, 128, 8, 2
W = 4  # expert-parallel ranks for the distributed wire-evidence rows

# Distributed wire evidence: one fwd+bwd value_and_grad step per (dispatch,
# wire dtype), with the device-side wire counter checked against the
# *forward* program's optimized-HLO exchange bytes (the counter models the
# forward exchange; the backward adds its mirror image on top).
_DIST_SCRIPT = """
import json
import numpy as np
import jax, jax.numpy as jnp
from repro.configs.base import MoEConfig
from repro.core import fmoe
from repro.launch.roofline import collective_bytes

w, E, T, DM, DH, K = {w}, {e}, {t}, {dm}, {dh}, {k}
x = jax.random.normal(jax.random.PRNGKey(1), (T, DM))
from repro.launch.mesh import make_local_mesh
mesh = make_local_mesh(1, w)
rows = []
for dispatch in ("capacity", "ragged"):
    cfg = MoEConfig(num_experts=E, top_k=K, d_expert_hidden=DH,
                    dispatch=dispatch, capacity_factor=2.0)
    params = fmoe.fmoe_init(jax.random.PRNGKey(0), DM, cfg)
    for wire in (None, "bf16"):
        dist = fmoe.DistConfig(mesh, ("data", "model"), wire_dtype=wire)

        def fwd(p, x_):
            return fmoe.fmoe_apply(p, x_, cfg, dist=dist)

        def loss(p, x_):
            y, m = fwd(p, x_)
            return (y ** 2).mean(), m

        step = jax.jit(jax.value_and_grad(loss, has_aux=True))
        with mesh:
            import time
            for _ in range(2):
                jax.block_until_ready(step(params, x)[0][0])
            ts = []
            for _ in range(8):
                t0 = time.perf_counter()
                (l, m), g = step(params, x)
                jax.block_until_ready(l)
                ts.append(time.perf_counter() - t0)
            ftxt = jax.jit(fwd).lower(params, x).compile().as_text()
        cb = collective_bytes(ftxt)
        hlo_wire = float(cb.get("all-to-all", 0)
                         + cb.get("collective-permute", 0))
        meas = float(m.obs.wire_bytes)
        assert abs(meas - hlo_wire) <= 0.10 * max(hlo_wire, 1.0), (
            f"{{dispatch}}/{{wire}}: counter {{meas}} vs fwd HLO {{hlo_wire}}")
        rows.append({{"dispatch": dispatch, "wire_dtype": wire or "f32",
                      "us": float(np.median(ts) * 1e6),
                      "wire_bytes": meas, "hlo_fwd_bytes": hlo_wire,
                      "dropped": float(m.obs.dropped),
                      "imbalance": float(m.obs.imbalance)}})

# two-level ragged exchange on the (data, node, model) mesh: same fwd+bwd
# step, wire counter split intra/inter and checked against the fwd HLO
mesh_h = make_local_mesh(1, w // 2, node=2)
cfg = MoEConfig(num_experts=E, top_k=K, d_expert_hidden=DH,
                dispatch="ragged", capacity_factor=2.0)
params = fmoe.fmoe_init(jax.random.PRNGKey(0), DM, cfg)
for wire in (None, "bf16"):
    dist = fmoe.DistConfig(mesh_h, ("data", "node", "model"),
                           expert_axis=("node", "model"), node_axis="node",
                           wire_dtype=wire)

    def fwd(p, x_):
        return fmoe.fmoe_apply(p, x_, cfg, dist=dist)

    def loss(p, x_):
        y, m = fwd(p, x_)
        return (y ** 2).mean(), m

    step = jax.jit(jax.value_and_grad(loss, has_aux=True))
    with mesh_h:
        import time
        for _ in range(2):
            jax.block_until_ready(step(params, x)[0][0])
        ts = []
        for _ in range(8):
            t0 = time.perf_counter()
            (l, m), g = step(params, x)
            jax.block_until_ready(l)
            ts.append(time.perf_counter() - t0)
        ftxt = jax.jit(fwd).lower(params, x).compile().as_text()
    cb = collective_bytes(ftxt)
    hlo_wire = float(cb.get("all-to-all", 0)
                     + cb.get("collective-permute", 0))
    meas = float(m.obs.wire_bytes)
    assert abs(meas - hlo_wire) <= 0.10 * max(hlo_wire, 1.0), (
        f"hier/{{wire}}: counter {{meas}} vs fwd HLO {{hlo_wire}}")
    rows.append({{"dispatch": "ragged-2lvl", "wire_dtype": wire or "f32",
                  "us": float(np.median(ts) * 1e6),
                  "wire_bytes": meas, "hlo_fwd_bytes": hlo_wire,
                  "wire_bytes_intra": float(m.obs.wire_bytes_intra),
                  "wire_bytes_inter": float(m.obs.wire_bytes_inter),
                  "dropped": float(m.obs.dropped),
                  "imbalance": float(m.obs.imbalance)}})

for d in ("capacity", "ragged", "ragged-2lvl"):
    f32 = next(r for r in rows if r["dispatch"] == d
               and r["wire_dtype"] == "f32")
    b16 = next(r for r in rows if r["dispatch"] == d
               and r["wire_dtype"] == "bf16")
    ratio = b16["wire_bytes"] / f32["wire_bytes"]
    assert 0.4 <= ratio <= 0.6, f"{{d}}: bf16 wire ratio {{ratio}}"
print("RESULTJSON " + json.dumps(rows))
"""


def _materializes_mh(fn, *args, min_rows: int, hidden: int) -> bool:
    jaxpr = jax.make_jaxpr(fn)(*args)
    for eqn in jaxpr.jaxpr.eqns:
        for v in eqn.outvars:
            s = getattr(v.aval, "shape", ())
            if len(s) == 2 and s[1] == hidden and s[0] >= min_rows:
                return True
    return False


def run(quick: bool = False) -> list[dict]:
    import dataclasses

    from repro.configs.base import MoEConfig
    from repro.core import fmoe

    t = T // 2 if quick else T
    rows = []
    x = jax.random.normal(jax.random.PRNGKey(1), (t, DM))
    for dispatch in ("capacity", "ragged"):
        cfg = MoEConfig(num_experts=E, top_k=K, d_expert_hidden=DH,
                        dispatch=dispatch)
        params = fmoe.fmoe_init(jax.random.PRNGKey(0), DM, cfg)
        for impl in ("pallas", "fused"):
            def loss(p, x, impl=impl, cfg=cfg):
                y, _ = fmoe.fmoe_apply(p, x, cfg, impl=impl)
                return (y ** 2).mean()

            step = jax.jit(jax.value_and_grad(loss))
            res = timeit(step, params, x)
            mh = _materializes_mh(jax.value_and_grad(loss), params, x,
                                  min_rows=t * K, hidden=DH)
            row = {"impl": impl, "dispatch": dispatch, "us": res["us"],
                   "std_us": res["std_us"], "materializes_mh": mh,
                   "tokens": t, "backend": jax.default_backend()}
            rows.append(row)
            emit(f"fig10_{dispatch}_{impl}", row["us"],
                 f"fwd+bwd materializes_MH={mh}")
            assert (impl == "fused") == (not mh), (
                "fused step must not materialize (M, H); two-pass must")
    rows += _run_dist(quick)
    return rows


def _run_dist(quick: bool) -> list[dict]:
    t = T // 2 if quick else T
    script = _DIST_SCRIPT.format(w=W, e=E, t=t, dm=DM, dh=DH, k=K)
    out = run_on_fake_devices(script, W)
    rows = json.loads(out.strip().split("RESULTJSON ")[1].splitlines()[0])
    for r in rows:
        r.update(impl="einsum", distributed=True, ranks=W,
                 backend=FAKE_DEVICE_PLATFORM)
        split = ("" if "wire_bytes_inter" not in r else
                 f" intra={r['wire_bytes_intra']:.0f}"
                 f" inter={r['wire_bytes_inter']:.0f}")
        emit(f"fig10_dist_{r['dispatch']}_{r['wire_dtype']}", r["us"],
             f"wire_bytes={r['wire_bytes']:.0f} "
             f"hlo_fwd_bytes={r['hlo_fwd_bytes']:.0f} "
             f"imbalance={r['imbalance']:.2f}" + split)
    return rows
