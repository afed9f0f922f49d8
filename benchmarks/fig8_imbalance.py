"""Beyond-paper Fig 8: step time under Zipf-skewed routing, expert placement
off vs on (the §6 load-balance loop closed by repro/placement/).

Skew is induced the way production skew arrives — through the data, not the
gate: tokens are drawn from per-expert cluster centers with Zipf frequencies
and the router weight matrix IS the center matrix, so top-1 routing follows
the cluster distribution.  One measurement process per setting (fake host
devices, same contract as fig6): baseline a2a, then the planner's layout
(shadowed hot experts + shrunk exchange buffer) after migrating the params.

Reported per row: median forward us, modeled a2a buffer elements per rank,
observed drop fraction, shadow count and capacity scale.
"""
from __future__ import annotations

from benchmarks.common import FAKE_DEVICE_PLATFORM, emit, run_on_fake_devices

W = 4  # expert-parallel ranks (fake devices)
NB, DM, DH, K, E = 4096, 64, 128, 2, 16
ZIPF_A = 1.2

_SCRIPT = """
import time
import numpy as np
import jax, jax.numpy as jnp
from repro.configs.base import MoEConfig
from repro.core import fmoe
from repro.core.dispatch import expert_capacity
from repro.placement import from_logical, plan_placement, shadow_spec

w, E, NB, DM, DH, K = {w}, {e}, {nb}, {dm}, {dh}, {k}
cfg = MoEConfig(num_experts=E, top_k=K, d_expert_hidden=DH,
                capacity_factor=2.0)
rng = np.random.RandomState(0)

# Zipf-clustered tokens: router columns = cluster centers
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

def bench(dist, prm):
    fn = jax.jit(lambda p_, x_: fmoe.fmoe_apply(p_, x_, cfg, dist=dist))
    with mesh:
        for _ in range(3):
            jax.block_until_ready(fn(prm, x))
        ts = []
        for _ in range(16):
            t0 = time.perf_counter()
            y, m = fn(prm, x)
            jax.block_until_ready(y)
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts) * 1e6), np.asarray(m.load), float(m.drop_frac)

us0, load, drop0 = bench(dist0, params)
cap = expert_capacity(NB // w, E, K, cfg.capacity_factor)
plan = plan_placement(load, w, d_model=DM, d_hidden=DH, capacity=cap,
                      capacity_factor=cfg.capacity_factor)
spec = shadow_spec(plan, E, cap)
base_elems = E * cap * DM
dist1 = fmoe.DistConfig(mesh, ("data", "model"), placement=plan)
us1, load1, drop1 = bench(dist1, from_logical(params, plan))
assert np.allclose(load1, load, atol=1e-6), "placement must not change routing"
imb = float(load.max() * E)
print(f"RESULT {{us0:.1f}} {{us1:.1f}} {{base_elems}} {{spec.a2a_elems(DM)}} "
      f"{{drop0:.4f}} {{drop1:.4f}} {{plan.num_shadow}} "
      f"{{plan.capacity_scale:.3f}} {{imb:.2f}}")
"""


def run(quick: bool = False) -> list[dict]:
    # quick halves tokens AND experts' hidden dim together: shadowing pays
    # when a2a slice bytes (C*d) beat weight-sync bytes (~3*d*h), so scale
    # both or the small regime stops demonstrating the mechanism
    nb, dh = (NB // 2, DH // 2) if quick else (NB, DH)
    script = _SCRIPT.format(w=W, e=E, nb=nb, dm=DM, dh=dh, k=K,
                            zipf_a=ZIPF_A)
    vals = run_on_fake_devices(script, W).strip().split("RESULT ")[1].split()
    us0, us1 = float(vals[0]), float(vals[1])
    elems0, elems1 = int(vals[2]), int(vals[3])
    row = {
        "us_off": us0, "us_on": us1,
        "a2a_elems_off": elems0, "a2a_elems_on": elems1,
        "drop_off": float(vals[4]), "drop_on": float(vals[5]),
        "num_shadow": int(vals[6]), "capacity_scale": float(vals[7]),
        "imbalance": float(vals[8]),
        # the backend tag gates cost-model calibration (placement/calibrate)
        "backend": FAKE_DEVICE_PLATFORM,
    }
    emit("fig8_placement_off", us0,
         f"a2a_elems={elems0} drop={row['drop_off']:.3f} imb={row['imbalance']:.2f}")
    emit("fig8_placement_on", us1,
         f"a2a_elems={elems1} shadow={row['num_shadow']} "
         f"cap_scale={row['capacity_scale']:.2f} drop={row['drop_on']:.3f}")
    return [row]
