"""Paper Fig 6: cross-worker scalability of distributed expert parallelism.

Each worker count runs in a subprocess with that many fake host devices; the
distributed a2a MoE layer (paper §3.2) executes real all-to-alls through
XLA's collective machinery.  Throughput = expert-GeMM FLOPs / wall time,
matching the paper's metric.  NOTE: fake devices share one CPU, so absolute
scaling is bounded by the host — the deliverable is that the multi-worker
path *works* and its throughput accounting is honest (the paper itself
reports sub-linear scaling).
"""
from __future__ import annotations

from benchmarks.common import FAKE_DEVICE_PLATFORM, emit, run_on_fake_devices

WORKERS = [1, 2, 4, 8]
NB, DM, DH, K, NE = 1024, 128, 512, 2, 4  # paper: ne=4 experts per worker

_SCRIPT = """
import time, jax, jax.numpy as jnp
from repro.configs.base import MoEConfig
from repro.core import fmoe
w = {w}
E = {ne} * w  # ne experts per worker (paper §5.3)
cfg = MoEConfig(num_experts=E, top_k={k}, d_expert_hidden={dh}, capacity_factor=2.0)
params = fmoe.fmoe_init(jax.random.PRNGKey(0), {dm}, cfg)
x = jax.random.normal(jax.random.PRNGKey(1), ({nb}, {dm}), jnp.float32)
if w == 1:
    fn = jax.jit(lambda p, x: fmoe.fmoe_apply(p, x, cfg)[0])
    ctx = None
else:
    from repro.launch.mesh import make_local_mesh
    mesh = make_local_mesh(1, w)
    dist = fmoe.DistConfig(mesh, ("data", "model"))
    fn = jax.jit(lambda p, x: fmoe.fmoe_apply(p, x, cfg, dist=dist)[0])
    ctx = mesh
def run():
    if ctx is not None:
        with ctx:
            return fn(params, x)
    return fn(params, x)
for _ in range(3):
    jax.block_until_ready(run())
ts = []
for _ in range(8):
    t0 = time.perf_counter(); jax.block_until_ready(run())
    ts.append(time.perf_counter() - t0)
import numpy as np
dt = float(np.median(ts))
flops = 2 * {nb} * {k} * 2 * {dm} * {dh} * 3  # swiglu: 3 projections
print(f"RESULT {{dt*1e6:.1f}} {{flops/dt/1e9:.2f}}")
"""


def run(quick: bool = False) -> list[dict]:
    rows = []
    for w in (WORKERS[:3] if quick else WORKERS):
        script = _SCRIPT.format(w=w, nb=NB, dm=DM, dh=DH, k=K, ne=NE)
        out = run_on_fake_devices(script, w)
        us, gflops = out.strip().split("RESULT ")[1].split()
        emit(f"fig6_workers{w}", float(us), f"{gflops}GFLOP/s "
             f"E={NE * w}")
        rows.append({"workers": w, "us": float(us), "gflops": float(gflops),
                     "backend": FAKE_DEVICE_PLATFORM})
    return rows
