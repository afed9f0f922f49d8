"""Distributed expert parallelism demo (paper §3.2): the same FMoE layer on
an 8-worker mesh, with the all-to-all global data exchange visible in HLO.

  PYTHONPATH=src python examples/expert_parallel.py
(spawns its own 8 fake devices — run as a standalone script, not inside a
process that already initialized jax)
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig
from repro.core import fmoe
from repro.core.naive import moe_loop_masked


def main() -> None:
    from repro.launch.mesh import make_local_mesh
    mesh = make_local_mesh(2, 4)
    cfg = MoEConfig(num_experts=8, top_k=2, d_expert_hidden=256,
                    capacity_factor=2.0)
    params = fmoe.fmoe_init(jax.random.PRNGKey(0), 128, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 64, 128))

    dist = fmoe.DistConfig(mesh, ("data", "model"))  # tokens over all 8 workers
    print(f"mode={dist.mode}: 8 experts sharded over {dist.expert_parallelism} "
          f"model-parallel workers, 2-way data parallel")

    fn = jax.jit(lambda p, x: fmoe.fmoe_apply(p, x, cfg, dist=dist))
    with mesh:
        y, metrics = fn(params, x)
        hlo = fn.lower(params, x).compile().as_text()
    n_a2a = hlo.count(" all-to-all(") + hlo.count(" all-to-all-start(")
    print(f"all-to-all ops in compiled HLO: {n_a2a} (dispatch + counts + return)")

    y_ref = moe_loop_masked(params, x, cfg)
    print("max |distributed - local reference| =",
          float(jnp.abs(y - y_ref).max()))
    print("per-expert load:", [f"{v:.2f}" for v in metrics.load.tolist()])


if __name__ == "__main__":
    main()
