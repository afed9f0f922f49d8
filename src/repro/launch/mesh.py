"""Device meshes.

``make_production_mesh`` targets the TPU v5e deployment: one pod = 256 chips
as (data=16, model=16); multi-pod adds a leading "pod" axis (2 pods = 512).
Defined as functions (never module-level constants) so importing this module
never touches jax device state — the dry-run must set
``--xla_force_host_platform_device_count`` *before* first jax init.

Every mesh gets Auto axis types: ``jax.make_mesh`` defaults to Explicit ones,
under which the model code's unannotated ops raise ``ShardingTypeError``.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape: tuple, axes: tuple):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1, node: int = 1,
                    pod: int = 1):
    """Small mesh over however many (fake) devices a process has.

    ``node > 1`` inserts a "node" axis between data and model: expert
    parallelism then spans ("node", "model") and the ragged exchange runs
    two-level — aggregate within the node-local "model" axis, slim exchange
    over the inter-node "node" axis (core/fmoe DistConfig.node_axis).
    ``pod > 1`` prepends the multi-pod "pod" axis.
    """
    shape, axes = (data, model), ("data", "model")
    if node > 1:
        shape, axes = (data, node, model), ("data", "node", "model")
    if pod > 1:
        shape, axes = (pod,) + shape, ("pod",) + axes
    return _mesh(shape, axes)


def node_axis(mesh):
    """The inter-node mesh axis name, or None for a single-level mesh."""
    return "node" if "node" in mesh.axis_names else None


def data_axes(mesh) -> tuple:
    """Mesh axes that carry the batch dimension (pod folds into data)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def all_axes(mesh) -> tuple:
    return tuple(mesh.axis_names)
