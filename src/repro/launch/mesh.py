"""Meshes the engines shard over: axes (data, model), with an optional
outer ``pod`` axis that carries the outermost data parallelism.

Functions, not module constants: importing this module must never touch
jax device state (multi-device host tests set XLA_FLAGS before the first
jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh

def make_host_mesh(data: int = 1, model: int = 1):
    """Host-CPU mesh with the production axis names at test sizes.

    ``make_host_mesh()`` is the historical 1×1 mesh. Multi-device CPU
    tests ask for ``make_host_mesh(data=8)`` after forcing placeholder
    devices via ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
    (which must be set before the first jax device query) — the same
    (data, model) axis names the engines shard over on real TPUs, so
    the shard_map'd hot paths are exercised in tier-1 without hardware."""
    data, model = int(data), int(model)
    if data < 1 or model < 1:
        raise ValueError(f"mesh axes must be >= 1, got data={data} "
                         f"model={model}")
    avail = jax.device_count()
    if data * model > avail:
        raise ValueError(
            f"host mesh {data}x{model} needs {data * model} devices but "
            f"only {avail} exist — set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={data * model} "
            f"before the first jax call")
    return jax.make_mesh((data, model), ("data", "model"),
                         (AxisType.Auto,) * 2)


def auto_axes(mesh):
    """``mesh`` with every axis of type Auto (``None`` stays ``None``).

    ``jax.make_mesh`` gives Explicit axes by default, and with them every
    array a jitted body touches carries its sharding in its type: a
    reshape or slice of a sharded dim that the type cannot describe is
    refused. The engines shard one batch axis with ``jax.shard_map`` and
    let the compiler place everything else, so they run on the Auto view
    of whatever mesh a caller built — same devices, same axis names."""
    if mesh is None or all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def data_axis_size(mesh) -> int:
    """Devices along the data axis — the shard count of the engines'
    batch/row/page-pool axes (pod · data when a pod axis exists)."""
    if mesh is None:
        return 1
    n = mesh.shape["data"]
    if "pod" in mesh.axis_names:
        n *= mesh.shape["pod"]
    return int(n)


def fsdp_axes(mesh) -> tuple:
    """The axes weights' d_in / the batch are sharded over."""
    names = mesh.axis_names
    return ("pod", "data") if "pod" in names else ("data",)


def num_chips(mesh) -> int:
    return mesh.devices.size
