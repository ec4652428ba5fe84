"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state. The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import to obtain placeholder devices; smoke tests and benchmarks see the
real single CPU device.

Target hardware: TPU v5e pods — 256 chips/pod in a 16x16 mesh
(data, model); 2 pods => (pod, data, model) = (2, 16, 16).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

# v5e hardware constants (used by the roofline analysis)
PEAK_FLOPS_BF16 = 197e12     # per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link (~4 links/chip on the 2D torus)
HBM_PER_CHIP = 16e9          # bytes


def _auto_mesh(shape, axes):
    """A mesh whose axes are all Auto: the sharding rules annotate with
    constraints and let the compiler propagate (``jax.make_mesh`` now
    defaults to Explicit axes, which type-check every op's sharding)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 2, multi_pod: bool = False):
    """Small mesh for CPU tests (requires host-device-count >= product)."""
    if multi_pod:
        return _auto_mesh((2, n_data, n_model), ("pod", "data", "model"))
    return _auto_mesh((n_data, n_model), ("data", "model"))


def make_replica_mesh(n_replicas: int, devices=None, multihost=None):
    """1-D ``(replica,)`` mesh for ``--placement sharded`` (DESIGN.md §5).

    On a real machine this spans the local accelerators; under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` it spans the
    virtual CPU devices (the multi-device CI job runs with N=8), and on a
    bare single-CPU container it degenerates to a size-1 mesh. Delegates to
    sharding.rules.replica_mesh, which picks the largest device count
    dividing ``n_replicas``.

    ``multihost`` accepts a bootstrapped
    :class:`repro.launch.multihost.MultihostContext`: under a *device*
    span the mesh is built from the jax.distributed global device list
    (DESIGN.md §10) so the SPMD executors span processes; under a *host*
    span each process meshes only its own devices and the context's file
    exchange bridges them, so local devices are used unchanged.
    """
    from repro.sharding.rules import replica_mesh

    if multihost is not None and devices is None:
        if multihost.spanning == "device":
            devices = multihost.global_devices()
    return replica_mesh(n_replicas, devices=devices)
