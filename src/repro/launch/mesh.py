"""Replica mesh construction for the launcher.

A function (not a module-level constant) so importing this module never
touches jax device state.
"""
from __future__ import annotations


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
