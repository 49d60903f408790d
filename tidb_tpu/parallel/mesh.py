"""Device mesh construction (ref: the role PD topology + store lists play —
which compute nodes exist and how fragments land on them)."""

from __future__ import annotations

from typing import Optional, Sequence


_MESH_CACHE: dict = {}

# forced mesh width, a test seam: the ndev-parity tests (test_mpp_stagechain,
# test_mpp_q3) pin the SAME process to 1 or 4 devices of the virtual CPU mesh
# (None = use every available device). Applies only when the caller passes no
# explicit n_devices/devices.
FORCE_NDEV: Optional[int] = None


def make_mesh(n_devices: Optional[int] = None, axis: str = "dp", devices: Optional[Sequence] = None):
    """1-D mesh over available devices. SQL fragments parallelize along one
    data axis; intra-device parallelism is XLA's job (VPU/MXU), so unlike an
    LLM stack there is no tp/pp split — dp + collectives covers the MPP
    model (hash/broadcast/passthrough exchanges ride ICI).

    ``devices`` overrides the device list (MPP failure retry builds a mesh
    over the surviving devices only — ref mpp_probe blacklisting)."""
    import jax
    from jax.sharding import Mesh

    devs = list(devices) if devices is not None else jax.devices()
    if n_devices is None and devices is None:
        n_devices = FORCE_NDEV
    if n_devices is not None:
        if n_devices > len(devs):
            raise RuntimeError(
                f"mesh wants {n_devices} devices, only {len(devs)} available "
                "(set XLA_FLAGS=--xla_force_host_platform_device_count=N "
                "JAX_PLATFORMS=cpu for a virtual mesh)"
            )
        devs = devs[:n_devices]
    import numpy as np

    # one Mesh object per (devices, axis): jitted MPP programs close over
    # the mesh, so identity stability keeps the XLA compile cache warm
    key = (tuple(id(d) for d in devs), axis)
    mesh = _MESH_CACHE.get(key)
    if mesh is None:
        mesh = Mesh(np.array(devs), (axis,))
        _MESH_CACHE[key] = mesh
    return mesh
