"""Production meshes.

Target: TPU v5e pods.  Single-pod = 256 chips as (data=16, model=16);
multi-pod = 2 pods = 512 chips as (pod=2, data=16, model=16).  The "pod"
axis is LSGD's slow (inter-communicator) layer; "data" is the fast
intra-pod data-parallel layer; "model" is tensor parallelism.

Functions, not module constants — importing this module never touches jax
device state (device count is locked at first use).
"""
from __future__ import annotations

import jax

# TPU v5e hardware constants (roofline terms, benchmarks/roofline.py)
PEAK_FLOPS_BF16 = 197e12          # per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link (intra-pod)
DCI_BW = 6.25e9                   # bytes/s per chip (inter-pod, ~25GB/s/host)


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis Auto (``jax.make_mesh``
    defaults to Explicit typing; this codebase partitions with GSPMD)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(shape=(2, 2, 2), axes=("pod", "data", "model")):
    """Small mesh for CPU-device tests (requires forced device count)."""
    return make_mesh(shape, axes)


def replica_slices(topology, num_pods: int = 1, devices=None):
    """One ``jax.Device`` slice per serving replica.

    Partitions the visible devices along the LSGD axes — the slow axis
    (pods) first, then each pod's devices into fast-fabric groups
    (``topology.device_slices``) — and returns them pod-major, fast
    groups inner: index ``i`` is the device territory of the
    ``ReplicaRouter``'s replica ``i``.  On CPU CI these are the forced
    virtual devices (``--xla_force_host_platform_device_count``); on
    real hardware they are honest hardware slices — either way each
    replica's per-token traffic stays inside its slice."""
    devices = list(jax.devices()) if devices is None else list(devices)
    return [tuple(devices[i] for i in grp)
            for grp in topology.device_slices(len(devices), num_pods)]


def mesh_axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
