"""Device mesh management.

The reference enumerates CUDA devices and builds one SSA sub-graph per GPU
(reference: python/paddle/fluid/parallel_executor.py:__init__ collects
CUDAPlace list; paddle/fluid/framework/details/*). TPU-native, a
``jax.sharding.Mesh`` is the device topology: named axes (dp/mp/pp/sp/ep)
over which shardings are declared; XLA's SPMD partitioner inserts the
collectives (over ICI within a slice, DCN across hosts).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np

import jax
from jax.sharding import Mesh

__all__ = [
    "make_mesh",
    "make_hybrid_mesh",
    "default_mesh",
    "device_count",
    "get_places",
    "init_distributed",
]


def device_count() -> int:
    return jax.device_count()


def make_mesh(
    shape: Optional[Sequence[int]] = None,
    axis_names: Sequence[str] = ("dp",),
    devices=None,
) -> Mesh:
    """Build a Mesh over (a prefix of) the available devices.

    ``shape=None`` puts every device on the first axis. Multi-host meshes
    should lay the DCN-crossing axis outermost (JAX enumerates devices
    host-major, so axis 0 naturally maps across hosts).
    """
    devices = list(jax.devices()) if devices is None else list(devices)
    if shape is None:
        shape = [len(devices)] + [1] * (len(axis_names) - 1)
    shape = tuple(int(s) for s in shape)
    n = int(np.prod(shape))
    if n > len(devices):
        raise ValueError(
            "mesh shape %s needs %d devices, only %d available"
            % (shape, n, len(devices))
        )
    arr = np.array(devices[:n]).reshape(shape)
    return Mesh(arr, tuple(axis_names))


def make_hybrid_mesh(
    axis_names: Sequence[str],
    ici_shape: Sequence[int],
    dcn_shape: Sequence[int],
    devices=None,
) -> Mesh:
    """Hybrid ICI×DCN mesh: axis ``i`` has size ``dcn[i] * ici[i]`` with
    the DCN (cross-host) factor slowest-varying, so collectives along an
    axis whose dcn factor is 1 stay entirely on ICI and only the axes
    that genuinely span hosts ride DCN.

    The reference's multi-trainer layout splits work host-major the same
    way (reference: transpiler/distribute_transpiler.py trainer split +
    ParallelExecutor num_trainers/trainer_id NCCL bootstrap); here the
    layout is a device permutation and XLA routes each collective over
    the fastest fabric it spans.

    Typical pod use: ``make_hybrid_mesh(("dp", "mp"), ici_shape=(1, 8),
    dcn_shape=(n_hosts, 1))`` — data parallel across hosts over DCN,
    tensor parallel inside each host over ICI.

    Under ``jax.distributed`` this delegates to
    ``mesh_utils.create_hybrid_device_mesh`` (groups by process). Single-
    process (virtual-device tests), devices are arranged host-major with
    ``prod(ici_shape)`` consecutive devices per emulated host — the same
    ordering a real multi-process enumeration produces, which is what the
    ordering tests pin down.
    """
    axis_names = tuple(axis_names)
    ici_shape = tuple(int(s) for s in ici_shape)
    dcn_shape = tuple(int(s) for s in dcn_shape)
    if not (len(axis_names) == len(ici_shape) == len(dcn_shape)):
        raise ValueError(
            "axis_names %s, ici_shape %s and dcn_shape %s must align"
            % (axis_names, ici_shape, dcn_shape))
    devices = list(jax.devices()) if devices is None else list(devices)
    n = int(np.prod(ici_shape)) * int(np.prod(dcn_shape))
    if n > len(devices):
        raise ValueError(
            "hybrid mesh ici %s x dcn %s needs %d devices, only %d "
            "available" % (ici_shape, dcn_shape, n, len(devices)))
    devices = devices[:n]

    if jax.process_count() > 1:
        # TPU pods: prefer jax's topology-aware construction (it groups
        # by pod slice); CPU/GPU jobs have one degenerate slice — group
        # by process there instead
        slices = {getattr(d, "slice_index", None) for d in devices}
        if None not in slices and len(slices) == int(np.prod(dcn_shape)):
            from jax.experimental import mesh_utils

            arr = mesh_utils.create_hybrid_device_mesh(
                ici_shape, dcn_shape, devices=devices)
            return Mesh(arr, axis_names)
        devices = sorted(devices, key=lambda d: (d.process_index, d.id))
        # the host-major reshape below puts prod(ici) CONSECUTIVE devices
        # on one emulated host; that only matches reality when each
        # process contributes exactly prod(ici) devices — otherwise an
        # "ICI" group would silently span processes (i.e. ride DCN)
        per_proc: dict = {}
        for d in devices:
            per_proc[d.process_index] = per_proc.get(d.process_index, 0) + 1
        ici_n = int(np.prod(ici_shape))
        if set(per_proc.values()) != {ici_n}:
            raise ValueError(
                "hybrid mesh needs prod(ici_shape)=%d devices per "
                "process, but processes contribute %s; pick an ici_shape "
                "matching the per-host device count"
                % (ici_n, sorted(per_proc.values())))
        if len(per_proc) != int(np.prod(dcn_shape)):
            raise ValueError(
                "hybrid mesh dcn_shape %s implies %d hosts but the "
                "devices span %d processes"
                % (dcn_shape, int(np.prod(dcn_shape)), len(per_proc)))

    # host-major enumeration: prod(ici) consecutive devices per host
    k = len(axis_names)
    arr = np.array(devices).reshape(dcn_shape + ici_shape)
    # interleave (dcn_0, ici_0, dcn_1, ici_1, ...) then merge per axis
    arr = arr.transpose([ax for i in range(k) for ax in (i, k + i)])
    arr = arr.reshape([d * i for d, i in zip(dcn_shape, ici_shape)])
    return Mesh(arr, axis_names)


def default_mesh(axis_name: str = "dp") -> Mesh:
    """1-D mesh over all devices (the ParallelExecutor default)."""
    return make_mesh(axis_names=(axis_name,))


def get_places(device_count_: Optional[int] = None):
    """Parity with fluid.layers.device.get_places (reference:
    python/paddle/fluid/layers/device.py): enumerate execution places.
    Returns TPUPlace list where chips are attached, CPUPlace otherwise."""
    from ..framework.scope import default_place

    devs = jax.devices()
    n = len(devs) if device_count_ is None else min(device_count_, len(devs))
    return [type(default_place())(i) for i in range(n)]


def init_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
):
    """Multi-host runtime initialization.

    Plays the role of the reference's NCCL bootstrap (ParallelExecutor's
    num_trainers/trainer_id → ncclCommInitRank). On TPU pods the arguments
    are auto-detected from the environment; on CPU/GPU clusters pass them
    explicitly. After this, ``jax.devices()`` spans the whole job and
    meshes built from it are global.
    """
    kwargs = {}
    if coordinator_address is not None:
        kwargs["coordinator_address"] = coordinator_address
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    jax.distributed.initialize(**kwargs)
