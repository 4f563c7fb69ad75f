"""Device-mesh helpers.

The reference scales by adding Spark executors and shuffling RDD partitions
between them (SURVEY.md §2.7). Here the unit of scale is a
`jax.sharding.Mesh` over TPU devices: data/model axes are sharded over ICI
and XLA inserts the collectives. These helpers centralize mesh creation and
host-side padding/partitioning for block-sharded kernels.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh


def local_device_count() -> int:
    return len(jax.devices())


def init_distributed(coordinator: str, num_processes: int,
                     process_id: int) -> None:
    """Join a multi-host training job (SURVEY.md §2.7 DCN scale-out).

    The reference scales out by spawning against a Spark cluster
    (tools/.../Runner.scala:185-307); the TPU-native equivalent is JAX's
    multi-controller runtime: every host runs the SAME `pio train`
    invocation with its own --process-id, jax.distributed.initialize
    wires them through the coordinator, and jax.devices() then returns
    the GLOBAL device set so get_mesh() spans all hosts — collectives
    ride ICI within a slice and DCN across slices, inserted by XLA.
    Idempotent: repeat calls with the same topology are no-ops.
    """
    if getattr(init_distributed, "_done", None) == (
            coordinator, num_processes, process_id):
        return
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id)
    init_distributed._done = (coordinator, num_processes, process_id)


def is_multiprocess() -> bool:
    return jax.process_count() > 1


def get_mesh(n_devices: Optional[int] = None,
             axis_name: str = "block") -> Mesh:
    """A 1-D mesh over the first n devices (default: all).

    ALS and the other classical-ML kernels here are block-parallel over one
    axis (users or items); a 1-D mesh suffices and maps onto an ICI ring.
    """
    devices = jax.devices()
    if n_devices is not None:
        if n_devices > len(devices):
            raise ValueError(
                f"requested {n_devices} devices but only {len(devices)} "
                "are visible")
        devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (axis_name,))


def shard_map_compat(f: Callable, mesh: Mesh, in_specs: Sequence[Any],
                     out_specs: Any) -> Callable:
    """``jax.shard_map`` with replication checking off: the kernels
    here use collectives (all_gather/psum) whose replication the checker
    cannot always infer, exactly why als_dist always ran with
    ``check_vma=False``."""
    return jax.shard_map(f, mesh=mesh, in_specs=tuple(in_specs),
                         out_specs=out_specs, check_vma=False)


def pad_to_multiple(arr: np.ndarray, multiple: int, pad_value) -> np.ndarray:
    """Pad axis 0 up to a multiple (XLA static-shape friendliness)."""
    n = arr.shape[0]
    target = ((n + multiple - 1) // multiple) * multiple if n else multiple
    if target == n:
        return arr
    pad_width = [(0, target - n)] + [(0, 0)] * (arr.ndim - 1)
    return np.pad(arr, pad_width, constant_values=pad_value)
