"""Device-mesh helpers.

The TPU equivalent of the reference's cluster topology plumbing (Spark
master/executor layout; Akka ActorSystem + ZooKeeper discovery): a
``jax.sharding.Mesh`` over the chips, with named axes that parallel
strategies refer to (data / model / pipeline / sequence / expert).
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"
PIPELINE_AXIS = "pipe"
SEQUENCE_AXIS = "seq"
EXPERT_AXIS = "expert"


def virtual_cpu_devices(n: int = 8) -> None:
    """Force a virtual n-device CPU platform BEFORE first backend use —
    the standalone-script version of the tests/conftest.py discipline
    (Spark local[N] role, BaseSparkTest.java:90), for the demos that are
    about a mesh and have no chips to build one from."""
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n)


def device_mesh(
    num_devices: Optional[int] = None,
    shape: Optional[Sequence[int]] = None,
    axis_names: Tuple[str, ...] = (DATA_AXIS,),
    devices=None,
) -> Mesh:
    """Build a Mesh. Default: 1-D data axis over all (or first n) devices."""
    devs = list(devices if devices is not None else jax.devices())
    if num_devices is not None:
        if num_devices > len(devs):
            raise ValueError(
                f"requested {num_devices} devices, only {len(devs)} available"
            )
        devs = devs[:num_devices]
    if shape is None:
        shape = (len(devs),)
    arr = np.asarray(devs).reshape(tuple(shape))
    return Mesh(arr, axis_names)


def data_sharding(mesh: Mesh, axis: str = DATA_AXIS) -> NamedSharding:
    """Batch-axis sharding: [B, ...] split over the data axis."""
    return NamedSharding(mesh, P(axis))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
