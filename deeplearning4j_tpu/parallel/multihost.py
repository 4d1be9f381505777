"""Multi-host (multi-process) distributed runtime.

Capability mirror of the reference's cluster communication layer (SURVEY.md
section 2.7 "Communication backends": Spark RPC/broadcast as the data plane,
ZooKeeper service discovery, NTP clock alignment). TPU-native equivalent:
jax.distributed — one controller process per host, XLA collectives riding
ICI within a slice and DCN across slices; discovery via the coordinator
address (the ZooKeeper role), clocks by the host (stats.TimeSource).

All helpers degrade gracefully to single-process: the same training code
runs unchanged on 1 host (jax.devices() == local) or N hosts
(jax.devices() == global). The driver validates the sharded program via
__graft_entry__.dryrun_multichip on a virtual mesh.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Optional

from deeplearning4j_tpu.ops import env as envknob


# THE env-var contract between launchers (provision/tpu_pod.py bootstrap)
# and this runtime — both sides import these names, so they cannot drift
COORDINATOR_ENV = "DL4J_TPU_COORDINATOR"
NUM_PROCESSES_ENV = "DL4J_TPU_NUM_PROCESSES"
PROCESS_ID_ENV = "DL4J_TPU_PROCESS_ID"


@dataclass
class MultiHostConfig:
    """The coordinator triple (jax.distributed.initialize signature);
    fields default from the standard env vars so launchers can inject them
    (the ZooKeeperConfigurationRegister role — SURVEY.md section 2.4)."""

    coordinator_address: Optional[str] = None
    num_processes: Optional[int] = None
    process_id: Optional[int] = None

    @classmethod
    def from_env(cls) -> "MultiHostConfig":
        return cls(
            coordinator_address=envknob.get_str(COORDINATOR_ENV),
            num_processes=_int_env(NUM_PROCESSES_ENV),
            process_id=_int_env(PROCESS_ID_ENV),
        )

    def is_configured(self) -> bool:
        return self.coordinator_address is not None


def _int_env(name: str) -> Optional[int]:
    return envknob.get_int(name)


_initialized = False


def initialize_multihost(config: Optional[MultiHostConfig] = None) -> bool:
    """Bring up jax.distributed if a coordinator is configured; returns
    whether multi-host mode is active. Safe to call multiple times and in
    single-process runs (no-op)."""
    global _initialized
    import jax

    if _initialized:
        return True
    config = config or MultiHostConfig.from_env()
    if not config.is_configured():
        return False
    jax.distributed.initialize(
        coordinator_address=config.coordinator_address,
        num_processes=config.num_processes,
        process_id=config.process_id,
    )
    _initialized = True
    return True


def is_multihost() -> bool:
    import jax

    return jax.process_count() > 1


def is_primary() -> bool:
    """True on the process that should own shared-filesystem writes —
    checkpoint payloads, manifests, and retention deletes
    (resilience/checkpoint.py): N processes writing the same manager
    directory would race the atomic renames. Env-first so the query NEVER
    initializes a backend (a chip belongs to one process: a process that
    only manages checkpoints must not become its owner by asking); an
    unconfigured single-process run is always primary."""
    pid = _int_env(PROCESS_ID_ENV)
    if pid is not None:
        return pid == 0
    try:
        # private probe (same one __graft_entry__ uses): ONLY safe way to
        # ask "is a backend up" without initializing one
        from jax._src import xla_bridge as _xb

        initialized = _xb.backends_are_initialized()
    except Exception:  # jax moved the symbol: fall through to the query —
        # every caller (CheckpointManager.save) runs after training steps
        # have already initialized the backend, so this cannot hang
        initialized = True
    if initialized:
        import jax

        return jax.process_index() == 0
    return True


def process_info() -> dict:
    import jax

    return {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "local_device_count": jax.local_device_count(),
        "global_device_count": jax.device_count(),
    }


def local_batch_slice(global_batch: int,
                      process_count: Optional[int] = None,
                      process_index: Optional[int] = None) -> slice:
    """Each process feeds only its shard of the global batch
    (jax.make_array_from_process_local_data pattern): process i gets the
    i-th contiguous slice.

    Raises a LOUD ValueError (consistently on EVERY process) when the
    global batch does not split evenly across the live processes —
    silently truncating the tail would drop examples, and an uneven
    split would make the divisibility check in ParallelWrapper pass on
    some processes and fail on others, turning a clean ValueError into a
    distributed deadlock (the surviving processes would block forever in
    the first collective waiting for the dead peer). This same rule
    gates the elastic fleet's round partitioning
    (parallel/fleet.ElasticParameterAveragingTrainer), which is why the
    LIVE membership can be passed explicitly: ``process_count`` /
    ``process_index`` override the jax.distributed topology (and, being
    env-free and jax-free, never initialize a backend), so a
    coordinator re-forming rounds over a survivor set applies
    the identical divisibility contract."""
    if process_count is None:
        import jax

        process_count = jax.process_count()
        process_index = jax.process_index()
    elif process_index is None:
        raise ValueError("process_index is required with process_count")
    if not 0 <= process_index < process_count:
        raise ValueError(
            f"process_index {process_index} outside [0, {process_count})")
    from deeplearning4j_tpu.parallel.training_master import balanced_splits

    if global_batch % process_count != 0:
        raise ValueError(
            f"global batch {global_batch} not divisible by "
            f"{process_count} live processes — pad or trim so every "
            "process feeds an equal shard; a silent tail truncation "
            "would drop examples (static shapes keep the step compiled "
            "once)")
    return balanced_splits(global_batch, process_count)[process_index]


def put_batch(array, sharding):
    """Place one training batch under `sharding`, transparently handling
    multi-process runs: single-process -> plain device_put; multi-process
    -> the array is this process's LOCAL shard of the global batch
    (each host feeds only the examples it loaded — the reference's Spark
    executors each feeding their partition of the RDD<DataSet>,
    SURVEY.md section 2.3) and the global array is assembled without any
    cross-host data movement via make_array_from_process_local_data.

    device_put would reject this: under multi-process JAX it requires the
    SAME value on every process (verified in the round-4 2-process CPU
    harness — tests/test_multihost_cpu.py)."""
    import jax
    import jax.numpy as jnp

    array = jnp.asarray(array)
    if jax.process_count() > 1:
        return jax.make_array_from_process_local_data(sharding, array)
    return jax.device_put(array, sharding)
