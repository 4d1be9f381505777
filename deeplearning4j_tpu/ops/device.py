"""What the attached device is: its platform, its published peaks, its
memory.

One table of per-chip peaks keyed by ``jax.Device.device_kind``, with the
source of every figure. bench.py's utilization denominators, the HBM
sizers (ops/memory.py) and chip_smoke.py all read it; a device that is not
in the table is an error, never a default — a utilization computed
against the wrong chip's peak is worse than none.

Policy code (donation, fusion, kernel gates) asks :func:`platform` what
the backend is instead of parsing ``jax.config.jax_platforms``: on a
machine with a chip that config is unset and the backend is the only
thing that knows.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax

#: Published per-chip peaks. Source for "TPU v5 lite" (the device_kind jax
#: reports for a v5e chip): Google Cloud documentation, "TPU v5e" —
#: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s.
PEAKS: Dict[str, Dict[str, Any]] = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_gb": 16.0,
        "hbm_bytes_per_s": 819e9,
        "source": 'Google Cloud documentation, "TPU v5e"',
    },
}

#: The chip the sizers plan for when no chip is attached (CPU tests,
#: serving/placement.py bin-packing): its ``hbm_gb`` is the planning
#: budget unless DL4J_TPU_HBM_GB overrides it.
PLANNING_KIND = "TPU v5 lite"


def peaks(device_kind: Optional[str] = None) -> Dict[str, Any]:
    """The peaks row for ``device_kind`` (default: the first device's).
    Raises ValueError for a device that is not in the table."""
    if device_kind is None:
        device_kind = jax.devices()[0].device_kind
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"device_kind {device_kind!r} is not in the peaks table "
            f"(deeplearning4j_tpu/ops/device.py knows {sorted(PEAKS)}); "
            "add its published peaks with their source before measuring "
            "on it") from None


def platform() -> str:
    """The platform computations land on: the ``jax.default_device``
    override when one is active (the equivalence harness runs its CPU
    legs that way on a TPU host), the default backend otherwise."""
    dd = jax.config.jax_default_device
    if dd is not None:
        return dd if isinstance(dd, str) else dd.platform
    return jax.default_backend()


def on_tpu() -> bool:
    return platform() == "tpu"


def hbm_bytes_limit() -> Optional[int]:
    """``memory_stats()["bytes_limit"]`` of the first device when the
    backend is a TPU (a little under the published 16 GiB: the runtime
    keeps its own reserve), None on any other platform."""
    if not on_tpu():
        return None
    return int(jax.devices()[0].memory_stats()["bytes_limit"])
