"""Measured-win gate for pallas kernels (the CLAUDE.md rent rule made
mechanical: default-on decided by the committed on-chip artifact, not just
VMEM fit).

PALLAS_BENCH.json (repo root) is written by the on-chip benches
(benchmarks/pallas_lstm_bench.py, bench.py ring/flash legs). A kernel may
engage BY DEFAULT only when the artifact records it beating its XLA twin;
VMEM-fit checks remain a necessary condition on top. Explicit opt-in
(use_flash=True, DL4J_TPU_PALLAS_FORCE=1) bypasses the win check but never
the fit check.
"""

from __future__ import annotations

import json
import os
import threading
from typing import Optional

from deeplearning4j_tpu.ops import env as envknob

_ARTIFACT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "..", "..", "PALLAS_BENCH.json")
_lock = threading.Lock()
_cache: Optional[dict] = None


def _load() -> dict:
    global _cache
    with _lock:
        if _cache is None:
            try:
                with open(_ARTIFACT) as f:
                    _cache = json.load(f)
            except (OSError, ValueError):
                _cache = {}
        return _cache


def reload() -> None:
    """Drop the cached artifact (tests; after a bench writes new rows)."""
    global _cache
    with _lock:
        _cache = None


def measured_win(group: str, name: str, *, min_speedup: float = 1.0,
                 default: bool = False) -> bool:
    """True when PALLAS_BENCH.json records `group.name.speedup` >=
    min_speedup on a real chip. `default` is the answer when no row
    exists: new kernels ship default-OFF until the artifact proves them."""
    if envknob.raw("DL4J_TPU_PALLAS_FORCE") == "1":
        return True
    row = _load().get(group, {}).get(name)
    if not isinstance(row, dict) or "speedup" not in row:
        return default
    if row.get("backend") == "cpu" or row.get("interpret"):
        return default  # only real-chip rows count as proof
    return float(row["speedup"]) >= min_speedup


def _merge(mutate) -> None:
    """Atomic read-mutate-replace of the artifact under the module lock."""
    with _lock:
        try:
            with open(_ARTIFACT) as f:
                data = json.load(f)
        except (OSError, ValueError):
            data = {}
        mutate(data)
        tmp = _ARTIFACT + ".tmp"
        with open(tmp, "w") as f:
            json.dump(data, f, indent=1, sort_keys=True)
        os.replace(tmp, _ARTIFACT)
    reload()


def record_win(group: str, name: str, row: dict) -> None:
    """Merge one bench result into PALLAS_BENCH.json, preserving unrelated
    groups/rows."""
    _merge(lambda data: data.setdefault(group, {}).__setitem__(name, row))
