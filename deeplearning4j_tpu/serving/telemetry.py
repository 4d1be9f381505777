"""Serving telemetry: the counters a production endpoint is judged by.

The reference's serving route has no metrics at all (the Camel route in
DL4jServeRouteBuilder.java just transforms bodies); its training side got
them through IterationListener / Spark stats (StatsUtils.java:65). Serving
needs the inference-side equivalents — latency percentiles, queue depth,
batch-fill ratio — because the dynamic batcher trades a bounded amount of
per-request latency (the max-wait window) for dispatch amortization, and
only these numbers show whether the trade is paying.

Latencies are kept in a fixed-size ring (last ``window`` observations) so
the percentiles track the RECENT regime — a device hiccup an hour ago must
not pollute this minute's p99 forever — and memory stays bounded under
heavy traffic.
"""

from __future__ import annotations

import threading
from collections import deque
from typing import Any, Dict, Optional

import numpy as np


class ServingStats:
    """Thread-safe serving counters + latency reservoir.

    batch-fill ratio: real rows / (real + pad) rows over all batches the
    batcher dispatched — 1.0 means every dispatched program was full of
    real work; low values mean the max-wait window is flushing nearly
    empty buckets (raise max_wait_ms or traffic).
    """

    def __init__(self, window: int = 2048) -> None:
        self._lock = threading.Lock()
        self._lat = deque(maxlen=int(window))
        # optional bucket-histogram sink (obs/registry.py): the engine
        # wires completed-request latencies into the central
        # MetricsRegistry so the Prometheus scrape gets real cumulative
        # buckets, not just the ring percentiles. Called OUTSIDE the
        # lock (the registry has its own).
        self.on_latency = None
        self.requests = 0          # submitted to the engine
        self.completed = 0         # answered successfully
        self.errors = 0            # model/payload errors
        self.rejected = 0          # backpressure (HTTP 429)
        self.timeouts = 0          # per-request deadline expired (504)
        self.batches = 0           # batcher dispatches
        self.batched_rows = 0      # real rows across all batches
        self.padded_rows = 0       # pad rows across all batches
        self.generated_tokens = 0  # continuous-decode output tokens
        # -- resilience plane (serving/resilience.py): the counters the
        # breaker/watchdog/drain paths are judged by — exported through
        # the central MetricsRegistry like every other field here
        self.breaker_opens = 0     # SERVING/DEGRADED -> BROKEN transitions
        self.breaker_closes = 0    # successful half-open probe recoveries
        self.breaker_probes = 0    # half-open probe requests admitted
        self.fast_fails_503 = 0    # requests shed by an open breaker
        self.wedged_batches = 0    # watchdog-expired in-flight dispatches
        self.watchdog_restarts = 0  # worker threads replaced after a wedge
        self.worker_deaths = 0     # worker threads dead from uncaught error
        self.slot_crashes = 0      # decode slots evicted by a crash
        self.load_failures = 0     # registry.load exceptions (isolated)
        self.warmup_failures = 0   # registry.warmup exceptions (isolated)
        self.drains_started = 0    # graceful drains begun (stop/SIGTERM)
        self.drains_completed = 0  # drains that emptied the queues in time
        # -- paged KV plane (serving/paged.py): arena occupancy gauges,
        # prefix-cache effectiveness, and the scheduler's preempt/shed
        # decisions — the numbers the block-pool trade is judged by
        self.kv_blocks_total = 0   # arena size (allocatable blocks)
        self.kv_blocks_in_use = 0  # gauge: blocks held by lanes + cache
        self.prefix_lookups = 0    # prompt blocks consulted in the cache
        self.prefix_hits = 0       # prompt blocks served from the cache
        self.preemptions = 0       # lanes evicted-and-requeued (OOB arena)
        # -- prefill/decode disaggregation (serving/mesh role handoff):
        # exported prefill dispatches and blocks adopted sight-unseen
        self.prefix_exports = 0        # /prefill export dispatches run
        self.prefix_imports = 0        # /prime adoptions applied
        self.prefix_import_blocks = 0  # blocks adopted across adoptions
        # -- speculative decode (serving/speculate.py): draft-k-then-
        # verify accounting — acceptance_rate (accepted/proposed) is the
        # number the draft model's cost trade is judged by
        self.draft_proposed = 0    # draft tokens proposed to the target
        self.draft_accepted = 0    # proposals the target agreed with
        self.draft_rejected = 0    # proposals the target overruled
        self.shed_by_class: Dict[str, int] = {}  # 429s per SLO class
        # per-component depths (batcher rows / decode pending prompts):
        # one shared last-writer-wins field would let an idle component
        # overwrite the backlog the other is about to 429 on
        self.queue_depths: Dict[str, int] = {}

    # -- recording --------------------------------------------------------
    def record_request(self) -> None:
        with self._lock:
            self.requests += 1

    def record_latency(self, seconds: float) -> None:
        with self._lock:
            self.completed += 1
            self._lat.append(float(seconds))
        hook = self.on_latency
        if hook is not None:
            hook(float(seconds))

    def record_error(self) -> None:
        with self._lock:
            self.errors += 1

    def record_rejected(self) -> None:
        with self._lock:
            self.rejected += 1

    def record_timeout(self) -> None:
        with self._lock:
            self.timeouts += 1

    def record_batch(self, real_rows: int, padded_to: int) -> None:
        with self._lock:
            self.batches += 1
            self.batched_rows += int(real_rows)
            self.padded_rows += int(padded_to) - int(real_rows)

    def record_tokens(self, n: int) -> None:
        with self._lock:
            self.generated_tokens += int(n)

    # -- resilience plane --------------------------------------------------
    def record_breaker_open(self) -> None:
        with self._lock:
            self.breaker_opens += 1

    def record_breaker_close(self) -> None:
        with self._lock:
            self.breaker_closes += 1

    def record_breaker_probe(self) -> None:
        with self._lock:
            self.breaker_probes += 1

    def record_fast_fail(self) -> None:
        with self._lock:
            self.fast_fails_503 += 1

    def record_wedged(self) -> None:
        with self._lock:
            self.wedged_batches += 1

    def record_watchdog_restart(self) -> None:
        with self._lock:
            self.watchdog_restarts += 1

    def record_worker_death(self) -> None:
        with self._lock:
            self.worker_deaths += 1

    def record_slot_crash(self) -> None:
        with self._lock:
            self.slot_crashes += 1

    def record_load_failure(self) -> None:
        with self._lock:
            self.load_failures += 1

    def record_warmup_failure(self) -> None:
        with self._lock:
            self.warmup_failures += 1

    def record_drain(self, completed: bool) -> None:
        with self._lock:
            self.drains_started += 1
            if completed:
                self.drains_completed += 1

    # -- paged KV plane ----------------------------------------------------
    def set_kv_blocks(self, in_use: int, total: int) -> None:
        with self._lock:
            self.kv_blocks_in_use = int(in_use)
            self.kv_blocks_total = int(total)

    def record_prefix(self, hits: int, lookups: int) -> None:
        with self._lock:
            self.prefix_hits += int(hits)
            self.prefix_lookups += int(lookups)

    def record_preemption(self) -> None:
        with self._lock:
            self.preemptions += 1

    def record_prefix_export(self) -> None:
        with self._lock:
            self.prefix_exports += 1

    def record_prefix_import(self, blocks: int) -> None:
        with self._lock:
            self.prefix_imports += 1
            self.prefix_import_blocks += int(blocks)

    def record_draft(self, proposed: int, accepted: int) -> None:
        """One speculative round's verdict: ``proposed`` draft tokens
        scored by the target, of which ``accepted`` matched the target's
        own greedy choice (the Leviathan et al. longest-prefix rule)."""
        with self._lock:
            self.draft_proposed += int(proposed)
            self.draft_accepted += int(accepted)
            self.draft_rejected += int(proposed) - int(accepted)

    def record_shed(self, slo_class: str) -> None:
        with self._lock:
            self.shed_by_class[slo_class] = \
                self.shed_by_class.get(slo_class, 0) + 1

    def set_queue_depth(self, depth: int,
                        component: str = "batcher") -> None:
        with self._lock:
            self.queue_depths[component] = int(depth)

    # -- reading ----------------------------------------------------------
    def latency_ms(self) -> Dict[str, Optional[float]]:
        """p50/p95/p99 of the recent-latency ring, in milliseconds."""
        with self._lock:
            # graftlint: disable=host-sync-under-lock -- self._lat is a host-side deque of floats; no device buffer ever enters this ring
            lat = np.asarray(self._lat, np.float64)
        if lat.size == 0:
            return {"p50": None, "p95": None, "p99": None, "count": 0}
        return {
            "p50": round(float(np.percentile(lat, 50)) * 1e3, 3),
            "p95": round(float(np.percentile(lat, 95)) * 1e3, 3),
            "p99": round(float(np.percentile(lat, 99)) * 1e3, 3),
            "count": int(lat.size),
        }

    def batch_fill_ratio(self) -> Optional[float]:
        with self._lock:
            total = self.batched_rows + self.padded_rows
            if total == 0:
                return None
            return round(self.batched_rows / total, 4)

    def snapshot(self) -> Dict[str, Any]:
        lat = self.latency_ms()
        with self._lock:
            out = {
                "requests": self.requests,
                "completed": self.completed,
                "errors": self.errors,
                "rejected_429": self.rejected,
                "timeouts": self.timeouts,
                "batches": self.batches,
                "batched_rows": self.batched_rows,
                "padded_rows": self.padded_rows,
                "generated_tokens": self.generated_tokens,
                "breaker_opens": self.breaker_opens,
                "breaker_closes": self.breaker_closes,
                "breaker_probes": self.breaker_probes,
                "fast_fails_503": self.fast_fails_503,
                "wedged_batches": self.wedged_batches,
                "watchdog_restarts": self.watchdog_restarts,
                "worker_deaths": self.worker_deaths,
                "slot_crashes": self.slot_crashes,
                "load_failures": self.load_failures,
                "warmup_failures": self.warmup_failures,
                "drains_started": self.drains_started,
                "drains_completed": self.drains_completed,
                "kv_blocks_total": self.kv_blocks_total,
                "kv_blocks_in_use": self.kv_blocks_in_use,
                "prefix_lookups": self.prefix_lookups,
                "prefix_hits": self.prefix_hits,
                "preemptions": self.preemptions,
                "prefix_exports": self.prefix_exports,
                "prefix_imports": self.prefix_imports,
                "prefix_import_blocks": self.prefix_import_blocks,
                "draft_proposed": self.draft_proposed,
                "draft_accepted": self.draft_accepted,
                "draft_rejected": self.draft_rejected,
                "acceptance_rate": (
                    round(self.draft_accepted / self.draft_proposed, 4)
                    if self.draft_proposed else None),
                "shed_by_class": dict(self.shed_by_class),
                "queue_depth": sum(self.queue_depths.values()),
                "queue_depths": dict(self.queue_depths),
            }
        out["latency_ms"] = lat
        out["batch_fill_ratio"] = self.batch_fill_ratio()
        return out
