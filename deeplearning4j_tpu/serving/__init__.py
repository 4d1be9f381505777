"""Production serving engine — the subsystem the reference's one-record
Camel route (dl4j-streaming/.../routes/DL4jServeRouteBuilder.java: load a
serialized model, run output() per incoming record) never grew into.

On TPU the per-record route is the inference-time twin of the op-by-op
dispatch gap SURVEY §3.1 identifies at training time: every request pays a
full device dispatch (its cost on the attached chip: not measured) for a
batch-1 program, so the chip idles while requests queue. This
package concentrates the counter-measures:

  batcher.py    DynamicBatcher — bounded request queue coalescing
                concurrent /predict requests into bucket-shaped batches
                (ops/dispatch.bucket_size, so the steady state is
                zero-retrace), flushing on deadline or bucket-full, with
                backpressure (429 past capacity) and per-request timeouts.
  decode.py     ContinuousDecoder — continuous-batching LM decode over a
                fixed KV-cache slot pool: finished sequences are evicted
                and queued prompts admitted mid-loop, so /generate
                throughput no longer quantizes to the slowest sequence of
                a static batch.
  paged.py      PagedDecoder — the block-pool /generate plane (ISSUE 11):
                one device-resident KV block arena with per-request block
                tables gathered inside the jitted tick, admission gated
                by free-block count, refcounted prefix caching, youngest-
                victim preemption, per-token streaming callbacks, and
                SLO-class scheduling (slo.py). Default via
                DL4J_TPU_SERVE_KV_BLOCK; =0 falls back to decode.py.
  slo.py        SLOClass/parse_slo_classes — jax-free scheduling classes
                (per-class deadlines + priority order + shed policy) for
                the paged admission loop.
  registry.py   ModelRegistry — named/versioned load → warmup → serve →
                unload lifecycle (warmup pre-compiles the bucket set
                before a model takes traffic; unload frees device
                buffers). The ModelSerializer zip (reference
                ModelSerializer.java:70-110) is the interchange format.
  telemetry.py  ServingStats — p50/p95/p99 latency, queue depth,
                batch-fill ratio, per-model dispatch_stats, exposed at
                /metrics.
  engine.py     ServingEngine — the stdlib-HTTP front door wiring the
                four together (/predict, /generate, /metrics, /health,
                /models).
  resilience.py the failure plane (ISSUE 8): per-model CircuitBreaker
                (SERVING -> DEGRADED -> BROKEN with half-open probe
                recovery; open == fast-fail 503 + Retry-After) and the
                InferenceWatchdog that detects a hung device call
                (~0 CPU, no error), fails the in-flight futures with a diagnosis and
                replaces the wedged worker. Graceful drain + SIGTERM
                wiring live on the engine; deterministic fault injection
                in resilience/chaos.ServingChaosConfig.

  router.py     FleetRouter — the health-routed front door over N
                replicas (ISSUE 12): membership from the PR 6 board,
                replica-level circuit breakers (eject on connect/5xx,
                half-open re-admit), retry-on-survivor for idempotent
                /predict, fleet-wide SLO shed, rolling rollout with
                auto-rollback.
  fleet.py      ServingFleet / run_replica — replica lifecycle: N
                in-process engines or OS processes, each heartbeating
                the membership board; SIGTERM -> engine drain ->
                deregister goodbye; hard kill -> heartbeat expiry.
  autoscale.py  FleetAutoscaler — the control loop over the fleet
                (ISSUE 20): scrape /signals each tick, decide up/down/
                hold from queue depth, per-class p99 vs deadline, and
                shed-rate evidence (pure tick-counted decisions — a
                recorded run replays bit-exact), enact through the
                fleet's add_replica/depart_replica hooks.
  placement.py  ModelFootprint/pack_models/PlacementPlan — HBM-aware
                first-fit-decreasing model placement priced by the
                ops/memory AOT accounting; the router's affinity filter
                and /placement endpoint consume the plan.

streaming/serving.py's ModelServer remains the compatibility surface: a
thin subclass of ServingEngine with the original single-model contract.
"""

from deeplearning4j_tpu.serving.batcher import (
    DynamicBatcher,
    QueueFullError,
    RequestTimeoutError,
)
from deeplearning4j_tpu.serving.engine import ServingEngine
from deeplearning4j_tpu.serving.registry import ModelRegistry
from deeplearning4j_tpu.serving.resilience import (
    BreakerOpenError,
    CircuitBreaker,
    ClientRequestError,
    DrainingError,
    InferenceWatchdog,
    ModelWedgedError,
    WorkerDeadError,
)
from deeplearning4j_tpu.serving.slo import (
    SLOClass,
    TenantBucket,
    TenantQuota,
    parse_slo_classes,
    parse_tenant_quotas,
)
from deeplearning4j_tpu.serving.telemetry import ServingStats

__all__ = [
    "BreakerOpenError",
    "CircuitBreaker",
    "ClientRequestError",
    "ContinuousDecoder",
    "DrainingError",
    "DynamicBatcher",
    "FleetAutoscaler",
    "InferenceWatchdog",
    "FleetRouter",
    "ModelFootprint",
    "ModelRegistry",
    "ModelWedgedError",
    "PagedDecoder",
    "PlacementPlan",
    "RouterStats",
    "ScaleConfig",
    "ServingFleet",
    "QueueFullError",
    "RequestTimeoutError",
    "SLOClass",
    "ServingEngine",
    "ServingStats",
    "TenantBucket",
    "TenantQuota",
    "WorkerDeadError",
    "model_footprint",
    "pack_models",
    "parse_slo_classes",
    "parse_tenant_quotas",
]


def __getattr__(name):
    # ContinuousDecoder/PagedDecoder resolve lazily (PEP 562): they pull
    # the whole models/transformer stack, which non-LM servers (and the
    # bench's serving subprocess) never need — engine.py defers the same
    # import into _decoder_for for the same reason.
    if name == "ContinuousDecoder":
        from deeplearning4j_tpu.serving.decode import ContinuousDecoder

        return ContinuousDecoder
    if name == "PagedDecoder":
        from deeplearning4j_tpu.serving.paged import PagedDecoder

        return PagedDecoder
    # the fleet tier (ISSUE 12) resolves lazily too: a single-engine
    # server never needs the router/membership plumbing
    if name in ("FleetRouter", "RouterStats"):
        from deeplearning4j_tpu.serving import router as _router

        return getattr(_router, name)
    if name == "ServingFleet":
        from deeplearning4j_tpu.serving.fleet import ServingFleet

        return ServingFleet
    if name in ("FleetAutoscaler", "ScaleConfig"):
        from deeplearning4j_tpu.serving import autoscale as _autoscale

        return getattr(_autoscale, name)
    if name in ("ModelFootprint", "PlacementPlan", "model_footprint",
                "pack_models"):
        from deeplearning4j_tpu.serving import placement as _placement

        return getattr(_placement, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
