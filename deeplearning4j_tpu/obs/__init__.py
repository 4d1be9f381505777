"""Unified observability plane: spans, one metrics registry, flight
recorder, Prometheus export.

The reference's observability spine is the IterationListener chain
(deeplearning4j-core/.../optimize/api/IterationListener.java) feeding
the UI/stats plane (deeplearning4j-ui-parent, dl4j-spark stats). This
package is its TPU-native growth: the five existing telemetry ledgers
(dispatch/memory/pipeline/resilience/serving) register into ONE
:class:`MetricsRegistry`; a default-off span tracer (``DL4J_TPU_OBS``)
correlates them across subsystems; a bounded flight-recorder journal
survives preemption; Prometheus text exposition is served by both the
serving engine's ``/metrics`` and the standalone training
:class:`MetricsExporter`.

Everything here is host-side and imports only the stdlib — no device
syncs (the listener-chain bulk-readback rule). The one touch of jax: where
the process has ALREADY loaded it, a live span is also a
``jax.profiler.TraceAnnotation`` (obs/trace.py), so a profiler session
sees the program's spans on the device trace's clock.
"""

from deeplearning4j_tpu.obs.exporter import MetricsExporter
from deeplearning4j_tpu.obs.journal import (
    FlightRecorder,
    default_journal,
    default_journal_path,
)
from deeplearning4j_tpu.obs.registry import (
    MetricsRegistry,
    default_registry,
    register_net,
)
from deeplearning4j_tpu.obs.trace import (
    ENV_OBS,
    Span,
    Tracer,
    close_span,
    obs_enabled,
    open_span,
    record_span,
    set_enabled,
    span,
    tracer,
)

__all__ = [
    "ENV_OBS",
    "FlightRecorder",
    "MetricsExporter",
    "MetricsRegistry",
    "Span",
    "Tracer",
    "close_span",
    "default_journal",
    "default_journal_path",
    "default_registry",
    "obs_enabled",
    "open_span",
    "record_span",
    "register_net",
    "set_enabled",
    "span",
    "tracer",
]
