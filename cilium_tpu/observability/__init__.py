"""Runtime self-telemetry: the system watching itself.

Hubble (hubble/) made the *traffic* observable; this package makes the
*agent* observable — the TPU analog of the reference's
pkg/metrics/metrics.go policy-revision and map-pressure series plus a
lightweight span tracer for control-plane causality:

- ``tracer``       — bounded in-memory span tracing with explicit
                     context propagation (daemon -> kvstore ->
                     verdict_service/relay), served at /debug/traces
                     and ``cilium-tpu trace``.
- ``propagation``  — policy-propagation latency: every repository
                     revision's journey import -> compile -> device
                     apply -> first verdict, as the
                     ``policy_implementation_delay_seconds`` histogram
                     plus a per-revision span tree.
- ``jitstats``     — JIT/compile telemetry (compile count/seconds,
                     persistent-cache hit/miss, live device bytes)
                     counted from JAX's own compile events.
- ``stages``       — host-timed pipeline stage slices and blocking
                     boundaries, exported as histograms and
                     ``pipeline_report()``; ``stage()`` also puts the
                     slice on a ``jax.profiler`` trace, and garbage
                     collections are timed by generation.
- ``pressure``     — map-pressure gauges + warning thresholds for
                     every device table (pkg/metrics BPFMapPressure
                     analog).
- ``events``       — the incident flight recorder: a bounded ring of
                     structured degraded-condition transitions
                     (supervisor/breaker/overload/kvstore/drift),
                     served at /debug/events and ``cilium-tpu
                     events``.
- ``slo``          — the serving SLO tier: per-lane latency
                     objectives, deadline-budget burn rates, and
                     queue-depth flight samples
                     (``serving_slo_*`` series).
"""

from .tracer import Span, SpanContext, Tracer, tracer
from .propagation import (POLICY_IMPLEMENTATION_DELAY,
                          PolicyPropagationTracker)
from .jitstats import JitTelemetry, jit_telemetry
from .stages import (NO_SPAN, PIPELINE_STAGE_SECONDS, pipeline_report,
                     record_stage, stage)
from .pressure import MAP_PRESSURE, compute_pressure
from .events import (DEGRADED_SIGNALS, EVENT_TYPES, FlightEvent,
                     FlightRecorder, recorder)
from .slo import SLOTracker, slo_tracker

__all__ = [
    "Span", "SpanContext", "Tracer", "tracer",
    "POLICY_IMPLEMENTATION_DELAY", "PolicyPropagationTracker",
    "JitTelemetry", "jit_telemetry",
    "NO_SPAN", "PIPELINE_STAGE_SECONDS", "pipeline_report",
    "record_stage", "stage",
    "MAP_PRESSURE", "compute_pressure",
    "DEGRADED_SIGNALS", "EVENT_TYPES", "FlightEvent",
    "FlightRecorder", "recorder",
    "SLOTracker", "slo_tracker",
]
