"""Observability bus: streaming planner provenance, SPMD comm health, and
trainer/serving metrics.

    from repro import obs

    with obs.session(obs.JsonlSink("run.jsonl")):
        trainer.train(...)      # plan-cache, fallback, step events stream

    python -m repro.obs.report run.jsonl

The repo's measured-vs-predicted discipline runs *offline* in
``repro.measure`` and ``repro.analyze``; this package is the same
discipline online: typed events (``obs.events``) emitted at the natural
seams of the launch path, the trainer, the batcher, and the validator,
delivered to pluggable sinks (``obs.sinks``) through an ambient nestable
session (``obs.bus``) that mirrors ``api.plan_context``.  The default
sink is a ``NullSink`` and producers gate on ``obs.enabled()``, so an
uninstrumented process pays nothing.  Host spans (``obs.span``) go to
the JAX profiler's trace instead, on the device trace's clock
(``obs.spans``).  See docs/OBS.md.
"""
from repro.obs.bus import (
    current_sinks,
    emit,
    enabled,
    reset_default_sinks,
    session,
    set_default_sinks,
)
from repro.obs.events import (
    EVENT_KINDS,
    AdmissionEvent,
    BatcherTickEvent,
    CheckpointEvent,
    DegradedEvent,
    Event,
    LbmPathEvent,
    MeshChangeEvent,
    PagePoolEvent,
    PlanEvent,
    PreemptionEvent,
    ProfileDriftEvent,
    RequestAbandonedEvent,
    ResumeEvent,
    SpmdFallbackEvent,
    SpmdOverrideShadowEvent,
    TrainStepEvent,
    ValidationEvent,
)
from repro.obs.sinks import (
    JsonlSink,
    LoggingSink,
    NullSink,
    RingBufferSink,
    Sink,
)
from repro.obs.spans import SPAN_NAMES, span

__all__ = [
    "session", "emit", "enabled", "current_sinks",
    "set_default_sinks", "reset_default_sinks",
    "Sink", "NullSink", "RingBufferSink", "JsonlSink", "LoggingSink",
    "Event", "PlanEvent", "SpmdFallbackEvent", "SpmdOverrideShadowEvent",
    "ValidationEvent", "TrainStepEvent", "CheckpointEvent",
    "AdmissionEvent", "BatcherTickEvent", "PagePoolEvent",
    "PreemptionEvent", "RequestAbandonedEvent", "ProfileDriftEvent",
    "MeshChangeEvent", "ResumeEvent", "DegradedEvent", "LbmPathEvent",
    "EVENT_KINDS", "SPAN_NAMES", "span",
]
