"""Host spans in the profiler's own trace.

    from repro import obs

    with obs.span("batcher.tick", tick=7):
        ...

A span is a ``jax.profiler.TraceAnnotation``.  While a profiler trace
runs (``jax.profiler.start_trace``) it lands on the trace's ``/host:CPU``
plane, on the same clock as the ``/device:*`` planes, with ``attrs`` as
its stats; the profiler keeps it in memory and writes it out when the
trace stops.  With no trace running it costs its construction, about a
microsecond, so spans mark phases and never sit inside a per-item loop.
No sink sees a span: the event bus (``obs.bus``) carries counters, the
profiler carries time.

``SPAN_NAMES`` lists every span the program opens (docs/OBS.md).
"""
from __future__ import annotations

__all__ = ["SPAN_NAMES", "span"]

SPAN_NAMES = (
    "batcher.tick",      # the whole of ContinuousBatcher.step
    "batcher.plan",      # the admitted batch shapes' plans
    "batcher.pages",     # page claims, their page-table writes, preemption
    "batcher.feed",      # the tick's token feed, built and sent
    "batcher.dispatch",  # the decode or chunk program's (async) call
    "batcher.sync",      # the wait for the program's next tokens
    "batcher.retire",    # events, per-slot bookkeeping, completions
    "batcher.admit",     # queued requests into free slots, slot resets
)


def span(name: str, **attrs):
    """A context manager that records ``name`` over its body, with
    ``attrs`` (numbers or strings without commas) as the span's stats.
    JAX is imported here, not with the package, so that the report CLI
    never loads it."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name, **attrs)
