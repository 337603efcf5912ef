"""Host spans of the serving path, in the profiler's own trace.

``span(name, **ids)`` is a ``jax.profiler.TraceAnnotation`` named
``repro.<name>``: under ``jax.profiler.trace`` it lands on the profiler's
host plane, on the same clock as the device's program runs and ops, so a
gap in the device's work can be put down to what the host was doing then.
With the profiler off it costs about a microsecond.

The spans nest by layer::

    scheduler.step
        scheduler.admit (rid)            engine.reset_lane (slot)
        scheduler.prefill                engine.prefill_chunk (slot, rid,
                                           tokens, bucket)
                                         engine.first_token (slot, rid)
                                             engine.fetch
        scheduler.decode                 engine.step
                                             engine.fetch
    engine.draft / engine.verify / engine.repair    (speculative decode)

Names are constants; request and slot ids are keyword arguments, so the
spans of one request share ``rid`` (-1 where the engine was not given one).
A span never waits for the device, transfers or changes control flow.
"""
from __future__ import annotations

import jax

__all__ = ["PREFIX", "span"]

PREFIX = "repro."


def span(name: str, **ids: int) -> jax.profiler.TraceAnnotation:
    """The host span ``repro.<name>``, carrying ``ids`` as its arguments."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **ids)
