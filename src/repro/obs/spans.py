"""Host spans of the serving program, on the profiler's clock.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation`` named
``pool.<name>``: while a profiler trace runs it records the span (and its
integer or string ``args``) on the calling thread, on the same clock as
the device's operations, so an idle gap on the device can be put down to
what the host was doing.  With no trace running it costs one small object
(well under a microsecond), so spans stay on in production.  Spans sit at
block or call granularity: never per lane, never per event.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

__all__ = ["SPAN_PREFIX", "span"]

SPAN_PREFIX = "pool."


def span(name: str, **args) -> TraceAnnotation:
    """The context manager of the host span ``pool.<name>``."""
    return TraceAnnotation(SPAN_PREFIX + name, **args)
