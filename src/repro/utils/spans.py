"""Named host spans on the profiler's clock.

``span("plan", seed=7)`` is a ``jax.profiler.TraceAnnotation`` called
``repro.plan`` with the stats ``seed=7``. It is recorded only while a
profiler trace is being taken (``jax.profiler.start_trace``,
``jax.profiler.trace`` or a TensorBoard capture), on the same clock as the
device's operations; otherwise entering and leaving it costs about half a
microsecond. A span reads no clock of its own and hands nothing back, so no
result of the program depends on it. Use it in host code only, never inside
a traced function (``jax.named_scope`` names the operations of a compiled
program).
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

__all__ = ["PREFIX", "span"]

PREFIX = "repro."


def span(name: str, **args) -> TraceAnnotation:
    """A host span called ``repro.<name>``; ``args`` (ints or strings known
    on entry) ride on it as stats."""
    return TraceAnnotation(PREFIX + name, **args)
