"""Wall-clock spans on the profiler's timeline.

The program's host phases (the fleet screen's tick, the screening
backend's device reads, the trainer's step) are marked with
:func:`span` and :func:`step`. Each returns a ``jax.profiler``
``TraceAnnotation`` / ``StepTraceAnnotation``, so a span lands in the same
``.xplane.pb`` timeline as the device ops and takes no timestamp of its
own: the profiler records it while a session is active
(``jax.profiler.start_trace`` / ``trace``), and it costs about a
microsecond otherwise. There is no flag: tracing is on exactly when a
profiler session is.

This is the wall-clock half of the observability layer; the simulated
clock is :class:`repro.obs.tracer.SpanTracer`'s. Modules that run without
JAX (``repro.core``, ``repro.controlplane``) may use spans too: in a
process that has not imported JAX no profiler can be active, so a span is
a shared no-op context and JAX is never imported on its account.
"""
from __future__ import annotations

import contextlib
import sys

_OFF = contextlib.nullcontext()
#: ``(TraceAnnotation, StepTraceAnnotation)`` once JAX is imported
_annotations: tuple | None = None


def _resolve() -> tuple | None:
    global _annotations
    jax = sys.modules.get("jax")
    profiler = getattr(jax, "profiler", None)
    if profiler is None:  # JAX not imported (or still importing)
        return None
    _annotations = (profiler.TraceAnnotation, profiler.StepTraceAnnotation)
    return _annotations


def span(name: str, **ids):
    """A context manager marking one host phase ``name``; ``ids`` (the tick
    or step number) ride along as the event's stats."""
    ann = _annotations or _resolve()
    if ann is None:
        return _OFF
    return ann[0](name, **ids)


def step(name: str, step_num: int):
    """A context manager marking one whole step, as the profiler's step
    marker (``StepTraceAnnotation``)."""
    ann = _annotations or _resolve()
    if ann is None:
        return _OFF
    return ann[1](name, step_num=step_num)
