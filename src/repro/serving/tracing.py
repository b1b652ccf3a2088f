"""Host spans of the serving engine, in the JAX profiler's own trace.

``span(name, **args)`` marks a phase of the engine's host loop
(``engine.step``, ``engine.admit``, ``engine.decode.dispatch``, ...) with a
``jax.profiler.TraceAnnotation``, so that inside a ``jax.profiler.trace`` the
phase lands on the same clock as the device's ops.  Outside a profiler
session an annotation records nothing; on a TPU v5e host one costs about
0.4 us against 0.3 us for an empty context manager, under 1 us a step.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation


def span(name: str, **args):
    """A context manager that records ``name``, with ``args`` as the span's
    stats, in an active profiler trace."""
    return TraceAnnotation(name, **args)
