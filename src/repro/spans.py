"""Spans at the simulator's layer boundaries, on the JAX profiler's trace.

    with span("repro.engine.loop"):
        sim.run()

A span is a ``jaxlib._profiler.TraceMe`` while a profiler trace runs
(``jax.profiler.trace(dir)``, or the benchmark's traced run) and one shared
no-op context otherwise.  The profiler keeps the spans, on the same clock
as the device's operations, and writes them out when the trace stops;
TensorBoard or Perfetto shows them.  Spans nest on the calling thread.

This module imports neither jax nor jaxlib: a process that never loaded
jax cannot be profiling, so the spawn workers and the numpy-only paths
stay jax-free.  Spans sit at call boundaries, never per packet event: with
no trace running one costs a dictionary lookup and an ``is_enabled`` call.
"""
from __future__ import annotations

import contextlib
import sys

NO_SPAN = contextlib.nullcontext()


def span(name: str):
    """A context that records ``name`` on the running profiler trace, or
    :data:`NO_SPAN` when no trace runs."""
    prof = sys.modules.get("jaxlib._profiler")
    if prof is not None and prof.TraceMe.is_enabled():
        return prof.TraceMe(name)
    return NO_SPAN
