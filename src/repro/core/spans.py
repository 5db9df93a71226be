"""Named host spans on the profiler's clock.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation``: a host span
that lands in the same ``.xplane.pb``, on the same clock, as the device's
operations, so a trace of the job says what the host was doing while the
device worked or idled.  The profiler is the one recorder: there is no
switch and nothing is kept in memory.  While no trace is being taken an
annotation costs well under a microsecond.

``repro.core`` stays importable without JAX (pod workers and the CPU
simulators import it), so the annotation is looked up only once some other
module has imported JAX; until then a span is a shared null context.
"""
from __future__ import annotations

import contextlib
import sys

_NULL = contextlib.nullcontext()


def span(name: str, **args):
    """A context manager that records ``name`` with ``args`` as a host span
    of the trace being taken, if any."""
    jax = sys.modules.get("jax")
    if jax is None:
        return _NULL
    return jax.profiler.TraceAnnotation(name, **args)
