"""Spans at the serving path's layer boundaries, on the JAX profiler's clock.

:func:`span` is the one entry point.  While a JAX profiler session records
(``jax.profiler.trace(dir)``, ``start_trace``/``stop_trace``, or the
profiler server), it opens a ``jax.profiler.TraceAnnotation``: the span
lands in the profiler's own trace beside the device's operations, on the
same clock, and each integer keyword argument becomes a stat of the event
in the ``.xplane.pb`` (``useful_macs=...``), so counts are recorded at the
boundary where the work happens.  Otherwise it returns one shared null
context, and costs a flag check and a call.

There is no store, exporter or switch of this module's own: the profiler's
buffer holds the spans and writes them when the session stops, and a
profiler session is the only way to turn them on.  ``bench/span_reduce.py``
reads them back (DESIGN.md §Serving lists the span names).
"""

from __future__ import annotations

import contextlib

try:
    from jax._src.lib import _profiler
    from jax.profiler import TraceAnnotation
    # the flag TraceAnnotation itself consults: true while a session records
    _recording = _profiler.TraceMe.is_enabled
except ImportError:  # pragma: no cover - exercised only without jax
    TraceAnnotation = None

    def _recording() -> bool:
        return False

_OFF = contextlib.nullcontext()


def span(name: str, **counts: int):
    """A context manager that records ``name`` (with ``counts`` as its
    stats) while a profiler session records, and does nothing otherwise."""
    if _recording():
        return TraceAnnotation(name, **counts)
    return _OFF
