"""The compiled network as the engine's workers call it, with the host
clock around every ``serve`` call (the benchmark's own span of the
network loop and the pallas backend).  The clock read as a call returns
is when each of its rows was answered: every end-to-end time ends there,
on the benchmark's own clock, not on a timestamp of the program's."""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional, Tuple


def real_rows(images) -> int:
    """Rows of a batch that are requests: the engine pads a batch to its
    ladder rung with the last request's own array, once per pad row."""
    last, n = images[-1], len(images)
    pads = 0
    while pads + 1 < n and images[n - 2 - pads] is last:
        pads += 1
    return n - pads


class ServedNet:
    """Wraps a ``NetworkProgram``; everything but ``serve`` is passed on.

    ``serve_fn(net, images, backend)`` stands in for ``net.serve`` where a
    test or the control puts something in the program's place."""

    def __init__(self, net, *, clock: Callable[[], float] = time.monotonic,
                 serve_fn: Optional[Callable] = None):
        self.net = net
        self._clock = clock
        self._serve_fn = serve_fn
        self._lock = threading.Lock()
        self.served = threading.Condition()
        self.spans: List[Tuple[float, float, int, int]] = []  # t0, t1, rows, real
        self._returned: Dict[int, Tuple[object, float]] = {}  # id: (row, t1)

    def __getattr__(self, name):
        return getattr(self.net, name)

    def serve(self, images, *, backend: str, **kw):
        import jax
        t0 = self._clock()
        with jax.profiler.TraceAnnotation("bench.serve"):
            if self._serve_fn is not None:
                out = self._serve_fn(self.net, images, backend)
            else:
                out = self.net.serve(images, backend=backend, **kw)
        t1 = self._clock()
        with self._lock:
            self.spans.append((t0, t1, len(images), real_rows(images)))
            for row in images:      # the row keeps its id from being reused
                self._returned[id(row)] = (row, t1)
        with self.served:
            self.served.notify_all()
        return out

    def returned_at(self, row) -> Optional[float]:
        """When the serve call that was handed this very array returned;
        None where no call was."""
        with self._lock:
            hit = self._returned.get(id(row))
        return hit[1] if hit is not None and hit[0] is row else None

    def spans_between(self, t0: float, t1: float):
        """Serve spans with the share of each inside ``[t0, t1]``:
        ``[(span, share)]`` for the spans that overlap it."""
        with self._lock:
            spans = list(self.spans)
        out = []
        for s in spans:
            inside = min(s[1], t1) - max(s[0], t0)
            if inside > 0:
                out.append((s, inside / (s[1] - s[0])))
        return out
