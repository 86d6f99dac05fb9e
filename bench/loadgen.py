"""The one traffic generator: it reads a mix's parameters
(``traffic/<mix>.json``) and drives an engine on the wall clock.

A mix is one of

* ``{"loop": "closed", "clients": n, "think_s": t, ...}`` — n clients,
  each sending its next request ``t`` seconds after its last answer;
* ``{"loop": "open", "arrivals": "poisson", "rate_rps": r, ...}`` —
  independent requests due at Poisson times at ``r`` per second, sent
  when due whether or not earlier ones were answered;

with the engine's ``policy`` (``BatchPolicy`` fields) and ``workers``
(one backend per worker).  Every request records when it was due, when
it was sent and what came of it; an open-loop request is timed from when
it was due.
"""

from __future__ import annotations

import dataclasses
import time
from typing import List, Optional

import numpy as np

from bench import refops

ROUNDS = 1e-4        # how long the closed loop waits between looks, in s


@dataclasses.dataclass
class Request:
    image: int                     # index into the run's image pool
    due_t: float
    sent_t: Optional[float] = None
    ticket: object = None
    refused: Optional[BaseException] = None
    answer: object = None          # what the engine answered, and when
    answered_t: Optional[float] = None   # its serve call returned
    error: Optional[BaseException] = None


def poisson_arrival_times(rate_rps: float, seconds: float,
                          seed: int) -> np.ndarray:
    """Arrival offsets in ``[0, seconds)`` of a Poisson process at
    ``rate_rps``, conditioned on its mean count: ``round(rate·seconds)``
    sorted uniform times.  Every seed then offers the same number of
    requests, in another order.  (Adapted from ``poisson_arrival_times``
    in ``src/repro/serving/vta/loadgen.py``, which draws exponential
    gaps and so a count that varies with the seed.)"""
    if rate_rps <= 0:
        raise ValueError(f"rate_rps must be > 0, got {rate_rps}")
    n = max(1, round(rate_rps * seconds))
    return np.sort(refops.stream(seed, 2).uniform(0.0, seconds, n))


def image_order(seed: int, pool: int, n: int) -> np.ndarray:
    """Which pool image each of ``n`` requests sends: the pool in a
    seeded order, again and again."""
    rng = refops.stream(seed, 3)
    reps = -(-n // pool)
    return np.concatenate([rng.permutation(pool) for _ in range(reps)])[:n]


def drive(engine, served, mix: dict, images: np.ndarray, seed: int,
          start: float, seconds: float, clock=time.monotonic
          ) -> List[Request]:
    """Offer ``mix`` to ``engine`` from ``start`` for ``seconds``;
    returns every request due in that window, in order."""
    if mix["loop"] == "open":
        return _open_loop(engine, mix, images, seed, start, seconds, clock)
    if mix["loop"] == "closed":
        return _closed_loop(engine, served, mix, images, seed, start,
                            seconds, clock)
    raise ValueError(f"unknown loop {mix['loop']!r}")


def _send(engine, req: Request, images: np.ndarray, clock) -> None:
    from repro.serving.vta import QueueFull
    req.sent_t = clock()
    try:
        req.ticket = engine.submit(images[req.image][None])
    except QueueFull as exc:
        req.refused = exc


def _open_loop(engine, mix, images, seed, start, seconds, clock):
    if mix["arrivals"] != "poisson":
        raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
    offsets = poisson_arrival_times(mix["rate_rps"], seconds, seed)
    order = image_order(seed, len(images), len(offsets))
    reqs = [Request(int(i), start + float(t)) for i, t in zip(order, offsets)]
    for req in reqs:
        wait = req.due_t - clock()
        if wait > 0:
            time.sleep(wait)
        _send(engine, req, images, clock)
    return reqs


def _closed_loop(engine, served, mix, images, seed, start, seconds, clock):
    clients, think = mix["clients"], mix["think_s"]
    end = start + seconds
    # enough of the seeded order for any window: one request per client
    # per 100 us is far beyond what a chip answers
    order = image_order(seed, len(images), 1 << 20)
    reqs: List[Request] = []
    pending: List[float] = []      # when waiting clients send again

    def send(due):
        req = Request(int(order[len(reqs) % len(order)]), due)
        reqs.append(req)
        _send(engine, req, images, clock)
        return req

    while clock() < start:
        time.sleep(start - clock())
    inflight = [send(clock()) for _ in range(clients)]
    while True:
        now = clock()
        still = []
        for req in inflight:
            if req.refused is not None or req.ticket.done():
                pending.append(now + think)
            else:
                still.append(req)
        inflight = still
        if now >= end:
            return reqs
        ready = [t for t in pending if t <= now]
        pending = [t for t in pending if t > now]
        for _ in ready:
            inflight.append(send(clock()))
        if not ready and inflight:
            _await_answer(served, inflight, pending)
        elif not ready:
            time.sleep(ROUNDS)


def _await_answer(served, inflight, pending) -> None:
    """Sleep until a serve call returns (or 2 ms, or the next client's
    think time is up), then until the engine has resolved a ticket."""
    with served.served:
        woke = served.served.wait(ROUNDS if pending else 0.002)
    if woke:
        for _ in range(100):        # the engine resolves right after serve
            if any(r.ticket.done() for r in inflight if r.ticket):
                return
            time.sleep(ROUNDS / 2)
