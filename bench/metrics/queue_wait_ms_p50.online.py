"""Median time a request waited in the engine's queue before its batch
was dispatched (``RequestRecord.dispatch_t - enqueue_t`` of
``serving/vta/engine.py``), over the requests due in the window that were
answered, in ms."""

from bench import stats


def read(r):
    waits = sorted(q.ticket.record.queue_wait_s for q in r.answered())
    return 1e3 * stats.nearest_rank(waits, 50) if waits else None
