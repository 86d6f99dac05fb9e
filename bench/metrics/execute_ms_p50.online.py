"""Median time a batch held a worker, in ms: nearest rank over the
program's ``engine.execute`` spans (``serving/vta/engine.py``, from
dispatch through resolving the tickets) that start in the traced
window."""

from bench import span_reduce


def read(r):
    s = span_reduce.of_run()
    if s is None or "engine.execute" not in s.spans:
        return None
    return span_reduce.p50_ms(s.spans["engine.execute"].durations)
