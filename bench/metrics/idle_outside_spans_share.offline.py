"""Share of the device's idle time in the traced window during which no
program span was open on any host thread, in %: idle that the program's
spans (``engine.*``, ``vta.*``) cannot name, such as the load generator,
the benchmark's own bookkeeping or a process stood still."""

from bench import span_reduce


def read(r):
    s = span_reduce.of_run()
    if s is None or s.idle is None:
        return None
    return span_reduce.pct(s.idle.get(span_reduce.NO_SPAN, 0.0),
                           sum(s.idle.values()))
