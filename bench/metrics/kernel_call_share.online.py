"""Share of the workers' batch time spent in kernel calls, in %: the
program's ``vta.kernel`` spans over its ``engine.execute`` spans, both
inside the traced window."""

from bench import span_reduce


def read(r):
    s = span_reduce.of_run()
    if s is None:
        return None
    return span_reduce.pct(s.time_s("vta.kernel"),
                           s.time_s("engine.execute"))
