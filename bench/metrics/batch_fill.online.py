"""Share of the stack rows the workers served that were requests, in %:
``real`` over ``rows`` (the padding ladder's rung) of the program's
``engine.execute`` spans in the traced window."""

from bench import span_reduce


def read(r):
    s = span_reduce.of_run()
    if s is None:
        return None
    return span_reduce.pct(s.count("engine.execute", "real"),
                           s.count("engine.execute", "rows"))
