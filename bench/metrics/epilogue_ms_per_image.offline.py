"""Host time in the numpy TensorAlu epilogue (ACC preload, pool pairs,
residual joins, global pool, the int8 commit) per image served in the
traced window: the program's ``vta.epilogue`` spans
(``core/pallas_backend.py``) over the ``real`` rows of its
``engine.execute`` spans, in ms."""

from bench import span_reduce


def read(r):
    s = span_reduce.of_run()
    return None if s is None else s.ms_per_image("vta.epilogue")
