"""Host time staging each layer's input into the DRAM stack (im2row,
pad/split/binarise, the residual operand) per image served in the traced
window: the program's ``vta.stage`` spans (``core/network_compiler.py``
``serve``) over the ``real`` rows of its ``engine.execute`` spans, in ms."""

from bench import span_reduce


def read(r):
    s = span_reduce.of_run()
    return None if s is None else s.ms_per_image("vta.stage")
