"""Host time in the FPGA byte format per image served in the traced
window: the program's ``vta.decode`` (operands read out of the DRAM
stack), ``vta.encode`` (the result written back) and ``vta.readout`` (the
OUT region turned into per-request tensors) spans, over the ``real`` rows
of its ``engine.execute`` spans, in ms."""

from bench import span_reduce


def read(r):
    s = span_reduce.of_run()
    return None if s is None else s.ms_per_image(
        "vta.decode", "vta.encode", "vta.readout")
