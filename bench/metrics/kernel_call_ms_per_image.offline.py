"""Host time of the kernel calls per image served in the traced window:
the program's ``vta.kernel`` spans (``core/pallas_backend.py``
``_kernel_gemm``: the operands' transfer, the eager pads, the ``vta_gemm``
call and slice, the blocking fetch of the result) over the ``real`` rows
of its ``engine.execute`` spans, in ms."""

from bench import span_reduce


def read(r):
    s = span_reduce.of_run()
    return None if s is None else s.ms_per_image("vta.kernel")
