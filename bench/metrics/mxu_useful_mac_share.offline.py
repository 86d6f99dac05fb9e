"""Share of the multiply-accumulates the kernel issues that are work, in
%: the ``useful_macs`` of the program's ``vta.layer`` spans (rows × the
layer's valid M·K·N) over the ``issued_macs`` of its
``vta.kernel.dispatch`` spans (the padded ``vta_gemm`` call's M·K·N,
``kernels/ops.py`` ``gemm_blocks``), in the traced window.  Pad rows of a
partial batch and the padding to MXU tiles are the rest."""

from bench import span_reduce


def read(r):
    s = span_reduce.of_run()
    if s is None:
        return None
    return span_reduce.pct(s.count("vta.layer", "useful_macs"),
                           s.count("vta.kernel.dispatch", "issued_macs"))
