"""Bytes moved between host and device by the kernel calls per image
served in the traced window: the ``bytes`` of the program's
``vta.kernel.put`` spans (operands sent: the staged input, the weights,
the bias) and ``vta.kernel.fetch`` spans (the result fetched), over the
``real`` rows of its ``engine.execute`` spans, in MB (10^6 bytes).  A
program whose spans count no bytes gives no reading."""

from bench import span_reduce


def read(r):
    s = span_reduce.of_run()
    if s is None:
        return None
    moved = (s.count("vta.kernel.put", "bytes")
             + s.count("vta.kernel.fetch", "bytes"))
    images = s.images()
    if moved <= 0 or images <= 0:
        return None
    return moved / images / 1e6
