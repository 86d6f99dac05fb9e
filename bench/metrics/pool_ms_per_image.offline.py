"""Host time in the pooling part of the TensorAlu epilogue per image
served in the traced window: the program's ``vta.pool`` spans
(``core/pallas_backend.py``: the pair lattices of the 2×2, padded 3×3/s2
and global pools, and the SHR over the rows they keep) over the ``real``
rows of its ``engine.execute`` spans, in ms.  A program that records no
such span gives no reading."""

from bench import span_reduce


def read(r):
    s = span_reduce.of_run()
    if s is None or "vta.pool" not in s.spans:
        return None
    return s.ms_per_image("vta.pool")
