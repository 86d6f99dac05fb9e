"""Share of the multiply-adds the depthwise kernel issues that are work,
in %: the ``useful_macs`` of the program's ``vta.kernel.dwconv`` spans
(rows × M·9·C of each depthwise call) over their ``issued_macs`` (what
``vta_dwconv`` runs: channels padded to the 128 lanes, every row of the
batch), in the traced window.  A program with no such span gives no
reading."""

from bench import span_reduce


def read(r):
    s = span_reduce.of_run()
    if s is None:
        return None
    return span_reduce.pct(s.count("vta.kernel.dwconv", "useful_macs"),
                           s.count("vta.kernel.dwconv", "issued_macs"))
