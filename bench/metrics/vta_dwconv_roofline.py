"""``vta_dwconv``'s share of its roofline (``kernels/vta_dwconv.py``, the
depthwise convs' kernel inside ``ops._staged_vta_gemm``), in %: the least
time the chip needs for the depthwise work of the serve calls in the
traced window over the device time of the kernel's op family
(``vta_dwconv``) in the trace.

The work is counted at the configuration's depthwise shapes
(``dw_shapes`` of its reference) for the requests served: per call over b
requests, 2·b·M·9·C operations and b·H·W·C + 9·C + 4·C + b·M·C bytes (the
int8 input map, taps, int32 bias, int8 result).  The least time of a call
is the larger of operations over the int8 peak and bytes over HBM
bandwidth.  No such op in the trace, or no depthwise shapes, gives no
reading."""

from bench import stats


def read(r):
    if r.trace is None or r.peak is None:
        return None
    taken = dict(r.trace.device_ops).get("vta_dwconv", 0.0)
    shapes = getattr(r.cell.ref_module(), "dw_shapes", None)
    if taken <= 0 or shapes is None:
        return None
    least = 0.0
    for span, share in r.served.spans_between(*r.window):
        b = span[3]
        for _, h, w, c, m in shapes(r.cell.config):
            least += share * stats.least_time_s(
                2 * b * m * 9 * c, b * h * w * c + 9 * c + 4 * c + b * m * c,
                r.peak)
    return stats.share_pct(least, taken)
