"""``vta_gemm``'s share of its roofline (``kernels/vta_gemm.py``, called
through ``ops.vta_matmul``), in %: the least time the chip needs for the
work of the serve calls in the traced window over the device time of the
kernel's module (``jit_vta_gemm``) in the trace.

The work is counted at each layer's valid shapes for the requests served
(pad rows of a partial batch and MXU padding are not work): per layer
call over b requests, 2·b·M·K·N operations and b·M·K + K·N + 4·N + b·M·N
bytes (int8 input and weights, int32 bias, int8 result).  The least time
of a call is the larger of operations over the int8 peak and bytes over
HBM bandwidth.  No kernel time in the trace gives no reading."""

from bench import stats


def read(r):
    if r.trace is None or r.peak is None:
        return None
    taken = r.trace.kernel_s.get("vta_gemm", 0.0)
    least = 0.0
    for span, share in r.served.spans_between(*r.window):
        b = span[3]
        for _, m, k, n in r.gemm_shapes:
            least += share * stats.least_time_s(
                2 * b * m * k * n, b * m * k + k * n + 4 * n + b * m * n,
                r.peak)
    return stats.share_pct(least, taken)
