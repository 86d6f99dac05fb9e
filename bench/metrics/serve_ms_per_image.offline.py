"""Host time of the network loop and the pallas backend per image: the
benchmark's clock around each ``NetworkProgram.serve`` call the engine's
workers make in the window, summed, over the requests those calls served
(``core/network_compiler.py`` ``serve``, ``core/pallas_backend.py``)."""


def read(r):
    spans = r.served.spans_between(*r.window)
    rows = sum(share * span[3] for span, share in spans)
    if rows <= 0:
        return None
    return 1e3 * sum(share * (span[1] - span[0])
                     for span, share in spans) / rows
