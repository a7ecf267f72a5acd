"""Weights: host-to-device weight bytes the window streamed
(``ServeReport.weight_htod_bytes``, counted from array sizes) over the
window's seconds; nothing to read where every weight is resident."""


def read(ctx):
    b = ctx["delta"]["htod_bytes"]
    if not b:
        return None
    return b / ctx["seconds"] / 1e9
