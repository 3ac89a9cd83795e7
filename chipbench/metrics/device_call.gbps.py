"""Bytes over the seconds of the ``device_transfer`` spans of ``encode``: a
device route's copy to the chip, its kernel and the copy back
(``core/predictors.py`` and the other device routes)."""
from lib.spans import gbps, total


def read(ctx):
    secs, nbytes = total(ctx.spans, ["device_transfer"], under="encode")
    return gbps(nbytes, secs)
