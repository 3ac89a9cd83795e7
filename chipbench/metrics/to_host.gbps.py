"""Bytes over the seconds of the ``to_host`` spans of ``encode``: the field
read off the chip to the host (``pipeline.to_host``, from ``Sz3Codec.encode``)."""
from lib.spans import gbps, total


def read(ctx):
    secs, nbytes = total(ctx.spans, ["to_host"], under="encode")
    return gbps(nbytes, secs)
