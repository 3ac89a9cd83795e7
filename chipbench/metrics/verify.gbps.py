"""Input GB over the seconds of the ``verify`` spans of ``encode``: the host's
bound check of a device route's output against the decoders' arithmetic
(``core/predictors.py``, ``core/transform.py``, ``core/fastmode.py``)."""
from lib.spans import gbps, total


def read(ctx):
    secs, _ = total(ctx.spans, ["verify"], under="encode")
    return gbps(ctx.work["traced_encode_bytes"], secs)
