"""Input GB over the self seconds of the host coding spans (``huffman``,
``lossless``: ``core/encoders.py``, ``core/lossless.py``) of ``encode``
that are not part of the contest (``select``)."""
from lib.spans import gbps, total


def read(ctx):
    secs, _ = total(ctx.spans, ["huffman", "lossless"], under="encode",
                    not_under=["select"], self_time=True)
    return gbps(ctx.work["traced_encode_bytes"], secs)
