"""Output GB over the self seconds of the host coding spans (``huffman``,
``lossless``) of ``decode``."""
from lib.spans import gbps, total


def read(ctx):
    secs, _ = total(ctx.spans, ["huffman", "lossless"], under="decode",
                    self_time=True)
    return gbps(ctx.work["traced_decode_bytes"], secs)
