"""Input GB over the seconds of the chunked engine's ``select`` spans
(contest and runoff, ``core/chunking.py``) inside ``encode``."""
from lib.spans import gbps, total


def read(ctx):
    secs, _ = total(ctx.spans, ["select"], under="encode")
    return gbps(ctx.work["traced_encode_bytes"], secs)
