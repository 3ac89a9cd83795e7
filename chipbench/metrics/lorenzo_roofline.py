"""Share of its roofline that the Lorenzo encode reaches on the device.

Least time: the bytes the work needs (``kernels/lorenzo.json``: per element
of the field, float32 in, int32 codes and int32 diffs out) over the chip's
HBM bandwidth.  Time: the device seconds of the ops of the programs the
kernel table names.  The bytes come from the field's shape and not from the
kernel, so a rewrite that does the same work keeps the numerator.
"""


def read(ctx):
    if ctx.device is None:
        return None
    k = ctx.kernel("lorenzo")
    secs = ctx.device.module_seconds(k["encode_programs"])
    if secs <= 0:
        return None
    least = ctx.work["traced_encode_elements"] * k["bytes_per_element"] / ctx.peaks()["hbm_bytes_per_s"]
    return 100.0 * least / secs
