"""A closed loop over a pool of device-resident fields through ``Sz3Codec``.

An archive writer holding fields on the device compresses one, reads it back,
and moves on to the next.  Each ``encode`` starts from the field as a device
array that has never been read to the host, and ends with the container
bytes on the host: the copy off the chip is part of the timed call.  Each
``decode`` ends with the host array.  The pool (``config["shape"]``, one
field per slope of ``config["assumed"]["spectral_slopes"]``) is made on the
device from ``--seed``, which also fixes the order in which the loop visits
it.  The window runs whole passes of the pool: it closes at the end of the
first pass that ends after ``--seconds``, so that each field is coded as
often as every other.  The first pass gives ``ratio``.

Set-up encodes and decodes every field of the pool once, so that every
contest candidate, chunk shape and winner's decoder is compiled before the
window.

Correct: every decoded field comes back with its shape and dtype; and, for
one call of each field drawn from the seed, the decoded field lies within
the configured bound of the field that went in, pointwise, measured in
float64 after the window against a bound worked out here from the field's
own range.  Only those sampled outputs are kept until the window closes.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from lib import fields as fields_lib
from lib.harness import Check, RunResult


def _codec(config, traffic):
    from repro.codec import Sz3Codec

    if config["error_bound"]["mode"] != "rel":
        raise ValueError(f"unsupported bound mode {config['error_bound']['mode']!r}")
    return Sz3Codec(eb_mode="rel", eb_rel=float(config["error_bound"]["value"]),
                    predictor=traffic["predictor"])


def reference_bound(x: np.ndarray, rel: float) -> float:
    """The absolute bound REL ``rel`` promises for ``x``: rel x value range."""
    return float(rel) * (float(x.max()) - float(x.min()))


def max_err(x: np.ndarray, y: np.ndarray) -> float:
    return float(np.max(np.abs(y.astype(np.float64) - x.astype(np.float64))))


def fresh(x):
    """A copy of ``x`` on its device, with no host copy: a JAX array keeps
    the host copy of its first read, which would take the transfer off the
    chip out of every later ``encode`` of the same field."""
    y = x.copy()
    y.block_until_ready()
    return y


def run(h) -> RunResult:
    config, traffic = h.cell.config, h.cell.traffic
    slopes = config["assumed"]["spectral_slopes"]
    if len(slopes) != config["fields"]:
        raise ValueError(f"{config['fields']} fields but {len(slopes)} slopes")
    pool = fields_lib.make_pool(h.seed, config["shape"], slopes, device=h.devices[0])
    order = fields_lib.pool_order(h.seed, len(pool))
    codec = _codec(config, traffic)
    for j in order:
        codec.decode(codec.encode(fresh(pool[j])))

    h.start_profile()
    deadline = h.begin_window()
    enc_times, dec_times, blob_bytes = [], [], []
    # one decoded output of each field, drawn from the seed (a reservoir of
    # one per field): the value check reads these after the window
    draw = np.random.default_rng([h.seed, 1])
    sampled, visits = {}, {}
    shape = tuple(pool[0].shape)
    bad_shape = 0
    i = 0
    while i == 0 or i % len(pool) or time.perf_counter() < deadline:
        j = int(order[i % len(pool)])
        x = fresh(pool[j])
        t0 = time.perf_counter()
        with h.span("encode"):
            blob = codec.encode(x)
        t1 = time.perf_counter()
        with h.span("decode"):
            y = codec.decode(blob)
        t2 = time.perf_counter()
        enc_times.append(t1 - t0)
        dec_times.append(t2 - t1)
        blob_bytes.append(len(blob))
        del x, blob
        y = np.asarray(y)
        if y.shape != shape or y.dtype != pool[0].dtype:
            bad_shape += 1
        visits[j] = visits.get(j, 0) + 1
        if draw.random() * visits[j] < 1.0:
            sampled[j] = y
        del y
        i += 1
    h.end_window()

    field_bytes = pool[0].size * pool[0].dtype.itemsize
    # ratio over the first pass: every field of the pool once
    ratio = len(pool) * field_bytes / sum(blob_bytes[: len(pool)])

    # the reference: each sampled field against the bound from its range
    rel = float(config["error_bound"]["value"])
    worst, failed = 0.0, bad_shape
    for j, y in sorted(sampled.items()):
        if y.shape != shape or y.dtype != pool[j].dtype:
            continue  # counted above
        x = np.asarray(pool[j])
        reading = max_err(x, y) / reference_bound(x, rel)
        worst = max(worst, reading)
        failed += int(not reading <= 1.0)
    report_times(order, enc_times, dec_times)
    checks = [Check("max_err_over_bound", worst, 1.0),
              Check("wrong_shape_or_dtype", float(bad_shape), 0.0)]
    e2e = {
        "compress_gbps": i * field_bytes / 1e9 / sum(enc_times),
        "decompress_gbps": i * field_bytes / 1e9 / sum(dec_times),
        "ratio": ratio,
    }
    work = {"traced_encode_bytes": i * field_bytes,
            "traced_decode_bytes": i * field_bytes,
            "traced_encode_elements": i * pool[0].size}
    return RunResult(e2e=e2e, checks=checks, attempted=i, failed=failed, work=work)


def report_times(order, enc_times, dec_times) -> None:
    """Per field, the mean seconds of its calls; and every call that took
    more than three times the median, which a stall on the host shows as."""
    n = len(order)
    for what, ts in (("encode", enc_times), ("decode", dec_times)):
        per = {int(order[k]): float(np.mean(ts[k::n])) for k in range(n)}
        med = float(np.median(ts))
        slow = [(k, int(order[k % n]), round(t, 4)) for k, t in enumerate(ts) if t > 3 * med]
        print(f"{what}: {len(ts)} calls in {len(ts) // n} passes, median {med:.4f} s; "
              f"mean by field {[round(per[j], 4) for j in sorted(per)]}; "
              f"over 3x median (call, field, s): {slow}", file=sys.stderr)
