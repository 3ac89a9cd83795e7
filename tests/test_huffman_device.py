"""The device route of HuffmanEncoder writes the host route's bytes.

On a TPU, ``HuffmanEncoder.encode`` packs a large v2 stream on the device
(``kernels/huffman.py``).  Here ``routing.on_tpu`` is patched to True, so
the same program runs on the CPU backend, and every blob is compared with
the one the host packer writes for the same codes.
"""
import numpy as np
import pytest

from repro.core import CompressionConfig, ErrorBoundMode, decompress, encoders
from repro.core import telemetry as tel
from repro.core.pipeline import sz3_lorenzo
from repro.kernels import huffman, routing

FLOOR = encoders._DEVICE_MIN_CODES
#: the floor the byte-identity tests pack at, to keep them small on the CPU
SMALL = 1 << 16


@pytest.fixture
def route(monkeypatch):
    """``route(True)`` takes the device routes where they apply; Pallas
    kernels stay in interpret mode, which is all the CPU backend runs."""
    monkeypatch.setattr(
        routing, "interpret_mode", lambda interpret=None: True if interpret is None else interpret
    )

    def set_route(on: bool) -> None:
        monkeypatch.setattr(routing, "on_tpu", lambda: on)

    return set_route


@pytest.fixture
def small_floor(monkeypatch):
    monkeypatch.setattr(encoders, "_DEVICE_MIN_CODES", SMALL)


def _one_symbol(rng):
    return np.full(SMALL + 5, 32768, np.uint16)


def _symbols_23(rng):
    vals = 32757 + np.arange(23)
    w = 0.6 ** np.abs(np.arange(23) - 11)
    return rng.choice(vals, size=SMALL + 4321, p=w / w.sum()).astype(np.uint16)


def _symbols_300(rng):
    return np.clip(np.rint(rng.standard_normal(90_000) * 45) + 32768, 0, 65535).astype(np.uint16)


def _codes_to_16_bits(rng):
    # geometric frequencies 2^17 .. 1: the length cap flattens them to 16 bits
    counts = 2 ** np.arange(17, -1, -1)
    codes = np.repeat(np.arange(500, 518), counts).astype(np.uint16)
    return rng.permutation(codes)


def _n_not_multiple_of_sync(rng):
    return (32768 + np.rint(rng.standard_normal(SMALL + 1023) * 3)).astype(np.uint16)


def _word_boundary(rng):
    # 256 equally frequent symbols: every code is 8 bits, 8 * n bits in all
    return rng.permutation(np.repeat(np.arange(256, 512), 300)).astype(np.uint16)


def _words_fill_their_bucket(rng):
    # 2^16 one-bit codes: 2^11 words, a power of two, and no padding
    return np.full(SMALL, 7, np.uint16)


#: bits of a tile, the unit in which the kernel's staged words go out
TILE_BITS = 32 * huffman.TILE_WORDS


def _zero_and_65535(rng):
    # the alphabet's ends and values in between: the lookup scans most rows
    vals = np.r_[0, 65535, rng.integers(1, 65535, 400)]
    return rng.choice(vals, SMALL + 999).astype(np.uint16)


def _one_bit_runs_cross_tiles(rng):
    # two equally frequent symbols: one-bit codes, 32 a word, past three tiles
    return rng.permutation(np.repeat([40_000, 40_001], (3 * TILE_BITS + 4_000) // 2)).astype(np.uint16)


def _tails_into_next_tile(rng):
    # geometric frequencies give fifteen 16-bit codes; each is put 8 bits
    # before a tile's end, so its tail is the next tile's first word
    counts = 2 ** np.arange(17, -1, -1)
    lens, _ = encoders._huffman_code_lengths(counts)
    syms = 500 + np.arange(counts.size)
    long = np.repeat(syms[lens == 16], counts[lens == 16])
    one = int(syms[lens == 1][0])
    reserve = 2_000  # one-bit codes that align the long ones
    rest = np.repeat(syms[lens < 16], counts[lens < 16] - np.where(syms[lens < 16] == one, reserve, 0))
    width = dict(zip(syms.tolist(), lens.tolist()))
    out, pos, k = [], 0, 0
    for s in rng.permutation(rest).tolist():
        mark = (k + 1) * TILE_BITS - 8
        if k < long.size and pos + width[s] > mark:
            out += [one] * (mark - pos)
            reserve -= mark - pos
            out.append(long[k])
            pos, k = mark + 16, k + 1
        out.append(s)
        pos += width[s]
    assert k == long.size and reserve >= 0
    return np.asarray(out + [one] * reserve, np.uint16)


def _ends_one_code_past_a_tile(rng):
    # 256 symbols of 8 bits: 17 whole tiles, then one code
    n = 17 * TILE_BITS // 8 + 1
    return rng.permutation(np.arange(n) % 256 + 1024).astype(np.uint16)


def _fills_one_tile(rng):
    # four symbols of 2 bits: exactly one tile (below SMALL codes)
    return rng.permutation(np.arange(TILE_BITS // 2) % 4 + 9).astype(np.uint16)


def _uint32_codes(rng):
    return _symbols_23(rng).astype(np.uint32)


def _int64_codes(rng):
    return _symbols_300(rng).astype(np.int64)


CASES = {
    "one_symbol": _one_symbol,
    "23_symbols": _symbols_23,
    "about_300_symbols": _symbols_300,
    "codes_to_16_bits": _codes_to_16_bits,
    "n_not_multiple_of_1024": _n_not_multiple_of_sync,
    "total_bits_on_word_boundary": _word_boundary,
    "words_fill_their_bucket": _words_fill_their_bucket,
    "zero_and_65535": _zero_and_65535,
    "one_bit_runs_cross_tiles": _one_bit_runs_cross_tiles,
    "16_bit_tails_into_next_tile": _tails_into_next_tile,
    "ends_one_code_past_a_tile": _ends_one_code_past_a_tile,
    "fills_exactly_one_tile": _fills_one_tile,
    "uint32_codes": _uint32_codes,
    "int64_codes": _int64_codes,
}


def _stream_head(blob):
    """(K, code lengths, n, total_bits) of a HuffmanEncoder v2 blob."""
    k = int(np.frombuffer(blob, np.int64, count=1)[0])
    lens = np.frombuffer(blob, np.uint8, count=k, offset=8 + 8 * k)
    head = np.frombuffer(blob, np.int64, count=4, offset=8 + 9 * k)
    assert int(head[0]) == encoders._V2_MARK
    return k, lens, int(head[1]), int(head[2])


def _code_offsets(codes, vals, lens):
    """Each code's length and bit offset in the stream."""
    width = lens[np.searchsorted(vals, codes)].astype(np.int64)
    return width, np.cumsum(width) - width


@pytest.mark.parametrize("case", sorted(CASES))
def test_device_stream_byte_identical(route, small_floor, monkeypatch, case):
    codes = CASES[case](np.random.default_rng(len(case)))
    monkeypatch.setattr(encoders, "_DEVICE_MIN_CODES", min(SMALL, codes.size))
    enc = encoders.HuffmanEncoder()
    route(False)
    host = enc.encode(codes)
    route(True)
    with tel.trace() as tr:
        dev = enc.encode(codes)
    assert tr.counters.get("huffman_device") == 1
    assert dev == host
    k, lens, n, total_bits = _stream_head(dev)
    assert n == codes.size
    if case == "one_symbol":
        assert k == 1
    elif case == "23_symbols":
        assert k == 23
    elif case == "about_300_symbols":
        assert 250 <= k <= 350
    elif case == "codes_to_16_bits":
        assert int(lens.max()) == 16
    elif case == "n_not_multiple_of_1024":
        assert n % 1024
    elif case == "total_bits_on_word_boundary":
        assert total_bits % 32 == 0
    elif case == "words_fill_their_bucket":
        assert total_bits == 32 * 2048
    elif case == "zero_and_65535":
        vals = np.frombuffer(dev, np.int64, count=k, offset=8)
        assert vals[0] == 0 and vals[-1] == 65535
        assert np.unique(vals >> 7).size > 200
    elif case == "one_bit_runs_cross_tiles":
        assert set(lens) == {1} and total_bits > 3 * TILE_BITS
    elif case == "16_bit_tails_into_next_tile":
        vals = np.frombuffer(dev, np.int64, count=k, offset=8)
        width, offs = _code_offsets(codes, vals, lens)
        spills = (width == 16) & (offs % TILE_BITS == TILE_BITS - 8)
        assert spills.sum() == 15
    elif case == "ends_one_code_past_a_tile":
        assert total_bits == 17 * TILE_BITS + 8
    elif case == "fills_exactly_one_tile":
        assert total_bits == TILE_BITS
    assert np.array_equal(enc.decode(dev, codes.size), codes.astype(np.int64))


def _field(shape=(256, 300)):
    rng = np.random.default_rng(7)
    x = np.cumsum(np.cumsum(rng.standard_normal(shape), axis=0), axis=1)
    return x.astype(np.float32)


def test_lorenzo_blob_identical_with_route_on_and_off(route, small_floor):
    x = _field()
    conf = CompressionConfig(mode=ErrorBoundMode.REL, eb=1e-3)
    route(False)
    off = sz3_lorenzo(device="force").compress(x, conf).blob
    route(True)
    with tel.trace() as tr:
        on = sz3_lorenzo(device="force").compress(x, conf).blob
    assert tr.counters.get("huffman_device") == 1
    assert on == off
    xhat = decompress(on)
    bound = 1e-3 * float(x.max() - x.min())
    assert np.abs(xhat.astype(np.float64) - x).max() <= bound


def _huffman_spans(tr):
    out = []

    def walk(s):
        for c in s.children:
            if c.name == "huffman":
                out.append(c)
            walk(c)

    walk(tr.root)
    return out


def test_route_counter_and_span_attribute(route, small_floor):
    x = _field()
    conf = CompressionConfig(mode=ErrorBoundMode.REL, eb=1e-3)
    route(True)
    with tel.trace() as tr:
        sz3_lorenzo().compress(x, conf)
        sz3_lorenzo().compress(x[:16], conf)  # 4,800 codes: below SMALL
    spans = _huffman_spans(tr)
    assert [s.attrs["route"] for s in spans] == ["device", "host"]
    assert tr.counters["huffman_device"] == 1
    # nothing opens inside the huffman span
    assert all(not s.children for s in spans)


def test_host_route_below_floor_and_for_v1_streams(route):
    rng = np.random.default_rng(3)
    small = (32768 + np.rint(rng.standard_normal(FLOOR - 1) * 3)).astype(np.uint16)
    large = (32768 + np.rint(rng.standard_normal(FLOOR * 2) * 3)).astype(np.uint16)
    route(True)
    with tel.trace() as tr:
        with tel.span("huffman") as below:
            encoders.HuffmanEncoder().encode(small)
        with tel.span("huffman") as v1:
            blob = encoders.HuffmanEncoder(stream_version=1).encode(large)
    assert "huffman_device" not in tr.counters
    assert below.attrs["route"] == v1.attrs["route"] == "host"
    route(False)
    assert blob == encoders.HuffmanEncoder(stream_version=1).encode(large)
    assert encoders.HuffmanEncoder().decode(blob, large.size).size == large.size


def test_host_route_for_values_beyond_the_device_table(route):
    rng = np.random.default_rng(4)
    wide = rng.integers(60_000, 70_000, FLOOR + 10).astype(np.uint32)
    route(True)
    with tel.trace() as tr:
        blob = encoders.HuffmanEncoder().encode(wide)
    assert "huffman_device" not in tr.counters
    assert np.array_equal(encoders.HuffmanEncoder().decode(blob, wide.size), wide)


@pytest.mark.parametrize("case", ["one_symbol", "about_300_symbols", "zero_and_65535"])
def test_table_rows_tag_is_the_occupied_rows(route, small_floor, case):
    codes = CASES[case](np.random.default_rng(len(case)))
    route(True)
    with tel.trace() as tr:
        with tel.span("huffman") as sp:
            blob = encoders.HuffmanEncoder().encode(codes)
    assert tr.counters.get("huffman_device") == 1
    k = int(np.frombuffer(blob, np.int64, count=1)[0])
    vals = np.frombuffer(blob, np.int64, count=k, offset=8)
    assert sp.attrs["table_rows"] == np.unique(vals >> 7).size
