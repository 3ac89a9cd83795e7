"""Canonical Huffman stream packing on the device: one Pallas TPU kernel.

The encode half of :class:`repro.core.encoders.HuffmanEncoder` for large v2
streams on a TPU.  The host keeps the alphabet, the code lengths and the
canonical table; :func:`huffman_pack` does the data-sized work in one
sequential pass over chunks of ``_CHUNK_ROWS`` x 128 codes, and writes the
sync offsets and the payload words that the host packer
(``encoders._pack_codes``) writes.

Why a kernel: XLA's gathers and scatters run at about 9 ns an element on a
TPU v5e, and the earlier XLA program needed one table gather per code and a
scatter and a gather per word (0.129 s at 2^23 codes, 73 ms of it the table
gather, where a ``cumsum`` of the same size took 2.4 ms).  Here no step
gathers or scatters through memory:

* **Table lookup.**  The dense table (``table_entries``, 2^16 uint32
  entries, 256 KB) sits in VMEM as 512 rows of 128.  The host passes the
  rows that hold a present symbol (``table_rows``; a field's Lorenzo codes
  fill 3-5) by scalar prefetch, and each code looks itself up in those rows
  only: a lane gather inside the vector registers (``take_along_axis`` on
  the row broadcast to the block) and a select by row.  A wide alphabet
  scans more rows (at most 512), more slowly, to the same bytes.
* **Bit offsets.**  Log-step prefix sums of the code lengths along lanes,
  then rows, plus the offset carried from the previous chunk.
* **Word assembly.**  A code's bits start in word ``off >> 5`` (its
  left-aligned head) and at most its last 15 bits spill into the next word
  (its tail).  A row of 128 codes spans at most 65 words from the word its
  first code starts in.  For each row, a one-hot (word, code) matrix on the
  MXU sums the bytes of the heads and of the tails into those words: exact
  in float32 from bfloat16 bytes, since bits within a word are disjoint.
  The row's words are rotated to their lane and ORed into a VMEM staging
  window; each chunk's window goes out by one DMA at a 1024-word aligned
  row, and its last, still open, tile is carried into the next chunk's.

Measured on a TPU v5e on the Lorenzo codes of a 1800x3600 field (6.48 M
codes, 3-5 table rows), a call with its inputs on the device: the XLA
program 98 ms, its largest ops the table gather (49 ms), the first-code
scatter (31 ms) and the gather per word (11 ms); this kernel 6.7 ms, of
which 4.2 ms on the device.  With all 512 table rows scanned it takes
52 ms.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import routing
from .lorenzo.kernel import prefix_sum

#: dense table width: every value of a uint16 code
TABLE_SIZE = 1 << 16
#: most symbols a stream may have: 16 bits a symbol at the padded size
#: keep every int32 bit offset below 2**31
MAX_SYMBOLS = 1 << 26
#: fewest padded symbols a stream is packed at (the smallest size bucket)
_MIN_SIZE = 1 << 16
#: 32-bit words in each piece of the output; the host reads back only the
#: pieces the payload reaches
_PIECE_WORDS = 1 << 16
_SYNC = 1024  # symbols between sync offsets, as in core/encoders.py

_LANES = 128
_TABLE_ROWS = TABLE_SIZE // _LANES
#: rows of 128 codes a grid step packs
_CHUNK_ROWS = 64
_CHUNK = _CHUNK_ROWS * _LANES
#: rows packed together: one vector register of eight rows
_GROUP = 8
#: word rows of a tile, the unit in which staged words go out: a chunk's
#: copy starts at a tile, and the tile holding its last bit is carried
_TILE_ROWS = 8
TILE_WORDS = _TILE_ROWS * _LANES
#: word rows (of 128 words) a group's words can reach from the staging
#: row its first word lies in, rounded down to a tile of eight
_WINDOW = 16
#: staging word rows: a chunk's words (at most 64 a row of codes, plus the
#: last tail), after up to 7 rows of the tile it starts in, and a window
_STAGE = _CHUNK_ROWS // 2 + 24
#: byte planes on the MXU: the head's four, the tail's upper two (a tail
#: holds at most 15 bits, left-aligned)
_PLANES = 6


def table_entries(codes: np.ndarray, lens: np.ndarray) -> np.ndarray:
    """Dense-table entries ``code << 8 | length`` (uint32) for canonical
    codes of at most 16 bits; an entry of 0 has length 0 and adds no bits."""
    return (codes.astype(np.uint32) << np.uint32(8)) | lens.astype(np.uint32)


def table_rows(table: np.ndarray) -> np.ndarray:
    """Rows of 128 entries of the dense ``table`` that hold a code (int32,
    ascending): the rows the kernel's lookup scans."""
    return np.flatnonzero(table.reshape(_TABLE_ROWS, _LANES).any(axis=1)).astype(np.int32)


def _bswap32(w: jnp.ndarray) -> jnp.ndarray:
    """Byte-swap uint32 words, so that their little-endian bytes on the host
    read as the big-endian words of the stream."""
    return (
        (w >> 24)
        | ((w >> 8) & jnp.uint32(0xFF00))
        | ((w << 8) & jnp.uint32(0xFF0000))
        | (w << 24)
    )


def _lookup(syms, table_ref, rows_ref, k):
    """Dense-table entries of ``syms`` (int32, (R, 128)), scanning the ``k``
    occupied table rows listed in ``rows_ref``."""
    lane = syms & (_LANES - 1)
    row = syms >> 7

    def scan(j, ent):
        r = rows_ref[j]
        line = jnp.broadcast_to(table_ref[pl.ds(r, 1), :], syms.shape)
        hit = jnp.take_along_axis(line, lane, axis=1, mode="promise_in_bounds")
        return jnp.where(row == r, hit, ent)

    return jax.lax.fori_loop(0, k, scan, jnp.zeros(syms.shape, jnp.uint32))


def _group_words(word, head, tail):
    """Words of each of eight rows of codes, relative to the word the row's
    first code starts in: ``(8, 128)`` uint32, lane ``j`` of row ``r`` the
    OR of the heads of row ``r`` in its word ``j`` and the tails spilling
    into it."""
    rel = word - word[:, :1]  # in [0, 64]
    planes = [(head >> s) & 0xFF for s in (24, 16, 8, 0)] + [(tail >> s) & 0xFF for s in (24, 16)]
    lhs = jnp.concatenate(
        [p.astype(jnp.int32).astype(jnp.float32).astype(jnp.bfloat16) for p in planes], axis=0
    )
    j = jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 0)
    sub = jax.lax.broadcasted_iota(jnp.int32, word.shape, 0)
    acc = [jnp.zeros(word.shape, jnp.float32)] * _PLANES
    for r in range(_GROUP):
        onehot = jnp.where(j == rel[r : r + 1, :], 1.0, 0.0).astype(jnp.bfloat16)
        # out[8 p + q, j]: plane p of row q summed over the codes of row r in word j
        out = jax.lax.dot_general(
            lhs, onehot, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc = [jnp.where(sub == r, out[_GROUP * p : _GROUP * (p + 1)], a) for p, a in enumerate(acc)]
    byte = [a.astype(jnp.int32).astype(jnp.uint32) for a in acc]
    heads = (byte[0] << 24) | (byte[1] << 16) | (byte[2] << 8) | byte[3]
    tails = (byte[4] << 24) | (byte[5] << 16)
    return heads | pltpu.roll(tails, 1, 1)  # a tail lands one word on


def _pack_kernel(meta_ref, rows_ref, syms_ref, table_ref, sync_ref, words_hbm,
                 word_scr, head_scr, tail_scr, stage, sem, state):
    """One chunk of ``_CHUNK`` codes.  ``meta_ref``: (live codes, occupied
    table rows).  ``state``: the chunk's first bit offset, and the output
    word row where ``stage[slot]`` starts (a whole tile)."""
    i = pl.program_id(0)
    slot = i % 2

    @pl.when(i == 0)
    def _():
        state[0] = 0
        state[1] = 0
        stage[0] = jnp.zeros(stage.shape[1:], jnp.uint32)

    shape = syms_ref.shape
    ent = _lookup(syms_ref[...].astype(jnp.int32), table_ref, rows_ref, meta_ref[1])
    code_idx = (
        i * _CHUNK
        + jax.lax.broadcasted_iota(jnp.int32, shape, 0) * _LANES
        + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    )
    live = code_idx < meta_ref[0]  # padding adds no bits
    lens = jnp.where(live, (ent & 0xFF).astype(jnp.int32), 0)
    codes = jnp.where(live, ent >> 8, jnp.uint32(0))
    incl = prefix_sum(lens, 1)
    row_bits = jnp.broadcast_to(incl[:, _LANES - 1 :], shape)
    rows_incl = prefix_sum(row_bits, 0)
    start = state[0]
    offs = start + (rows_incl - row_bits) + (incl - lens)
    state[0] = start + rows_incl[_CHUNK_ROWS - 1, 0]
    sync_ref[...] = offs.reshape(_CHUNK_ROWS // _GROUP, _GROUP, _LANES)[:, 0, :]

    # bits left free in the head's word once the code is placed; < 0 spills
    room = 32 - (offs & 31) - lens
    word_scr[...] = offs >> 5
    head_scr[...] = jnp.where(
        room >= 0,
        codes << jnp.maximum(room, 0).astype(jnp.uint32),
        codes >> jnp.maximum(-room, 0).astype(jnp.uint32),
    )
    tail_scr[...] = jnp.where(
        room < 0, codes << (32 + jnp.minimum(room, 0)).astype(jnp.uint32), jnp.uint32(0)
    )

    origin = state[1]  # output word row of stage[slot, 0]
    win_sub = jax.lax.broadcasted_iota(jnp.int32, (_WINDOW, _LANES), 0)
    win_lane = jax.lax.broadcasted_iota(jnp.int32, (_WINDOW, _LANES), 1)

    def group(g, carry):
        rows = pl.ds(pl.multiple_of(g * _GROUP, _GROUP), _GROUP)
        word = word_scr[rows]
        words = _group_words(word, head_scr[rows], tail_scr[rows])
        top = (word[0, 0] >> 7) - origin
        base = top - top % _TILE_ROWS  # the window's first staging row
        win = jnp.zeros((_WINDOW, _LANES), jnp.uint32)
        for r in range(_GROUP):
            first = word[r, 0]
            at = (first >> 7) - origin - base  # window row of the row's first word
            lane = first & (_LANES - 1)
            moved = jnp.broadcast_to(pltpu.roll(words, lane, 1)[r : r + 1, :], win.shape)
            here = ((win_sub == at) & (win_lane >= lane)) | ((win_sub == at + 1) & (win_lane < lane))
            win = win | jnp.where(here, moved, jnp.uint32(0))
        at = pl.ds(pl.multiple_of(base, _TILE_ROWS), _WINDOW)
        stage[slot, at] = stage[slot, at] | win
        return carry

    jax.lax.fori_loop(0, _CHUNK_ROWS // _GROUP, group, 0)

    def copy(s, row):
        return pltpu.make_async_copy(stage.at[s], words_hbm.at[pl.ds(row, _STAGE)], sem.at[s])

    # the previous chunk's copy overlaps this one's first tile: it lands first
    @pl.when(i > 0)
    def _():
        copy(1 - slot, 0).wait()

    out = copy(slot, pl.multiple_of(origin, _TILE_ROWS))
    out.start()
    # the tile holding the next chunk's first bit is still open: carry it
    tile = (state[0] >> 5) // TILE_WORDS * _TILE_ROWS
    keep = pl.multiple_of(tile - origin, _TILE_ROWS)
    stage[1 - slot] = jnp.zeros(stage.shape[1:], jnp.uint32)
    stage[1 - slot, pl.ds(0, _TILE_ROWS)] = stage[slot, pl.ds(keep, _TILE_ROWS)]
    state[1] = tile

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        out.wait()


@functools.partial(jax.jit, static_argnames=("n_words", "interpret"))
def huffman_pack(syms, table, meta, rows, *, n_words: int, interpret: bool = False):
    """Pack the first ``meta[0]`` symbols of ``syms`` (uint16, padded to a
    multiple of ``_CHUNK``) with the dense ``table`` (:func:`table_entries`,
    ``TABLE_SIZE`` entries), scanning the ``meta[1]`` table rows listed first
    in ``rows`` (:func:`table_rows`, padded to ``_TABLE_ROWS``), into
    ``n_words`` 32-bit words (whole tiles), at least as many as the
    stream fills.

    Returns the bit offset of every ``_SYNC``-th symbol (int32) and the
    words, byte-swapped (:func:`_bswap32`), as a tuple of pieces of at most
    ``_PIECE_WORDS`` words; words past the stream's end are zero.
    """
    size = syms.shape[0]
    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(size // _CHUNK,),
        in_specs=[
            pl.BlockSpec((_CHUNK_ROWS, _LANES), lambda i, m, r: (i, 0)),
            pl.BlockSpec((_TABLE_ROWS, _LANES), lambda i, m, r: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((_CHUNK_ROWS // _GROUP, _LANES), lambda i, m, r: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((_CHUNK_ROWS, _LANES), jnp.int32),
            pltpu.VMEM((_CHUNK_ROWS, _LANES), jnp.uint32),
            pltpu.VMEM((_CHUNK_ROWS, _LANES), jnp.uint32),
            pltpu.VMEM((2, _STAGE, _LANES), jnp.uint32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((2,), jnp.int32),
        ],
    )
    sync, words = pl.pallas_call(
        _pack_kernel,
        grid_spec=grid,
        out_shape=[
            jax.ShapeDtypeStruct((size // _SYNC, _LANES), jnp.int32),
            # a chunk's copy reaches up to _STAGE rows past its origin
            jax.ShapeDtypeStruct((n_words // _LANES + _STAGE, _LANES), jnp.uint32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(meta, rows, syms.reshape(-1, _LANES), table.reshape(_TABLE_ROWS, _LANES))
    piece = min(_PIECE_WORDS, n_words)
    pieces = _bswap32(words[: n_words // _LANES]).reshape(-1, piece)
    return sync[:, 0], tuple(pieces[i] for i in range(pieces.shape[0]))


def _bucket(k: int) -> int:
    """The power of two at or above ``k`` (>= 1)."""
    return 1 << max(0, k - 1).bit_length()


def _size_bucket(n: int) -> int:
    """``n`` rounded up to one of eight sizes an octave, and to a whole
    chunk: at most an eighth of padding, every code of which costs as a
    live one does."""
    step = 1 << max(0, n.bit_length() - 4)
    size = -(-n // step) * step
    return -(-size // _CHUNK) * _CHUNK


def pack_stream(syms: np.ndarray, table: np.ndarray, total_bits: int):
    """Pack ``syms`` (values below ``TABLE_SIZE``, at most ``MAX_SYMBOLS``
    of them) on the default device.

    ``table`` is the dense table (:func:`table_entries`) and ``total_bits``
    the stream's length in bits, which the host knows from its histogram.
    The symbols are padded to one of eight sizes an octave and the words
    sized to a power of two, so a compile serves every stream of a size and
    entropy bucket.  Returns the sync offsets (uint32, one every ``_SYNC``
    symbols) and the payload (uint8, ``ceil(total_bits / 8)`` bytes).
    The kernel is interpreted off a TPU (:mod:`repro.kernels.routing`).
    """
    n = syms.size
    size = max(_MIN_SIZE, _size_bucket(n))
    buf = np.zeros(size, np.uint16)
    buf[:n] = syms
    occupied = table_rows(table)
    rows = np.zeros(_TABLE_ROWS, np.int32)
    rows[: occupied.size] = occupied
    meta = np.asarray([n, occupied.size], np.int32)
    need = -(-total_bits // 32)
    sync, pieces = huffman_pack(
        buf, table, meta, rows, n_words=max(TILE_WORDS, _bucket(need)),
        interpret=routing.interpret_mode(),
    )
    piece = pieces[0].shape[0]
    sync, pieces = jax.device_get((sync, pieces[: -(-need // piece)]))
    payload = np.concatenate(pieces).view(np.uint8)[: (total_bits + 7) >> 3]
    return sync[: -(-n // _SYNC)].astype(np.uint32), payload
