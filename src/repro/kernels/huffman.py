"""Canonical Huffman stream packing on the device.

The encode half of :class:`repro.core.encoders.HuffmanEncoder` for large v2
streams on a TPU.  The host keeps the alphabet, the code lengths and the
canonical table; :func:`huffman_pack` does the data-sized work: one table
gather per code, an int32 exclusive ``cumsum`` of the code lengths (the bit
offsets), and the stream's 32-bit words.  The payload bytes and the sync
offsets are the ones the host packer (``encoders._pack_codes``) writes.

Words are built from prefix sums.  A code's bits start in word ``off >> 5``
(its left-aligned "head" part) and at most its last 15 bits spill into the
next word (its "tail" part).  Codes are in stream order, so the codes whose
heads land in word ``w`` are a contiguous run ``[first(w), first(w + 1))``;
their bits are disjoint, so their OR is their sum, which is a difference of
a uint32 prefix sum of the heads (wrap-around subtraction is exact, since
the true sum fits 32 bits).  The one tail that spills into ``w`` belongs to
code ``first(w) - 1``.  A code is at most 16 bits, so a head lands in every
word up to the stream's end, and ``first`` is where the word index steps:
one scatter of those codes, then one gather per word.  (On a TPU v5e a
binary search for ``first`` cost 6x this, and scatter-adding every head and
tail 1.6x; XLA's gathers and scatters run at about 9 ns an element there.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

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


def table_entries(codes: np.ndarray, lens: np.ndarray) -> np.ndarray:
    """Dense-table entries ``code << 8 | length`` (uint32) for canonical
    codes of at most 16 bits; an entry of 0 has length 0 and adds no bits."""
    return (codes.astype(np.uint32) << np.uint32(8)) | lens.astype(np.uint32)


def _bswap32(w: jnp.ndarray) -> jnp.ndarray:
    """Byte-swap uint32 words, so that their little-endian bytes on the host
    read as the big-endian words of the stream."""
    return (
        (w >> 24)
        | ((w >> 8) & jnp.uint32(0xFF00))
        | ((w << 8) & jnp.uint32(0xFF0000))
        | (w << 24)
    )


@functools.partial(jax.jit, static_argnames=("n_words",))
def huffman_pack(syms: jnp.ndarray, table: jnp.ndarray, n: jnp.ndarray, *, n_words: int):
    """Pack the first ``n`` symbols of ``syms`` (uint16, padded to a
    multiple of ``_SYNC``) with the dense ``table`` (:func:`table_entries`,
    ``TABLE_SIZE`` entries) into ``n_words`` 32-bit words, at least as many
    as the stream fills.

    Returns the bit offset of every ``_SYNC``-th symbol (int32) and the
    words, byte-swapped (:func:`_bswap32`), as a tuple of pieces of at most
    ``_PIECE_WORDS`` words; words past the stream's end are zero.
    """
    size = syms.shape[0]
    idx = jnp.arange(size, dtype=jnp.int32)
    ent = table[syms.astype(jnp.int32)]
    live = idx < n  # padding adds no bits
    lens = jnp.where(live, (ent & jnp.uint32(0xFF)).astype(jnp.int32), 0)
    codes = jnp.where(live, ent >> 8, jnp.uint32(0))
    offs = jnp.cumsum(lens, dtype=jnp.int32) - lens
    # bits left free in the head's word once the code is placed; < 0 spills
    room = 32 - (offs & 31) - lens
    head = jnp.where(
        room >= 0,
        codes << jnp.maximum(room, 0).astype(jnp.uint32),
        codes >> jnp.maximum(-room, 0).astype(jnp.uint32),
    )
    tail = jnp.where(
        room < 0, codes << (32 + jnp.minimum(room, 0)).astype(jnp.uint32), jnp.uint32(0)
    )
    word = offs >> 5
    steps = jnp.concatenate([jnp.ones(1, bool), word[1:] != word[:-1]])
    # codes that start no word go out of bounds (each to its own index) and
    # are dropped; words past the end keep `size`, whose heads and tail are 0
    first = jnp.full(n_words + 1, size, jnp.int32).at[
        jnp.where(steps, word, n_words + 1 + idx)
    ].set(idx, mode="drop", unique_indices=True)
    zero = jnp.zeros(1, jnp.uint32)
    before = jnp.stack(
        [
            jnp.concatenate([zero, jnp.cumsum(head, dtype=jnp.uint32)]),  # heads before
            jnp.concatenate([zero, tail]),  # the tail of the code before
        ],
        axis=1,
    )[first]
    words = (before[1:, 0] - before[:-1, 0]) | before[:-1, 1]
    piece = min(_PIECE_WORDS, n_words)
    pieces = _bswap32(words).reshape(-1, piece)
    sync = offs.reshape(-1, _SYNC)[:, 0]
    return sync, tuple(pieces[i] for i in range(pieces.shape[0]))


def _bucket(k: int) -> int:
    """The power of two at or above ``k`` (>= 1)."""
    return 1 << max(0, k - 1).bit_length()


def _size_bucket(n: int) -> int:
    """``n`` rounded up to one of eight sizes an octave: at most an eighth
    of padding, every gather and scatter of which costs as a code's does."""
    step = 1 << max(0, n.bit_length() - 4)
    return -(-n // step) * step


def pack_stream(syms: np.ndarray, table: np.ndarray, total_bits: int):
    """Pack ``syms`` (values below ``TABLE_SIZE``, at most ``MAX_SYMBOLS``
    of them) on the default device.

    ``table`` is the dense table (:func:`table_entries`) and ``total_bits``
    the stream's length in bits, which the host knows from its histogram.
    The symbols are padded to one of eight sizes an octave and the words
    sized to a power of two, so a compile serves every stream of a size and
    entropy bucket.  Returns the sync offsets (uint32, one every ``_SYNC``
    symbols) and the payload (uint8, ``ceil(total_bits / 8)`` bytes).
    """
    n = syms.size
    size = max(_MIN_SIZE, _size_bucket(n))
    buf = np.zeros(size, np.uint16)
    buf[:n] = syms
    need = -(-total_bits // 32)
    sync, pieces = huffman_pack(buf, table, np.int32(n), n_words=_bucket(need))
    piece = pieces[0].shape[0]
    sync, pieces = jax.device_get((sync, pieces[: -(-need // piece)]))
    payload = np.concatenate(pieces).view(np.uint8)[: (total_bits + 7) >> 3]
    return sync[: -(-n // _SYNC)].astype(np.uint32), payload
