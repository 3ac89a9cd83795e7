"""The engine table: every named pipeline, and the ``auto`` contest's roster.

This module sits at the top of ``repro.core``: it imports every engine
module, and no engine module imports it at load time.  A lower module that
needs the table (``chunking._make_pipeline`` building a contest entrant,
``quality`` reading its default roster) imports it inside the function, at
call time.

Decoding does not go through this table: ``pipeline.decompress`` dispatches
on the container's ``kind`` tag, so a blob decodes whatever engine wrote it.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

from .blockwise import sz3_hybrid
from .chunking import ChunkedCompressor, sz3_chunked, sz3_pwr
from .fastmode import sz3_fast
from .pipeline import (
    sz3_aps,
    sz3_interp,
    sz3_lorenzo,
    sz3_lr,
    sz3_pastri,
    sz3_truncation,
    sz_pastri,
    sz_pastri_zstd,
)
from .quality import sz3_quality
from .transform import sz3_transform

#: the ``auto`` contest: prediction, transform, block-hybrid and fixed-length
#: entrants, the online SZ/ZFP/SZx selection.  The order breaks ties in the
#: contest and is written into a traced container's ``sel`` entries, so it
#: is part of the bytes.
AUTO_CANDIDATES: Tuple[str, ...] = (
    "sz3_lorenzo",
    "sz3_lr",
    "sz3_interp",
    "sz3_transform",
    "sz3_hybrid",
    "sz3_fast",
)


def sz3_auto(
    candidates: Optional[Sequence[str]] = None,
    chunk_bytes: int = 1 << 22,
    workers: int = 1,
    **kw,
) -> ChunkedCompressor:
    """Chunked engine contesting every coder family per chunk
    (``candidates=None`` is :data:`AUTO_CANDIDATES`)."""
    return ChunkedCompressor(
        candidates=AUTO_CANDIDATES if candidates is None else candidates,
        chunk_bytes=chunk_bytes,
        workers=workers,
        **kw,
    )


#: name -> factory for every engine; ``Sz3Codec(predictor=...)``, the chunked
#: contest and ``jitmode.host_compress`` build engines from it
PIPELINES = {
    "sz3_aps": sz3_aps,
    "sz3_auto": sz3_auto,
    "sz3_chunked": sz3_chunked,
    "sz3_fast": sz3_fast,
    "sz3_hybrid": sz3_hybrid,
    "sz3_interp": sz3_interp,
    "sz3_lorenzo": sz3_lorenzo,
    "sz3_lr": sz3_lr,
    "sz3_pastri": sz3_pastri,
    "sz3_pwr": sz3_pwr,
    "sz3_quality": sz3_quality,
    "sz3_transform": sz3_transform,
    "sz3_truncation": sz3_truncation,
    "sz_pastri": sz_pastri,
    "sz_pastri_zstd": sz_pastri_zstd,
}
