"""SZ3 core: modular prediction-based error-bounded lossy compression.

The paper's five-module abstraction (preprocessor -> predictor -> quantizer ->
encoder -> lossless) composed per §3.3, plus the customized pipelines of §4
(GAMESS / SZ3-Pastri), §5 (APS adaptive) and §6.2 (LR / Interp / Truncation).
"""
from . import telemetry
from .telemetry import Trace, explain, trace_summary
from . import encoders, lossless, metrics, predictors, preprocess, quantizers
from . import faults, integrity
from .config import CompressionConfig, ErrorBoundMode
from .integrity import (
    ChunkDamage,
    ContainerError,
    IntegrityError,
    SalvageReport,
    verify_blob,
)
from .pipeline import (
    AdaptiveAPSCompressor,
    CompressionResult,
    SZ3Compressor,
    TruncationCompressor,
    decompress,
    parse_header,
    sz3_aps,
    sz3_interp,
    sz3_lorenzo,
    sz3_lr,
    sz3_pastri,
    sz3_truncation,
    sz_pastri,
    sz_pastri_zstd,
)
from . import chunking
from .chunking import (
    ChunkedCompressor,
    ChunkedIndex,
    PWRelChunkedCompressor,
    compress_stream,
    decompress_chunk,
    parse_chunked_index,
    decompress_stream,
    frames_to_blob,
    read_frames,
    select_pipeline,
    sz3_chunked,
    sz3_pwr,
    write_frames,
)
from . import blockwise, fastmode, quality, transform
from .blockwise import BlockHybridCompressor, sz3_hybrid
from .fastmode import FastModeCompressor, sz3_fast
from .quality import (
    QualityCompressor,
    QualityTarget,
    achieved_quality,
    sz3_quality,
)
from .transform import TransformCompressor, sz3_transform
from . import engines
from .engines import AUTO_CANDIDATES, PIPELINES, sz3_auto

__all__ = [
    "telemetry",
    "Trace",
    "explain",
    "trace_summary",
    "CompressionConfig",
    "ErrorBoundMode",
    "ContainerError",
    "IntegrityError",
    "SalvageReport",
    "ChunkDamage",
    "verify_blob",
    "integrity",
    "faults",
    "SZ3Compressor",
    "TruncationCompressor",
    "AdaptiveAPSCompressor",
    "CompressionResult",
    "decompress",
    "parse_header",
    "PIPELINES",
    "sz3_lr",
    "sz3_interp",
    "sz3_lorenzo",
    "sz3_truncation",
    "sz_pastri",
    "sz_pastri_zstd",
    "sz3_pastri",
    "sz3_aps",
    "ChunkedCompressor",
    "PWRelChunkedCompressor",
    "sz3_chunked",
    "sz3_pwr",
    "QualityCompressor",
    "QualityTarget",
    "achieved_quality",
    "sz3_quality",
    "quality",
    "TransformCompressor",
    "sz3_transform",
    "sz3_auto",
    "AUTO_CANDIDATES",
    "transform",
    "BlockHybridCompressor",
    "sz3_hybrid",
    "blockwise",
    "FastModeCompressor",
    "sz3_fast",
    "fastmode",
    "engines",
    "compress_stream",
    "decompress_stream",
    "decompress_chunk",
    "parse_chunked_index",
    "ChunkedIndex",
    "frames_to_blob",
    "write_frames",
    "read_frames",
    "select_pipeline",
    "chunking",
    "encoders",
    "lossless",
    "metrics",
    "predictors",
    "preprocess",
    "quantizers",
]
