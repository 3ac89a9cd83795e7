from .ops import (
    lorenzo_decode,
    lorenzo_encode,
    ref_decode,
    ref_encode,
)

__all__ = [
    "lorenzo_encode",
    "lorenzo_decode",
    "ref_encode",
    "ref_decode",
]
