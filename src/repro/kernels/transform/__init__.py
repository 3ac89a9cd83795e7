from .ops import (
    AMP_1AXIS,
    fwd_pipeline,
    inv_pipeline,
    ref_fwd,
    ref_inv,
    transform_fwd,
    transform_inv,
)

__all__ = [
    "AMP_1AXIS",
    "fwd_pipeline",
    "inv_pipeline",
    "ref_fwd",
    "ref_inv",
    "transform_fwd",
    "transform_inv",
]
