"""Where the Pallas kernels run: compiled on a TPU, interpreted elsewhere.

The one routing point for every ``kernels/*/ops.py`` entry point (their
``interpret=None`` default resolves here) and for the device routes of
``core/`` (Lorenzo, transform and fast-tier stats), which engage under
``device="auto"`` only where :func:`on_tpu` holds.  Tests steer a kernel by
passing ``interpret`` explicitly, or a core route with ``device="force"``.
"""
from __future__ import annotations

from typing import Optional

import jax


def on_tpu() -> bool:
    """True when the default backend is a TPU (kernels compile with Mosaic)."""
    return jax.default_backend() == "tpu"


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """Resolve a kernel's ``interpret`` flag: ``None`` -> by backend."""
    return not on_tpu() if interpret is None else bool(interpret)
