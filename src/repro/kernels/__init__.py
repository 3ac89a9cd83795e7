# Pallas TPU kernels for the compression hot-spots the paper optimizes
# (predict+quantize, bitplane encode) plus the serving-path KV quantization.
# Each package: kernel.py (pl.pallas_call + BlockSpec), ops.py (jit wrapper),
# ref.py (pure-jnp oracle).  Validated in interpret mode on CPU; compiled on
# TPU (routing.py resolves the mode from the backend).
from . import bitplane, kvquant, lorenzo, transform  # noqa: F401
