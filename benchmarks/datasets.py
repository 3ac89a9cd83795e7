"""Synthetic analogues of the paper's evaluation datasets.

The real GAMESS ERI / APS ptychography / SDRBench fields are not
redistributable offline, so each generator reproduces the *structural
characteristics the paper's method exploits*, with knobs calibrated so the
qualitative orderings of the paper hold (pattern periodicity & scale decay
for GAMESS [§4.1]; photon-count Poisson stacks with strong temporal and weak
spatial correlation for APS [§5.1]; smooth multi-scale Gaussian random fields
with domain-appropriate spectra for the 8-dataset table [§6.2 Table 3]).
Every generator is deterministic in (seed, size).
"""
from __future__ import annotations

import numpy as np


def gamess_eri(
    n_blocks: int = 20000,
    pattern: int = 96,
    unpred_frac: float = 0.15,
    eb: float = 1e-10,
    seed: int = 7,
    dtype=np.float64,
) -> np.ndarray:
    """Two-electron repulsion integral stream: periodic pattern scaled per
    block (SZ-Pastri's premise).  Residuals after scaled-pattern prediction
    are calibrated against the target error bound so the quantization-
    integer statistics match paper Fig 3: a zero-centred population of
    predictable codes plus ~15-20% heavy-tail points outside the range
    ("a significant percentage (20%) ... fall out of the quantization
    range"), which is exactly the regime the unpred-aware quantizer (§4.2)
    attacks."""
    rng = np.random.default_rng(seed)
    t = np.linspace(0, 1, pattern)
    base = np.exp(-6 * t) * np.sin(24 * t) + 0.3 * np.exp(-9 * t) * np.cos(53 * t)
    scales = np.exp(rng.normal(-6.0, 2.5, n_blocks))  # log-normal magnitudes
    x = scales[:, None] * base[None, :]
    # predictable residuals: a few quantization bins wide
    x = x + rng.normal(0.0, 15.0 * eb, (n_blocks, pattern))
    # non-conforming blocks: integrals whose shell shape breaks the pattern
    # (block-level, as in real ERI tiles) -> their points fall out of the
    # quantization range but keep smooth structure the bitplane encoding
    # exploits (paper §4.2)
    bad = rng.random(n_blocks) < unpred_frac
    alt = np.exp(-3 * t) * np.cos(31 * t + 0.7)
    alt_scales = np.exp(rng.normal(-9.0, 1.5, n_blocks))
    x[bad] += alt_scales[bad, None] * alt[None, :]
    return np.ascontiguousarray(x.reshape(-1).astype(dtype))


def aps_ptycho(
    frames: int = 400, h: int = 64, w: int = 64, seed: int = 11
) -> np.ndarray:
    """X-ray diffraction stack: integer photon counts, bright central speckle,
    high correlation along time (scan positions move slowly), low spatial
    correlation — the regime where the paper's transposed-1D pipeline wins."""
    rng = np.random.default_rng(seed)
    yy, xx = np.mgrid[0:h, 0:w]
    r2 = ((yy - h / 2) ** 2 + (xx - w / 2) ** 2) / (0.08 * h * w)
    envelope = 40.0 * np.exp(-r2)
    # slowly-drifting speckle field -> temporal correlation
    phase = rng.standard_normal((h, w))
    drift = rng.standard_normal((h, w)) * 0.05
    out = np.empty((frames, h, w), np.float32)
    for t in range(frames):
        speckle = np.abs(np.fft.ifft2(np.fft.fft2(np.exp(1j * (phase + t * drift))) * np.exp(-r2)))
        lam = envelope * (0.2 + speckle / max(1e-9, speckle.max()))
        out[t] = rng.poisson(lam).astype(np.float32)
    return out


def gaussian_random_field(shape, slope: float, seed: int) -> np.ndarray:
    """FFT-synthesized field with power-law spectrum k^-slope."""
    rng = np.random.default_rng(seed)
    white = rng.standard_normal(shape)
    f = np.fft.fftn(white)
    k = np.zeros(shape)
    for ax, n in enumerate(shape):
        kk = np.fft.fftfreq(n) * n
        sh = [1] * len(shape)
        sh[ax] = n
        k = k + (kk.reshape(sh)) ** 2
    k = np.sqrt(np.maximum(k, 1e-9))
    f = f * k ** (-slope / 2.0)
    out = np.real(np.fft.ifftn(f))
    out = (out - out.mean()) / (out.std() + 1e-12)
    return out.astype(np.float32)


DOMAIN_FIELDS = {
    # name: (shape, spectral slope, post)
    "hacc_vx": ((64, 128, 128), 1.2, "none"),  # cosmology particle velocity
    "atm_t2m": ((512, 1024), 2.8, "none"),  # climate 2-D, very smooth
    "hurricane_p": ((48, 128, 128), 2.2, "none"),
    "nyx_rho": ((96, 96, 96), 1.8, "exp"),  # density: log-normal-ish
    "scale_qv": ((48, 160, 160), 2.4, "relu"),  # moisture: nonneg, sharp
    "qmcpack_o": ((24, 48, 48, 48), 1.6, "none"),  # 4-D orbital
    "rtm_wave": ((96, 96, 96), 1.4, "wave"),  # seismic wavefield
    "miranda_u": ((96, 128, 128), 2.0, "none"),  # turbulence
}


def domain_field(name: str, seed: int = 3) -> np.ndarray:
    shape, slope, post = DOMAIN_FIELDS[name]
    x = gaussian_random_field(shape, slope, seed + hash(name) % 1000)
    if post == "exp":
        x = np.exp(1.5 * x).astype(np.float32)
    elif post == "relu":
        x = np.maximum(x, 0).astype(np.float32)
    elif post == "wave":
        t = np.linspace(0, 6 * np.pi, shape[0], dtype=np.float32)
        x = (x * np.sin(t)[:, None, None]).astype(np.float32)
    return x


def all_domain_fields(seed: int = 3):
    return {k: domain_field(k, seed) for k in DOMAIN_FIELDS}
