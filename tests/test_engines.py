"""The engine table (``repro.core.engines``): its names, the ``auto``
contest's roster, and the container bytes the codec writes through it.

The roster's order reaches the ``sel`` entries of every ``auto`` container
and breaks ties in the contest, so it is pinned as a literal; the table must
not depend on which module is imported first.
"""
import hashlib
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.codec import Sz3Codec
from repro.core import AUTO_CANDIDATES, PIPELINES, telemetry

ROSTER = (
    "sz3_lorenzo",
    "sz3_lr",
    "sz3_interp",
    "sz3_transform",
    "sz3_hybrid",
    "sz3_fast",
)
NAMES = {
    "sz3_aps", "sz3_auto", "sz3_chunked", "sz3_fast", "sz3_hybrid",
    "sz3_interp", "sz3_lorenzo", "sz3_lr", "sz3_pastri", "sz3_pwr",
    "sz3_quality", "sz3_transform", "sz3_truncation", "sz_pastri",
    "sz_pastri_zstd",
}
SRC = str(Path(__file__).resolve().parents[1] / "src")


def test_roster_and_table_are_the_literals():
    assert AUTO_CANDIDATES == ROSTER
    assert set(PIPELINES) == NAMES
    assert set(AUTO_CANDIDATES) <= set(PIPELINES)


@pytest.mark.parametrize(
    "first", ["repro.core.fastmode", "repro.core.quality", "repro.core.chunking"]
)
def test_table_does_not_depend_on_import_order(first):
    """A fresh interpreter that imports an engine module first sees the same
    roster and table as one that imports the package."""
    code = (
        f"import {first}\n"
        "import json\n"
        "from repro.core import AUTO_CANDIDATES, PIPELINES\n"
        "print(json.dumps([list(AUTO_CANDIDATES), sorted(PIPELINES)]))\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True,
        timeout=120, check=True,
    ).stdout
    roster, names = json.loads(out.strip().splitlines()[-1])
    assert tuple(roster) == ROSTER
    assert set(names) == NAMES


def test_quality_default_contest_is_the_roster():
    from repro.core import QualityCompressor

    assert QualityCompressor(target_psnr=60.0).candidates == ROSTER


def _field():
    rng = np.random.default_rng(17)
    y, x = np.mgrid[0:256, 0:512].astype(np.float64)
    f = np.sin(x / 23.0) * np.cos(y / 17.0) + 0.05 * rng.standard_normal((256, 512))
    return f.astype(np.float32)


#: sha256 of ``Sz3Codec(eb_mode="rel", eb_rel=1e-3, predictor=p).encode``
#: of ``_field()`` on the host route, taken before the engine table was
#: gathered into one module: moving the table changes no container byte.
#: Under a trace the ``auto`` container also carries each chunk's ``sel``
#: entry, whose ``cands`` list is the roster in order.
BYTE_PINS = {
    ("auto", False): "8de700c539bbe7daebfadf1ede520236656fe45da73d02a64e9cb20f0941bae5",
    ("lorenzo", False): "79bae1a26e27b465db2bdf6852692567917d44bfd6f8dae087ee01b9eb9f657d",
    ("hybrid", False): "ec31cb1450e417424cfa33c9f417a3e89a5d85e8e37b85d5276e93c7676d58e4",
    ("fast", False): "6af87d2b87e7584fa9f30e8ded09d8567869b5462fab1b1a4cc7568f065ecfa9",
    ("auto", True): "6123d8c2762b8e6ecc4e6cd1c932a3dbd732f7ffe51a4420552ae5580bacb07f",
}


@pytest.mark.parametrize(
    "predictor,traced",
    sorted(BYTE_PINS),
    ids=[f"{p}{'-traced' if t else ''}" for p, t in sorted(BYTE_PINS)],
)
def test_codec_bytes_are_pinned(predictor, traced):
    codec = Sz3Codec(eb_mode="rel", eb_rel=1e-3, predictor=predictor)
    if traced:
        with telemetry.trace():
            blob = codec.encode(_field())
    else:
        blob = codec.encode(_field())
    assert hashlib.sha256(blob).hexdigest() == BYTE_PINS[(predictor, traced)]
