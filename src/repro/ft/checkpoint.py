"""SZ3-compressed, atomic, async checkpointing (deliverable: fault tolerance).

Integration of the paper's pipelines at the checkpoint boundary:

  * bf16/int parameters   -> lossless: byte-shuffle (BLOSC-style, §3.2
    "Lossless Compressor" instances) + zstd.
  * f32 optimizer moments -> error-bounded lossy: dual-quant Lorenzo pipeline
    with a value-range-relative bound (default 1e-4) — moments tolerate
    bounded error (validated by tests/test_ft.py convergence checks).
  * arbitrary per-path policy overrides (the composability thesis: choosing a
    pipeline per tensor is a config change, paper §3.3).

Durability: manifest + one blob per leaf written to a temp dir, fsync'd, then
atomically renamed to ``step_<n>``; a crash mid-save never corrupts the
previous checkpoint.  Saves run on a background thread (async=True) double-
buffered against training.  Restore targets ANY mesh: leaves are materialized
on host and re-device_put with the new sharding (ft/elastic.py).
"""
from __future__ import annotations

import concurrent.futures as cf
import dataclasses
import hashlib
import json
import os
import shutil
import threading
import time
import zlib
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from ..core import (
    ChunkedCompressor,
    CompressionConfig,
    ErrorBoundMode,
    QualityCompressor,
    decompress as sz3_decompress,
    integrity,
    sz3_lorenzo,
    telemetry,
)
from ..core.integrity import IntegrityError, decode_errors
from ..core.lossless import Zstd, make as make_lossless

# leaves at/above this size go through the chunked engine (bounded working
# memory per chunk + per-chunk pipeline selection) instead of one-shot Lorenzo
_CHUNKED_MIN_BYTES = 1 << 22

# chunk workers for large lossy leaves: saves run on a background thread
# already, so stay modest — half the cores, at least 1
_CHUNK_WORKERS = max(1, (os.cpu_count() or 2) // 2)


# ---------------------------------------------------------------------------
# per-leaf codecs
# ---------------------------------------------------------------------------

def _byteshuffle(raw: bytes, itemsize: int) -> bytes:
    a = np.frombuffer(raw, np.uint8)
    n = a.size - (a.size % itemsize)
    if n == 0 or itemsize == 1:
        return raw
    body = a[:n].reshape(-1, itemsize).T.copy().tobytes()
    return body + a[n:].tobytes()


def _byteunshuffle(raw: bytes, itemsize: int, nbytes: int) -> bytes:
    n = nbytes - (nbytes % itemsize)
    a = np.frombuffer(raw[: n], np.uint8)
    body = a.reshape(itemsize, -1).T.copy().tobytes()
    return body + raw[n:]


@dataclasses.dataclass(frozen=True)
class LeafPolicy:
    mode: str = "lossless"  # "lossless" | "lossy" | "psnr" | "raw"
    rel_eb: float = 1e-4  # for lossy
    target_psnr: float = 60.0  # for psnr: quality-targeted rate control —
    # the leaf is stored at whatever error bound the closed-loop controller
    # finds to hit the PSNR floor, instead of a hand-picked eb


@dataclasses.dataclass(frozen=True)
class CheckpointPolicy:
    """Path-keyed policies; first substring match wins."""

    rules: Tuple[Tuple[str, LeafPolicy], ...] = (
        ("opt/m", LeafPolicy("lossy", 1e-4)),
        ("opt/v", LeafPolicy("lossy", 1e-4)),
        ("feedback", LeafPolicy("lossy", 1e-4)),
        ("", LeafPolicy("lossless")),
    )

    def for_path(self, path: str) -> LeafPolicy:
        for pat, pol in self.rules:
            if pat in path:
                return pol
        return LeafPolicy("lossless")


_zstd = Zstd(level=3)


def encode_leaf(
    arr: np.ndarray, pol: LeafPolicy, workers: Optional[int] = None
) -> Tuple[bytes, Dict[str, Any]]:
    meta: Dict[str, Any] = {
        "shape": list(arr.shape),
        # extension dtypes (ml_dtypes' bfloat16) have no array-protocol
        # string of their own (``.str`` is '<V2'): record them by name
        "dtype": arr.dtype.name if arr.dtype.kind == "V" else arr.dtype.str,
        "mode": pol.mode,
    }
    if (
        pol.mode in ("lossy", "psnr")
        and arr.dtype in (np.float32, np.float64)
        and arr.size >= 1024
        and np.isfinite(arr).all()
        and float(arr.max() - arr.min()) > 0
    ):
        flat2d = arr.reshape(arr.shape[0], -1) if arr.ndim > 1 else arr
        if pol.mode == "psnr":
            # quality-targeted: the controller finds the bound per chunk;
            # big leaves parallelize exactly like the chunked path
            comp = QualityCompressor(
                target_psnr=pol.target_psnr,
                workers=(_CHUNK_WORKERS if workers is None else workers)
                if arr.nbytes >= _CHUNKED_MIN_BYTES
                else 1,
            )
            meta["codec"] = "sz3_psnr"
            res = comp.compress(np.ascontiguousarray(flat2d))
            meta["achieved_psnr"] = float(res.meta["quality"]["achieved_psnr"])
            return res.blob, meta
        conf = CompressionConfig(mode=ErrorBoundMode.REL, eb=pol.rel_eb)
        if arr.nbytes >= _CHUNKED_MIN_BYTES:
            # every coder family contests per chunk: optimizer moments are
            # usually Lorenzo-friendly, attention-derived leaves can be
            # oscillatory along the feature axis (transform wins those),
            # leaves mixing regimes — embedding tables with hot/cold rows,
            # moments with dead blocks — go to the block-hybrid engine, and
            # near-constant slabs (zero-init moments) let the fixed-length
            # fast tier win on its constant-block path
            comp = ChunkedCompressor(
                candidates=(
                    "sz3_lorenzo",
                    "sz3_lr",
                    "sz3_transform",
                    "sz3_hybrid",
                    "sz3_fast",
                ),
                workers=_CHUNK_WORKERS if workers is None else workers,
            )
            meta["codec"] = "sz3_auto_rel"
        else:
            comp = sz3_lorenzo()
            meta["codec"] = "sz3_lorenzo_rel"
        res = comp.compress(np.ascontiguousarray(flat2d), conf)
        return res.blob, meta
    if pol.mode == "raw":
        meta["codec"] = "raw"
        return arr.tobytes(), meta
    raw = _byteshuffle(arr.tobytes(), arr.dtype.itemsize)
    # record the ACTUAL backend (the Zstd class degrades to 'gzip' when
    # zstandard is missing) so restore picks the right decompressor anywhere
    meta["codec"] = f"shuffle_{_zstd.name}"
    return _zstd.compress(raw), meta


def decode_leaf(blob: bytes, meta: Dict[str, Any]) -> np.ndarray:
    shape = tuple(meta["shape"])
    dtype = np.dtype(meta["dtype"])
    codec = meta["codec"]
    if codec in ("sz3_lorenzo_rel", "sz3_chunked_rel", "sz3_auto_rel", "sz3_psnr"):
        # all are self-describing SZ3 containers (v1 / v2 multi-chunk / v3)
        arr = sz3_decompress(blob)
        return arr.reshape(shape).astype(dtype)
    if codec == "raw":
        return np.frombuffer(blob, dtype).reshape(shape).copy()
    nbytes = int(np.prod(shape)) * dtype.itemsize if shape else dtype.itemsize
    lname = codec.split("_", 1)[1] if codec.startswith("shuffle_") else "zstd"
    backend = _zstd if lname == _zstd.name else make_lossless(lname)
    raw = _byteunshuffle(backend.decompress(blob), dtype.itemsize, nbytes)
    return np.frombuffer(raw, dtype, count=int(np.prod(shape)) if shape else 1).reshape(shape).copy()


# ---------------------------------------------------------------------------
# manager
# ---------------------------------------------------------------------------

def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


class CheckpointManager:
    def __init__(
        self,
        directory: str,
        policy: CheckpointPolicy = CheckpointPolicy(),
        keep: int = 3,
        use_async: bool = True,
        workers: Optional[int] = None,
    ):
        self.dir = Path(directory)
        self.dir.mkdir(parents=True, exist_ok=True)
        self.policy = policy
        self.keep = keep
        self.workers = workers  # chunk workers for large lossy leaves
        self._pool = cf.ThreadPoolExecutor(max_workers=1) if use_async else None
        self._pending: Optional[cf.Future] = None
        self._lock = threading.Lock()

    # -- save ---------------------------------------------------------------
    def save(self, step: int, state, extra: Optional[Dict[str, Any]] = None):
        """Snapshot to host, then (optionally async) compress + atomic write."""
        host_state = jax.tree.map(lambda x: np.asarray(x), state)
        if self._pool is None:
            self._write(step, host_state, extra)
            return None
        self.wait()
        self._pending = self._pool.submit(self._write, step, host_state, extra)
        return self._pending

    def wait(self):
        if self._pending is not None:
            self._pending.result()
            self._pending = None

    def _write(self, step: int, host_state, extra):
        tmp = self.dir / f".tmp_step_{step}"
        final = self.dir / f"step_{step}"
        if tmp.exists():
            shutil.rmtree(tmp)
        tmp.mkdir(parents=True)
        leaves = {}
        flat, _ = jax.tree_util.tree_flatten_with_path(host_state)
        total_in = total_out = 0
        for path, leaf in flat:
            pstr = _path_str(path)
            pol = self.policy.for_path(pstr)
            arr = np.asarray(leaf)
            t_leaf = time.perf_counter()
            with telemetry.span("leaf", path=pstr, bytes=arr.nbytes):
                blob, meta = encode_leaf(arr, pol, workers=self.workers)
            d_leaf = time.perf_counter() - t_leaf
            # per-leaf observability: which codec won, what it cost, what it
            # bought — queryable from the manifest long after the run
            meta["seconds"] = round(d_leaf, 6)
            meta["ratio"] = round(arr.nbytes / max(1, len(blob)), 4)
            telemetry.metric_observe("sz3_checkpoint_leaf_seconds", d_leaf)
            fname = hashlib.sha1(pstr.encode()).hexdigest()[:16] + ".bin"
            (tmp / fname).write_bytes(blob)
            meta["file"] = fname
            meta["crc"] = zlib.crc32(blob)  # kept for pre-integrity readers
            # algorithm-tagged per-leaf checksum (CRC32C when available) —
            # the manifest-side twin of the container trailer, covering raw
            # and lossless leaves that carry no SZ3J framing
            meta["csum"] = {
                "a": integrity.CHECKSUM_ALGO,
                "v": integrity.checksum(blob),
            }
            leaves[pstr] = meta
            total_in += arr.nbytes
            total_out += len(blob)
        manifest = {
            "step": step,
            "leaves": leaves,
            "bytes_in": total_in,
            "bytes_out": total_out,
            "ratio": total_in / max(1, total_out),
            "extra": extra or {},
        }
        (tmp / "manifest.json").write_text(json.dumps(manifest, indent=2))
        telemetry.metric_count("sz3_checkpoint_saves_total")
        telemetry.metric_count("sz3_checkpoint_bytes_out_total", total_out)
        # fsync the directory entries before rename (durability)
        for f in tmp.iterdir():
            fd = os.open(f, os.O_RDONLY)
            os.fsync(fd)
            os.close(fd)
        if final.exists():
            shutil.rmtree(final)
        os.rename(tmp, final)
        self._gc()
        return manifest

    def _gc(self):
        steps = sorted(self.list_steps())
        for s in steps[: -self.keep]:
            shutil.rmtree(self.dir / f"step_{s}", ignore_errors=True)

    # -- restore --------------------------------------------------------------
    def list_steps(self):
        out = []
        for p in self.dir.glob("step_*"):
            try:
                out.append(int(p.name.split("_")[1]))
            except ValueError:
                pass
        return sorted(out)

    def restore(
        self,
        template,
        step: Optional[int] = None,
        *,
        salvage: bool = False,
        io_retries: int = 3,
        io_backoff: float = 0.05,
    ):
        """Restore into the structure of ``template`` (host numpy leaves).

        ``template`` supplies the pytree structure (e.g. from
        jax.eval_shape(init_fn)); leaves are validated against the manifest
        and their per-leaf checksums.  Returns ``(state, extra)``.

        ``salvage=True`` turns a corrupt leaf from a restore-killing error
        into a local loss: damaged / missing / shape-mismatched leaves are
        REFILLED from the template's own values (zeros when the template
        leaf is shape-only, e.g. ``jax.eval_shape`` output) and the call
        returns ``(state, extra, RestoreReport)`` naming what was refilled —
        the training loop decides whether a warm restart from N-1 leaves
        beats losing the checkpoint entirely.

        Transient I/O errors (``OSError`` other than a missing file) are
        retried ``io_retries`` times with exponential backoff starting at
        ``io_backoff`` seconds — NFS blips and overloaded object stores
        should not look like corruption."""
        steps = self.list_steps()
        if not steps:
            raise FileNotFoundError(f"no checkpoints under {self.dir}")
        step = steps[-1] if step is None else step
        d = self.dir / f"step_{step}"
        manifest = json.loads(
            self._read_retry(d / "manifest.json", io_retries, io_backoff).decode()
        )
        leaves = manifest["leaves"]
        flat, treedef = jax.tree_util.tree_flatten_with_path(template)
        out = []
        report = RestoreReport(step=int(step))
        for path, leaf in flat:
            pstr = _path_str(path)
            try:
                arr = self._restore_leaf(
                    d, leaves, pstr, step, leaf, io_retries, io_backoff
                )
            except FileNotFoundError:
                if not salvage:
                    raise
                arr, reason = None, "missing"
            except (KeyError, LookupError):
                if not salvage:
                    raise
                arr, reason = None, "missing"
            except (IntegrityError, IOError) as e:
                if not salvage:
                    raise
                arr, reason = None, "checksum"
            except ValueError:
                if not salvage:
                    raise
                arr, reason = None, "decode-error"
            if arr is None:
                arr = _template_fill(leaf)
                report.refilled.append((pstr, reason))
            else:
                report.restored.append(pstr)
            out.append(arr)
        state = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(template), out
        )
        extra = manifest.get("extra", {})
        if salvage:
            return state, extra, report
        return state, extra

    def _restore_leaf(
        self, d: Path, leaves, pstr: str, step, leaf, io_retries, io_backoff
    ) -> np.ndarray:
        if pstr not in leaves:
            raise KeyError(f"leaf {pstr} missing from checkpoint {step}")
        meta = leaves[pstr]
        blob = self._read_retry(d / meta["file"], io_retries, io_backoff)
        csum = meta.get("csum")
        if csum is not None:
            if integrity.checksum(blob, algo=csum["a"]) != csum["v"]:
                raise IntegrityError(
                    f"leaf {pstr} fails its {csum['a']} checksum — corrupt "
                    "checkpoint"
                )
        elif zlib.crc32(blob) != meta["crc"]:  # pre-integrity manifests
            raise IOError(f"checksum mismatch for {pstr} — corrupt checkpoint")
        with decode_errors(f"checkpoint leaf {pstr}"):
            arr = decode_leaf(blob, meta)
        want_shape = tuple(getattr(leaf, "shape", arr.shape))
        if tuple(arr.shape) != want_shape:
            raise ValueError(
                f"{pstr}: checkpoint shape {arr.shape} != expected {want_shape}"
            )
        return arr

    @staticmethod
    def _read_retry(path: Path, retries: int, backoff: float) -> bytes:
        """Read with bounded retry-with-backoff on transient I/O errors.
        A missing file is NOT transient (the checkpoint layout is immutable
        once renamed into place) and raises immediately."""
        attempt = 0
        while True:
            try:
                return path.read_bytes()
            except FileNotFoundError:
                raise
            except OSError:
                if attempt >= retries:
                    raise
                time.sleep(backoff * (2**attempt))
                attempt += 1


@dataclasses.dataclass
class RestoreReport:
    """What a ``salvage=True`` restore recovered vs refilled."""

    step: int
    restored: List[str] = dataclasses.field(default_factory=list)
    refilled: List[Tuple[str, str]] = dataclasses.field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.refilled

    def summary(self) -> str:
        if self.ok:
            return f"restore step {self.step}: all {len(self.restored)} leaves"
        lost = ", ".join(f"{p} ({r})" for p, r in self.refilled)
        return (
            f"restore step {self.step}: {len(self.restored)} leaves restored, "
            f"{len(self.refilled)} refilled from template: {lost}"
        )


def _template_fill(leaf) -> np.ndarray:
    """A replacement value for a leaf the checkpoint could not supply: the
    template's own value when it carries one, zeros when it is shape-only
    (``jax.eval_shape`` / ``ShapeDtypeStruct`` templates)."""
    if hasattr(leaf, "__array__"):
        return np.asarray(leaf)
    return np.zeros(
        tuple(getattr(leaf, "shape", ())), np.dtype(getattr(leaf, "dtype", "f4"))
    )
