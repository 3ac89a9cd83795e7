"""Find everything a cell needs by the names in ``BENCHMARK.json``.

A workload names a configuration and a traffic mix.  The configuration's
file (``configs/<config>.json``) names the runner that runs it
(``runners/<runner>.py``); the traffic mix is ``traffic/<traffic>.json``.
Per-layer metrics are ``metrics/<metric>.py`` and kernels
``kernels/<kernel>.json``.  Adding a cell, a configuration, a mix or a
metric therefore means adding files and entries, never editing one.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]


class SpecError(ValueError):
    """A name in BENCHMARK.json has no file, or a file is malformed."""


def load_module(path: Path, name: str):
    """Import a file by path (metric and runner names contain dots)."""
    if not path.is_file():
        raise SpecError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(f"chipbench_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(path: Path) -> Dict[str, Any]:
    if not path.is_file():
        raise SpecError(f"no file {path}")
    return json.loads(path.read_text())


@dataclass
class Cell:
    """One workload of BENCHMARK.json with its files resolved."""

    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    bench_dir: Path = BENCH_DIR

    @property
    def runner(self):
        return load_module(
            self.bench_dir / "runners" / f"{self.config['runner']}.py",
            self.config["runner"],
        )

    def metric_reader(self, name: str):
        return load_module(self.bench_dir / "metrics" / f"{name}.py", name)

    def kernel(self, name: str) -> Dict[str, Any]:
        return _json(self.bench_dir / "kernels" / f"{name}.json")


def _applies(metric: Dict[str, Any], cell: str, e2e_names: List[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def load_cell(workload: str, root: Optional[Path] = None,
              bench_dir: Optional[Path] = None) -> Cell:
    """Resolve ``workload`` from ``<root>/BENCHMARK.json``."""
    bench_dir = bench_dir or BENCH_DIR
    root = root or bench_dir.parent
    bench = _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SpecError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    confs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in confs:
        raise SpecError(f"workload {workload!r} names unknown config {w['config']!r}")
    config = _json(root / confs[w["config"]]["file"])
    traffic = _json(bench_dir / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    e2e_names = [m["name"] for m in e2e]
    per_layer = [m for m in bench["per_layer"] if _applies(m, workload, e2e_names)]
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer,
                bench_dir=bench_dir)


def peaks_for(kind: str, bench_dir: Optional[Path] = None) -> Dict[str, Any]:
    """The peak table's row for a ``device_kind``; an unknown kind is an error."""
    table = _json((bench_dir or BENCH_DIR) / "peaks.json")
    if kind not in table["devices"]:
        raise SpecError(f"no peaks for device kind {kind!r} in peaks.json "
                        f"(known: {sorted(table['devices'])})")
    return table["devices"][kind]
