"""The benchmark's manifest and the files it names.

``BENCHMARK.json`` at the checkout root lists configurations, traffic mixes
and metrics by name; each has a file of its own under ``bench/``, found by
that name alone:

- ``bench/configs/<config>.json``  -- the configuration as it is run;
- ``bench/traffic/<traffic>.json`` -- the parameters of one traffic mix,
  read by the one generator in :mod:`bench.jobs`;
- ``bench/metrics/<metric>.py``    -- the reader of one per-layer metric,
  a module with ``read(ctx) -> float | None``.

A new cell, configuration or metric is therefore added with new files and
manifest entries only.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
MANIFEST = ROOT / "BENCHMARK.json"


@dataclass(frozen=True)
class Cell:
    """One workload of the manifest with everything it names, loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple[dict, ...]
    per_layer: tuple[dict, ...]


def load_manifest(path: Path = MANIFEST) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def config_path(name: str, bench_dir: Path = BENCH_DIR) -> Path:
    return bench_dir / "configs" / f"{name}.json"


def traffic_path(name: str, bench_dir: Path = BENCH_DIR) -> Path:
    return bench_dir / "traffic" / f"{name}.json"


def metric_path(name: str, bench_dir: Path = BENCH_DIR) -> Path:
    return bench_dir / "metrics" / f"{name}.py"


def _load_json(path: Path) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def reports(metric: dict, workload: str) -> bool:
    """Does ``workload`` report ``metric`` (all cells, or those listed)?"""
    return "workloads" not in metric or workload in metric["workloads"]


def cell(workload: str, manifest: dict | None = None,
         bench_dir: Path = BENCH_DIR) -> Cell:
    """The named workload with its configuration, traffic and metrics."""
    manifest = load_manifest() if manifest is None else manifest
    found = [w for w in manifest["workloads"] if w["name"] == workload]
    if not found:
        raise KeyError(f"no workload {workload!r} in the manifest")
    w = found[0]
    return Cell(
        name=w["name"],
        chips=int(w["chips"]),
        config=_load_json(config_path(w["config"], bench_dir)),
        traffic=_load_json(traffic_path(w["traffic"], bench_dir)),
        end_to_end=tuple(m for m in manifest["end_to_end"]
                         if reports(m, w["name"])),
        per_layer=tuple(m for m in manifest["per_layer"]
                        if reports(m, w["name"])),
    )


def load_reader(metric: str, bench_dir: Path = BENCH_DIR):
    """The ``read(ctx)`` function of one per-layer metric's file."""
    path = metric_path(metric, bench_dir)
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_').replace('-', '_')}", path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load metric reader {path}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read
