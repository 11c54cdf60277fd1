"""Finds a cell's pieces by the names in BENCHMARK.json.

Every piece that belongs to one configuration, traffic mix, per-layer
metric or cell is a file of its own, found by its name:

    bench/configs/<config>.json     sizes and solver settings
    bench/traffic/<traffic>.json    parameters of the load, read by the
                                    load module its "load" key names
    bench/metrics/<metric>.py       one reader per per-layer metric
    bench/limits/<workload>.json    the limits `correct` is judged by
    bench/peaks.json                published peaks, keyed by device kind

so a later change adds a cell, a mix or a metric by adding files.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Any, NamedTuple

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
MANIFEST = ROOT / "BENCHMARK.json"


class SpecError(ValueError):
    """A name in the manifest that has no file, or a file that is malformed."""


class Cell(NamedTuple):
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list      # manifest entries reported with --trace 0
    per_layer: list       # manifest entries reported with --trace 1


def _load_json(path: Path) -> dict:
    if not path.is_file():
        raise SpecError(f"no file {path.relative_to(ROOT)}")
    with open(path) as f:
        return json.load(f)


def manifest(path: Path = MANIFEST) -> dict:
    return _load_json(path)


def _reports(metric: dict, workload: str) -> bool:
    return workload in metric.get("workloads", [workload])


def cell(workload: str, man: dict | None = None,
         bench_dir: Path = BENCH_DIR) -> Cell:
    """The cell named `workload`, with every file it names loaded."""
    man = manifest() if man is None else man
    by_name = {w["name"]: w for w in man["workloads"]}
    if workload not in by_name:
        raise SpecError(f"unknown workload {workload!r}; have "
                        f"{sorted(by_name)}")
    w = by_name[workload]
    if w["config"] not in {c["name"] for c in man["configs"]}:
        raise SpecError(f"workload {workload!r} names config "
                        f"{w['config']!r}, which the manifest lacks")
    return Cell(
        workload, int(w["chips"]),
        _load_json(bench_dir / "configs" / f"{w['config']}.json"),
        _load_json(bench_dir / "traffic" / f"{w['traffic']}.json"),
        _load_json(bench_dir / "limits" / f"{workload}.json"),
        [m for m in man["end_to_end"] if _reports(m, workload)],
        [m for m in man["per_layer"] if _reports(m, workload)])


def metric_reader(name: str, bench_dir: Path = BENCH_DIR):
    """The `read(ctx)` function of bench/metrics/<name>.py."""
    path = bench_dir / "metrics" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no reader {path.relative_to(bench_dir.parent)} "
                        f"for per-layer metric {name!r}")
    spec = importlib.util.spec_from_file_location(
        "bench.metrics." + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def peaks(device_kind: str, bench_dir: Path = BENCH_DIR) -> dict[str, Any]:
    """Published peaks of `device_kind`; a kind not in the table is an
    error, never a default."""
    table = _load_json(bench_dir / "peaks.json")
    if device_kind not in table["kinds"]:
        raise SpecError(f"device kind {device_kind!r} is not in "
                        f"bench/peaks.json (have {sorted(table['kinds'])})")
    return table["kinds"][device_kind]
