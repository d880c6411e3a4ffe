"""Find everything a cell needs by the names in `BENCHMARK.json`.

    <root>/BENCHMARK.json            cells, configurations, metrics
    bench/configs/<config>.json      a configuration's sizes and limits
    bench/traffic/<traffic>.json     a traffic mix's parameters; its
                                     "client" names bench/clients/<c>.py
    bench/metrics/<metric>.py        one reader per per-layer metric
    bench/kernels/<kernel>.py        one work count per kernel
    bench/peaks.json                 chip peaks, keyed by device_kind

A new configuration, mix, cell or per-layer metric is new files and
entries: nothing here names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys
from types import ModuleType
from typing import Any

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


class SpecError(ValueError):
    """The benchmark's files do not describe the requested cell."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    end_to_end: tuple[dict, ...]     # metric entries this cell reports
    per_layer: tuple[dict, ...]
    bench_dir: pathlib.Path


def _read_json(path: pathlib.Path) -> Any:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise SpecError(f"missing file {path}") from None


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(workload: str, root: pathlib.Path = ROOT,
              bench_dir: pathlib.Path | None = None) -> Cell:
    bench_dir = bench_dir or root / "bench"
    spec = _read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r}; known: "
                        f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {workload!r} names unknown config "
                        f"{w['config']!r}")
    config = _read_json(root / configs[w["config"]]["file"])
    traffic = _read_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    e2e = tuple(m for m in spec["end_to_end"] if _reports(m, workload))
    e2e_names = {m["name"] for m in e2e}
    per_layer = tuple(
        m for m in spec["per_layer"]
        if (workload in m["workloads"] if "workloads" in m
            else m["moves"] in e2e_names))
    return Cell(workload, int(w["chips"]), w["config"], w["traffic"],
                config, traffic, e2e, per_layer, bench_dir)


_LOADED: dict[pathlib.Path, ModuleType] = {}


def load_module(path: pathlib.Path, qualname: str) -> ModuleType:
    """Import one file by path, once (names may hold dots, as metric
    names do)."""
    path = path.resolve()
    if path in _LOADED:
        return _LOADED[path]
    if not path.is_file():
        raise SpecError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(qualname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[qualname] = mod
    spec.loader.exec_module(mod)
    _LOADED[path] = mod
    return mod


def client(cell: Cell) -> ModuleType:
    name = cell.traffic["client"]
    return load_module(cell.bench_dir / "clients" / f"{name}.py",
                       f"bench_client_{name}")


def metric_reader(cell: Cell, metric: str) -> ModuleType:
    return load_module(cell.bench_dir / "metrics" / f"{metric}.py",
                       f"bench_metric_{metric}")


def kernel(bench_dir: pathlib.Path, name: str) -> ModuleType:
    return load_module(bench_dir / "kernels" / f"{name}.py",
                       f"bench_kernel_{name}")


def kernel_names(bench_dir: pathlib.Path) -> list[str]:
    return sorted(p.stem for p in (bench_dir / "kernels").glob("*.py"))


def peaks(bench_dir: pathlib.Path, device_kind: str) -> dict:
    """The chip's peaks; an unknown device is an error, never a default."""
    table = _read_json(bench_dir / "peaks.json")
    if device_kind not in table["devices"]:
        raise SpecError(f"no peaks for device_kind {device_kind!r} in "
                        f"{bench_dir / 'peaks.json'}; known: "
                        f"{sorted(table['devices'])}")
    return table["devices"][device_kind]
