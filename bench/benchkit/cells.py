"""Find a cell's pieces by name: nothing here names a cell, a configuration,
a traffic mix or a metric.

``BENCHMARK.json`` lists the cells and metrics.  A configuration is the file
its entry names; a traffic mix is ``traffic/<name>.json``; a per-layer
metric is the reader ``metrics/<name>.py``, whose ``read(ctx)`` returns a
number or ``None`` when the run gave it nothing to read.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple[dict, ...]
    per_layer: tuple[dict, ...]


def load_spec(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(spec: dict, cell_name: str, root: Path = ROOT,
            bench: Path = BENCH) -> Cell:
    cells = {w["name"]: w for w in spec["workloads"]}
    if cell_name not in cells:
        raise KeyError(f"no cell {cell_name!r}; have {sorted(cells)}")
    w = cells[cell_name]
    configs = {c["name"]: c for c in spec["configs"]}
    with open(root / configs[w["config"]]["file"]) as f:
        config = json.load(f)
    with open(bench / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    e2e = tuple(m for m in spec["end_to_end"] if _applies(m, cell_name))
    layer = tuple(m for m in spec["per_layer"] if _applies(m, cell_name))
    return Cell(cell_name, int(w["chips"]), config, traffic, e2e, layer)


def reader(metric: str, bench: Path = BENCH):
    """The ``read`` function of ``metrics/<metric>.py``."""
    path = bench / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}",
                                                  path)
    if spec is None or not path.exists():
        raise FileNotFoundError(f"no reader for metric {metric!r}: {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str, bench: Path = BENCH) -> dict:
    """The chip's published peaks; an unknown kind is an error."""
    with open(bench / "peaks.json") as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in the peak "
                       f"table {sorted(table)}")
    return table[device_kind]
