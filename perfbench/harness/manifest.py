"""Find a cell's pieces by name: the manifest (``BENCHMARK.json``) names
cells, configurations and metrics; every configuration, traffic mix,
population kind, limit set and per-layer metric lives in a file of its own
under ``perfbench/``, found by that name. Adding one is adding a file."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from types import ModuleType
from typing import Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _module(path: str, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_manifest(root: str = CHECKOUT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = CHECKOUT) -> Cell:
    """The cell ``name`` with its configuration, traffic and limits, and
    the metrics the manifest reports in it."""
    man = load_manifest(root)
    cells = {w["name"]: w for w in man["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} (have {sorted(cells)})")
    w = cells[name]
    conf = {c["name"]: c for c in man["configs"]}[w["config"]]
    config = _json(os.path.join(root, conf["file"]))
    bench = os.path.join(root, "perfbench")
    traffic = _json(os.path.join(bench, "traffic", f"{w['traffic']}.json"))
    limits = _json(os.path.join(bench, "limits", f"{w['config']}.json"))
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, limits=limits,
                end_to_end=[m for m in man["end_to_end"] if _applies(m, name)],
                per_layer=[m for m in man["per_layer"] if _applies(m, name)])


def population(kind: str) -> ModuleType:
    """The generator module of a population kind."""
    return _module(os.path.join(BENCH_DIR, "populations", f"{kind}.py"),
                   f"population_{kind}")


def metric_reader(name: str, root: str = CHECKOUT) -> ModuleType:
    """The reader of per-layer metric ``name``: ``metrics/<name>.py``, else
    the reader of its family, ``metrics/<name up to the first dot>.py``."""
    top = os.path.join(root, "perfbench", "metrics")
    for stem in (name, name.split(".", 1)[0]):
        path = os.path.join(top, f"{stem}.py")
        if os.path.exists(path):
            return _module(path, "metric_" + stem.replace(".", "_"))
    raise FileNotFoundError(f"no reader for metric {name!r} under {top}")


def peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of one chip of ``device_kind``; a kind missing from
    the table is an error, never a default."""
    table = _json(os.path.join(BENCH_DIR, "peaks.json"))
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in the peak "
                       f"table (have {sorted(table)})")
    return table[device_kind]


def metric_units(cell: Cell) -> Dict[str, str]:
    return {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}

