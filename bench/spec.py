"""Resolve a cell of ``BENCHMARK.json`` into the files that define it.

Everything that belongs to one configuration, traffic mix, per-layer metric
or cell lives in a file of its own, found by the name ``BENCHMARK.json``
gives it:

- ``<config file>``: the configuration as it is run (``configs[].file``),
  with its plain reference beside it (same stem, ``.py``);
- ``bench/traffic/<traffic>.json``: the traffic mix;
- ``bench/metrics/<metric>.py``: the reader of one per-layer metric;
- ``bench/limits/<workload>.json``: the limits of the cell's correctness
  comparison, with the readings each was set from.

Adding a configuration, a mix, a metric or a cell therefore adds files and
entries; no code here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_module(path: Path) -> ModuleType:
    """Import a file by path (file names follow BENCHMARK.json names, which
    may hold '-' and '.')."""
    mod_name = "bench_" + "".join(c if c.isalnum() else "_"
                                  for c in str(path.relative_to(ROOT)))
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict            # the configuration file's JSON
    traffic: dict           # the traffic mix's JSON
    end_to_end: List[dict]  # BENCHMARK.json entries this cell reports
    per_layer: List[dict]
    limits: Dict[str, dict]
    reference: ModuleType   # the configuration's plain reference
    readers: Dict[str, ModuleType]  # per-layer metric name -> reader


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def resolve(name: str, root: Path = ROOT) -> Cell:
    """The cell called ``name``, with every file it names loaded."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_path = root / configs[w["config"]]["file"]
    with open(cfg_path) as f:
        config = json.load(f)
    with open(root / "bench" / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    with open(root / "bench" / "limits" / f"{name}.json") as f:
        limits = json.load(f)
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, name) and m["moves"] in e2e_names]
    readers = {m["name"]: load_module(root / "bench" / "metrics"
                                      / f"{m['name']}.py")
               for m in per_layer}
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer,
                limits=limits, reference=load_module(cfg_path.with_suffix(
                    ".py")), readers=readers)
