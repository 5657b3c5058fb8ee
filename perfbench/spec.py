"""Find a cell's parts by name.

``BENCHMARK.json`` names each cell (``<config>.<traffic>``), its
configuration and its traffic mix.  Every part lives in a file of its own
that is found by that name alone, so a later cell, mix or metric is added
by adding files and a ``workloads`` entry:

* configuration: ``perfbench/configs/<config>.json``
* architecture module: the path the configuration's ``"reference"`` names,
  from the root of the checkout: the float32 reference, the published
  weight layout and its map to the program's tree, the size-key map and
  the work counts of that architecture.  No other file of the benchmark
  names an architecture, so a new one comes as a new module.
* traffic mix: ``perfbench/mixes/<traffic>.json``
* per-layer metric: ``perfbench/metrics/<metric>.py`` with ``read(ctx)``
* peaks: ``perfbench/peaks.json``, keyed by ``device_kind``
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    moves: Optional[str] = None
    workloads: Optional[List[str]] = None

    def applies_to(self, cell: str) -> bool:
        return self.workloads is None or cell in self.workloads


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: dict
    arch: ModuleType  # the configuration's architecture module
    mix: dict
    chips: int
    end_to_end: List[Metric]
    per_layer: List[Metric]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def _metrics(entries: List[dict]) -> List[Metric]:
    return [
        Metric(
            name=e["name"],
            unit=e["unit"],
            better=e["better"],
            source=e["source"],
            moves=e.get("moves"),
            workloads=e.get("workloads"),
        )
        for e in entries
    ]


def find_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its configuration
    and mix loaded from their files; a name it does not list raises."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[w["config"]]
    config = load_json(root / entry["file"])
    config["name"] = entry["name"]
    mix = load_json(root / "perfbench" / "mixes" / f"{w['traffic']}.json")
    mix["name"] = w["traffic"]
    return Cell(
        name=name,
        config=config,
        arch=architecture(config, root),
        mix=mix,
        chips=int(w["chips"]),
        end_to_end=[m for m in _metrics(bench["end_to_end"]) if m.applies_to(name)],
        per_layer=[m for m in _metrics(bench["per_layer"]) if m.applies_to(name)],
    )


def _load(module_name: str, path: Path) -> ModuleType:
    spec = importlib.util.spec_from_file_location(module_name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.lru_cache(maxsize=None)
def _architecture(path: Path) -> ModuleType:
    return _load(f"perfbench_arch_{path.stem}", path)


def architecture(config: dict, root: Path = ROOT) -> ModuleType:
    """The architecture module that ``config["reference"]`` names, a path
    from ``root``; loaded once per process, so its compiled programs are
    shared by every caller."""
    path = (root / config["reference"]).resolve()
    if not path.is_file():
        raise FileNotFoundError(f"architecture module {path} (the configuration's reference)")
    return _architecture(path)


def metric_reader(name: str, bench_dir: Path = BENCH_DIR) -> Callable:
    """``read(ctx)`` of ``metrics/<name>.py``; the file name is the metric's
    name, dots included, so it is loaded by path and not imported."""
    return _load(f"perfbench_metric_{name}", bench_dir / "metrics" / f"{name}.py").read


def peaks_for(device_kind: str, bench_dir: Path = BENCH_DIR) -> Dict[str, float]:
    """The published peaks of ``device_kind``; a device the table does not
    list is an error, never a default."""
    table = load_json(bench_dir / "peaks.json")
    for entry in table["devices"]:
        if device_kind in entry["device_kinds"]:
            return entry
    raise KeyError(
        f"no peaks for device_kind {device_kind!r} in peaks.json "
        f"(have {[k for e in table['devices'] for k in e['device_kinds']]})"
    )
