"""Find every piece of a benchmark cell by name.

``BENCHMARK.json`` at the root of the checkout names the cells
(``workloads``), the model configurations and the metrics.  Everything
that belongs to one of them sits in files of its own, found here by name
alone, so a later change adds a configuration, a traffic mix or a metric
by adding files and entries and edits nothing that is already there:

* configuration ``<c>``: the ``file`` that ``BENCHMARK.json`` gives
  (``bench/configs/<c>.json``, the sizes as run) and, beside it,
  ``bench/configs/<c>.py`` (weights from the seed, the plain reference,
  and the counts of the work the model requires);
* traffic mix ``<t>``: ``bench/traffic/<t>.json``, whose ``kind`` names
  the generator ``bench/generators/<kind>.py``;
* metric ``<m>`` (end-to-end or per-layer): ``bench/metrics/<m>.py``,
  a reader with ``read(run) -> float | None``;
* chip peaks: ``bench/peaks.json``, keyed by JAX's ``device_kind``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from types import ModuleType
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def load_module(path: str, name: Optional[str] = None) -> ModuleType:
    """Import a Python file by path (names may hold ``-`` and ``.``)."""
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    name = name or "bench_" + os.path.relpath(path, BENCH_DIR).replace(
        os.sep, "_").replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    workloads: Optional[List[str]]  # None: every cell (per-layer: every
    moves: Optional[str] = None     # cell that reports `moves`)
    reader: Optional[ModuleType] = None


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads``, with every piece it names resolved."""
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]          # the configuration file, as run
    model: ModuleType               # bench/configs/<config>.py
    traffic_name: str
    traffic: Dict[str, Any]         # bench/traffic/<traffic>.json
    generator: ModuleType           # bench/generators/<kind>.py
    end_to_end: List[Metric]
    per_layer: List[Metric]


def _read_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> Dict[str, Any]:
    return _read_json(os.path.join(root, "BENCHMARK.json"))


def _metrics(manifest: Dict[str, Any], kind: str) -> List[Metric]:
    return [Metric(name=m["name"], unit=m["unit"],
                   workloads=m.get("workloads"), moves=m.get("moves"))
            for m in manifest.get(kind, [])]


def cell_metrics(manifest: Dict[str, Any], cell: str, root: str = ROOT
                 ) -> Dict[str, List[Metric]]:
    """The end-to-end and per-layer metrics that ``cell`` reports: those
    that list it, and those with no list (a per-layer metric without
    one goes wherever the end-to-end metric it moves is reported)."""
    e2e = [m for m in _metrics(manifest, "end_to_end")
           if m.workloads is None or cell in m.workloads]
    names = {m.name for m in e2e}
    per = [m for m in _metrics(manifest, "per_layer")
           if (cell in m.workloads if m.workloads is not None
               else m.moves in names)]
    for m in e2e + per:
        m.reader = load_module(os.path.join(root, "bench", "metrics",
                                            m.name + ".py"))
    return {"end_to_end": e2e, "per_layer": per}


def load_cell(name: str, root: str = ROOT) -> Cell:
    manifest = load_manifest(root)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    centry = configs[w["config"]]
    cfile = os.path.join(root, centry["file"])
    config = _read_json(cfile)
    model = load_module(os.path.splitext(cfile)[0] + ".py")
    traffic = _read_json(os.path.join(root, "bench", "traffic",
                                      w["traffic"] + ".json"))
    generator = load_module(os.path.join(root, "bench", "generators",
                                         traffic["kind"] + ".py"))
    metrics = cell_metrics(manifest, name, root)
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config, model=model, traffic_name=w["traffic"],
                traffic=traffic, generator=generator,
                end_to_end=metrics["end_to_end"],
                per_layer=metrics["per_layer"])


def load_peaks(device_kind: str, root: str = ROOT) -> Dict[str, Any]:
    """Peaks of one chip, by JAX's ``device_kind``; an unknown kind is
    an error, never a default."""
    table = _read_json(os.path.join(root, "bench", "peaks.json"))
    kinds = table["kinds"]
    if device_kind not in kinds:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(kinds)})")
    return kinds[device_kind]
