"""Find a cell's files by name.

``BENCHMARK.json`` names the cells; each cell names a configuration (its
``file``) and a traffic mix (``chipbench/traffic/<mix>.json``). Both paths are
relative to the directory of ``BENCHMARK.json``. A per-layer metric is
``chipbench/metrics/<name>.py`` and a configuration's ``system`` key picks
``chipbench/systems/<system>.py``, both in this package. Nothing here lists a cell, a
mix or a metric: adding one is adding its files.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from types import ModuleType
from typing import Dict, List

HERE = pathlib.Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: List[Metric]
    per_layer: List[Metric]

    @property
    def system(self) -> str:
        return self.config["system"]


def load_cell(workload: str, root: pathlib.Path) -> Cell:
    """The cell ``workload`` of ``root/BENCHMARK.json`` with its files."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in {root / 'BENCHMARK.json'}"
                       f"; have {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (root / "chipbench" / "traffic" / f"{w['traffic']}.json").read_text())
    def here(m):
        return m.get("workloads") is None or workload in m["workloads"]

    e2e = [m for m in bench["end_to_end"] if here(m)]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if m["moves"] in reported and here(m)]
    return Cell(name=workload, config=config, traffic=traffic,
                chips=int(w["chips"]),
                end_to_end=[Metric(m["name"], m["unit"]) for m in e2e],
                per_layer=[Metric(m["name"], m["unit"]) for m in layer])


def _load_file(path: pathlib.Path) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str) -> ModuleType:
    """``chipbench/metrics/<name>.py``: ``UNIT``, ``LAYER``, ``MOVES`` and
    ``read(readings) -> float | None``."""
    return _load_file(HERE / "metrics" / f"{name}.py")


def system_module(system: str) -> ModuleType:
    """``chipbench/systems/<system>.py``: ``run(ctx) -> Outcome``."""
    return importlib.import_module(f"chipbench.systems.{system}")


def peaks() -> Dict[str, dict]:
    """Published peaks by ``device_kind`` (``chipbench/peaks.json``)."""
    return json.loads((HERE / "peaks.json").read_text())["devices"]
