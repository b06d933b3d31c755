"""Find a cell's files by name.

``BENCHMARK.json`` names the cells; each cell names a configuration (its
``file``) and a traffic mix (``chipbench/traffic/<mix>.json``). Both paths are
relative to the directory of ``BENCHMARK.json``. A per-layer metric is
``chipbench/metrics/<name>.py`` and a configuration's ``system`` key picks
``chipbench/systems/<system>.py``, both in this package. Nothing here lists a cell, a
mix, a metric or a model: adding one is adding its files.

A serving configuration's ``model_type`` (its config.json key) picks the
model's two files, relative to the directory of ``BENCHMARK.json`` as the
configuration is (:func:`model_files`):

* ``chipbench/reference/<model_type>.py``, the plain reference, which
  imports nothing of the program: ``make_weights(seed_word, cfg)``,
  ``cfg_items(cfg)``, ``token_gaps(params, tokens, cfg_items, control)``,
  and the model FLOPs that ``serve_mfu`` counts, ``flops_per_token(cfg,
  context)`` and ``prefill_flops(cfg, prompt_len)``;
* ``chipbench/adapters/<model_type>.py``: ``model_config(cfg)``, the
  program's ``ModelConfig`` for the config.json keys.

A later model's kernel counts (a kernel's operations and bytes from its
shapes) go in its reference file too, beside its FLOPs; the kernel's
roofline reader, ``chipbench/metrics/<kernel>_roofline.py``, finds them
with ``model_files(r.config["model_type"], r.config[ROOT_KEY])``.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import pathlib
import re
from types import ModuleType
from typing import Dict, List

HERE = pathlib.Path(__file__).resolve().parent
# the key under which ``load_cell`` gives a configuration the directory of
# its BENCHMARK.json: a ``pathlib.Path``, which no JSON value is, so no
# config.json key can mean it and ``cfg_items`` and the weights leave it out
ROOT_KEY = "bench_root"
REFERENCE_API = ("make_weights", "cfg_items", "token_gaps", "flops_per_token",
                 "prefill_flops")
ADAPTER_API = ("model_config",)
_NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]*")


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
    config[ROOT_KEY] = root
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


@functools.cache
def _load_file(path: pathlib.Path) -> ModuleType:
    """The module in ``path``, executed once a process (a reference's
    jitted functions keep their compiled programs across set-ups)."""
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{path.parent.name}_{path.stem.replace('.', '_')}", path)
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


@dataclasses.dataclass(frozen=True)
class Model:
    """A serving model type's files (see the module's docstring)."""
    reference: ModuleType
    adapter: ModuleType


def model_files(model_type: str, root: pathlib.Path) -> Model:
    """The files of ``model_type`` under ``root``; a missing file or
    function raises ``ValueError`` naming what to add."""
    if not isinstance(model_type, str) or not _NAME.fullmatch(model_type):
        raise ValueError(f"model_type {model_type!r} is not a file name")
    want = {"reference": REFERENCE_API, "adapters": ADAPTER_API}
    paths = {d: root / "chipbench" / d / f"{model_type}.py" for d in want}
    missing = [f"{paths[d]} ({', '.join(want[d])})" for d in want
               if not paths[d].is_file()]
    if missing:
        raise ValueError(f"no serving model {model_type!r}: add "
                         + " and ".join(missing))
    mods = {d: _load_file(paths[d].resolve()) for d in want}
    lacking = [f"{paths[d]}: {name}" for d in want for name in want[d]
               if not callable(getattr(mods[d], name, None))]
    if lacking:
        raise ValueError(f"model {model_type!r} lacks " + "; ".join(lacking))
    return Model(mods["reference"], mods["adapters"])


def peaks() -> Dict[str, dict]:
    """Published peaks by ``device_kind`` (``chipbench/peaks.json``)."""
    return json.loads((HERE / "peaks.json").read_text())["devices"]
