"""The benchmark's data: ``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric sits in a file of its own, found by its name:

- ``bench/configs/<config>.json``: the model as it is run, naming its
  plain reference under ``"reference"``;
- ``bench/reference/<module>.py``: that reference and its work counts
  (the contract is in ``bench/reference/__init__.py``);
- ``bench/traffic/<mix>.json``: the parameters the one generator reads;
- ``bench/cells/<cell>.json``: the server's flags, the offered rate and
  the limits of the correctness comparison;
- ``bench/metrics/<metric>.py``: a reader with ``read(run)``.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
#: the package a configuration's ``"reference"`` names a module of
REFERENCE_PACKAGE = "bench.reference"
#: what every reference module defines (``bench/reference/__init__.py``)
REFERENCE_CONTRACT = ("frozen", "gaps", "counts", "lower")


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Metric:
    name: str
    unit: str
    end_to_end: bool
    read: Callable


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    #: the plain reference module the configuration names
    reference: ModuleType
    traffic: dict
    server: dict
    metrics: List[Metric]

    def metrics_for(self, trace: bool) -> List[Metric]:
        """The end-to-end metrics of an untraced run, the per-layer ones
        of a traced run."""
        return [m for m in self.metrics if m.end_to_end != trace]


def load_reader(name: str) -> Callable:
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_reference(config: dict) -> ModuleType:
    """The reference module ``config`` names under ``"reference"``; stops
    where it names none, or one that is missing or incomplete."""
    who = f"configuration {config.get('name')!r}"
    name = config.get("reference")
    if not isinstance(name, str) or not name.isidentifier():
        raise ValueError(f"{who} names no reference module: give "
                         f'"reference": "<module>" of {REFERENCE_PACKAGE}'
                         f" (got {name!r})")
    full = f"{REFERENCE_PACKAGE}.{name}"
    try:
        mod = importlib.import_module(full)
    except ModuleNotFoundError as e:
        if e.name != full:
            raise
        raise ValueError(f"{who} names reference {name!r}, but there is no "
                         f"module {full}") from None
    missing = [a for a in REFERENCE_CONTRACT if not hasattr(mod, a)]
    if missing:
        raise ValueError(f"reference {full} of {who} lacks "
                         f"{', '.join(missing)}")
    return mod


def load_cell(name: str, benchmark: Optional[dict] = None) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` with every file it names."""
    bm = benchmark if benchmark is not None else _load_json(
        ROOT / "BENCHMARK.json")
    by_name = {w["name"]: w for w in bm["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(by_name)})")
    w = by_name[name]
    configs = {c["name"]: c for c in bm["configs"]}
    config = _load_json(ROOT / configs[w["config"]]["file"])
    reference = load_reference(config)
    traffic = _load_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json")
    server = _load_json(BENCH_DIR / "cells" / f"{name}.json")
    metrics = []
    for e2e, key in ((True, "end_to_end"), (False, "per_layer")):
        for m in bm[key]:
            cells = m.get("workloads")
            if cells is not None and name not in cells:
                continue
            metrics.append(Metric(m["name"], m["unit"], e2e,
                                  load_reader(m["name"])))
    return Cell(name, int(w["chips"]), config, reference, traffic, server,
                metrics)


def load_peaks() -> Dict[str, dict]:
    return _load_json(BENCH_DIR / "peaks.json")["devices"]
