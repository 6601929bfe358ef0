"""What a cell is: found by name in ``BENCHMARK.json`` and in files of its own.

* a cell is an entry of ``workloads`` in ``BENCHMARK.json``;
* its configuration is ``configs/<config>.json``: the published config as it
  is run, the registry entry it is built from, and its architecture's name
  (the ``reference`` key), which picks the plain reference that checks it
  (``references/<reference>.py``) and the family module that knows its
  shapes, fields, weights and counts (``families/<reference>.py``);
* its traffic mix is ``traffic/<traffic>.json`` (see ``traffic.py``);
* its correctness limits are ``limits/<cell>.json``;
* each metric is read by ``metrics/<metric>.py``.

A later cell adds files; nothing here names one.
"""
from __future__ import annotations

import importlib
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Dict, List

from . import traffic

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]  # the checkout: BENCHMARK.json and src/


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict
    mix: Dict
    limits: Dict
    family: ModuleType  # families/<reference>.py
    sizes: object  # the family's record of shapes
    e2e: List[str]  # end-to-end metrics this cell reports
    per_layer: List[str]  # per-layer metrics this cell reports


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_path: Path = ROOT / "BENCHMARK.json") -> Cell:
    bench = json.loads(bench_path.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have {sorted(cells)}")
    w = cells[name]
    config = json.loads((HERE / "configs" / f"{w['config']}.json").read_text())
    limits_file = HERE / "limits" / f"{name}.json"
    family = family_module(config)
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        mix=traffic.load_mix(w["traffic"]),
        limits=json.loads(limits_file.read_text()),
        family=family,
        sizes=family.sizes(config),
        e2e=[m["name"] for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m["name"] for m in bench["per_layer"] if _applies(m, name)],
    )


def program_config(config: Dict):
    """The program's ``ModelConfig`` for a config file, checked against it.

    Built by ``dataclasses.replace`` on the registry entry with the file's
    ``program.replace``; every field the family compares
    (``program_fields``) must then agree with the published numbers, or the
    cell refuses to run.
    """
    from dataclasses import replace

    from repro.configs import get_config

    prog = config["program"]
    cfg = replace(get_config(prog["registry"]), **prog["replace"])
    family = family_module(config)
    want = family.program_fields(config, family.sizes(config))
    bad = {k: (getattr(cfg, k), v) for k, v in want.items() if getattr(cfg, k) != v}
    if bad:
        raise ValueError(f"{config['name']}: the program's config departs from the "
                         f"file (program, file): {bad}")
    return cfg


def family_module(config: Dict) -> ModuleType:
    return importlib.import_module(f"{__package__}.families.{config['reference']}")


def reference_module(config: Dict):
    return importlib.import_module(f"{__package__}.references.{config['reference']}")


def entry_module(name: str):
    return importlib.import_module(f"{__package__}.entries.{name}")
