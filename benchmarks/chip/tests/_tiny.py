"""Tiny stand-ins for the benchmark's cells, for tests on the CPU.

The same configuration files and traffic mixes, with every width and depth
cut as the cell's family says (``families/<reference>.py`` ``tiny``), and
the program's ``ModelConfig`` built the same way (``dataclasses.replace`` on
the registry entry)."""
from __future__ import annotations

import dataclasses
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchmarks.chip import spec  # noqa: E402


def cut(cell):
    """(cell, program config) of a loaded ``cell`` at a CPU test's size: the
    family's cut (``tiny``) and a short traffic mix."""
    from repro.configs import get_config

    conf, repl = cell.family.tiny(cell.config)
    m = dict(cell.mix, pad_to=32, prompt_len=[16, 32], max_new=8)
    p = conf["program"]
    cfg = dataclasses.replace(get_config(p["registry"]), **p["replace"], **repl)
    cell = dataclasses.replace(cell, config=conf, mix=m, sizes=cell.family.sizes(conf))
    return cell, cfg


def tiny_cell(name: str):
    """(cell, program config) of ``name`` at a CPU test's size."""
    return cut(spec.load_cell(name))
