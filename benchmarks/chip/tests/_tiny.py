"""Tiny stand-ins for the benchmark's cells, for tests on the CPU.

The same configuration files and traffic mixes, with every width and depth
cut to what a CPU test can hold, the weights' scale raised to keep the
activations' statistics, and the program's ``ModelConfig`` built the same
way (``dataclasses.replace`` on the registry entry)."""
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

TINY = dict(hidden_size=128, num_attention_heads=4, intermediate_size=256,
            vocab_size=512, num_hidden_layers=2)


def tiny_cell(name: str, **mix):
    """(cell, program config) of ``name`` at a CPU test's size."""
    from repro.configs import get_config

    cell = spec.load_cell(name)
    conf = dict(cell.config, **TINY)
    # the published init scaled so that activations keep their full-size
    # statistics at the small width: std * sqrt(d) is held fixed
    conf["initializer_range"] = cell.config["initializer_range"] * (
        cell.sizes.d / TINY["hidden_size"]) ** 0.5
    conf["num_key_value_heads"] = 2 if cell.sizes.kv_heads < cell.sizes.heads else 4
    m = dict(cell.mix, pad_to=32, prompt_len=[16, 32], max_new=8)
    m.update(mix)
    p = conf["program"]
    cfg = dataclasses.replace(
        get_config(p["registry"]), **p["replace"], n_layers=2, d_model=128, n_heads=4,
        n_kv=conf["num_key_value_heads"], head_dim=32, d_ff=256, vocab=512)
    cell = dataclasses.replace(cell, config=conf, mix=m, sizes=spec.Sizes.from_file(conf))
    return cell, cfg
