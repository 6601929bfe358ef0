"""What a cell is: found by name in ``BENCHMARK.json`` and in files of its own.

* a cell is an entry of ``workloads`` in ``BENCHMARK.json``;
* its configuration is ``configs/<config>.json``: the published config as it
  is run, the registry entry it is built from, and the plain reference that
  checks it (``references/<reference>.py``);
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
from typing import Dict, List

from . import traffic

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]  # the checkout: BENCHMARK.json and src/


@dataclass(frozen=True)
class Sizes:
    """The shapes the yardstick counts with, read from the config file alone."""

    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    glu: bool
    tied: bool
    layernorm: bool  # norms carry a bias beside the scale
    dtype_bytes: int

    @classmethod
    def from_file(cls, c: Dict) -> "Sizes":
        d, h = c["hidden_size"], c["num_attention_heads"]
        return cls(
            layers=c["num_hidden_layers"], d=d, heads=h,
            kv_heads=c["num_key_value_heads"],
            head_dim=c.get("head_dim") or d // h,
            d_ff=c["intermediate_size"], vocab=c["vocab_size"],
            glu=c["hidden_act"] == "silu",
            tied=bool(c["tie_word_embeddings"]),
            layernorm=c["norm"] == "layernorm",
            dtype_bytes={"bfloat16": 2, "float32": 4}[c["torch_dtype"]],
        )


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict
    mix: Dict
    limits: Dict
    sizes: Sizes
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
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        mix=traffic.load_mix(w["traffic"]),
        limits=json.loads(limits_file.read_text()),
        sizes=Sizes.from_file(config),
        e2e=[m["name"] for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m["name"] for m in bench["per_layer"] if _applies(m, name)],
    )


def program_config(config: Dict):
    """The program's ``ModelConfig`` for a config file, checked against it.

    Built by ``dataclasses.replace`` on the registry entry with the file's
    ``program.replace``; every size the yardstick counts with must then agree
    with the published numbers, or the cell refuses to run.
    """
    from dataclasses import replace

    from repro.configs import get_config

    prog = config["program"]
    cfg = replace(get_config(prog["registry"]), **prog["replace"])
    s = Sizes.from_file(config)
    eps = config.get("rms_norm_eps", config.get("layer_norm_eps"))
    want = {
        "n_layers": s.layers, "d_model": s.d, "n_heads": s.heads,
        "n_kv": s.kv_heads, "hd": s.head_dim, "d_ff": s.d_ff, "vocab": s.vocab,
        "tie_embeddings": s.tied, "rope_theta": float(config["rope_theta"]),
        "norm_eps": float(eps), "norm": config["norm"], "dtype": config["torch_dtype"],
        "act": "swiglu" if s.glu else "gelu", "family": "lm", "window": None,
        "moe_experts": 0, "layer_pattern": "attn", "use_rope": True,
        "embed_scale": False, "sandwich_norm": False, "attn_softcap": None,
        "final_softcap": None, "padded_vocab": s.vocab,
    }
    bad = {k: (getattr(cfg, k), v) for k, v in want.items() if getattr(cfg, k) != v}
    if bad:
        raise ValueError(f"{config['name']}: the program's config departs from the "
                         f"file (program, file): {bad}")
    return cfg


def reference_module(config: Dict):
    return importlib.import_module(f"{__package__}.references.{config['reference']}")


def entry_module(name: str):
    return importlib.import_module(f"{__package__}.entries.{name}")
