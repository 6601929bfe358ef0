"""The one traffic generator: bursts of request wires from a mix's parameters.

A mix is a JSON file under ``traffic/``.  Its keys:

``entry``             the serving entry point the cell drives (a module under
                      ``entries/``);
``wires``             request wires per burst;
``prompts_per_wire``  prompts in each wire;
``prompt_len``        ``[lo, hi]``, inclusive, of prompt lengths in tokens;
``pad_to``, ``max_new``, ``slots``, ``admit_cap`` (optional)
                      the entry's static prompt length, tokens generated per
                      prompt, KV-cache slots, and prompts prefilled at once;
``check_sequences``   how many served sequences the reference checks;
further keys          passed to the entry as they are (``n_shards``).

Every burst holds the same set of prompt lengths: ``wires *
prompts_per_wire`` lengths spread evenly over ``[lo, hi]``.  The seed draws
the tokens and the order.  With an even number of prompts per wire the
lengths go to the wires in pairs that sum to ``lo + hi``, so every wire has
the same byte length; with one prompt per wire the longest wire is the same
in every burst.  Either way the decode programs see the same shapes in every
burst, nothing compiles after the warm-up, and every seed asks for the same
work.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List

import numpy as np

from . import wire

HERE = Path(__file__).resolve().parent


@dataclass(frozen=True)
class Burst:
    index: int
    req_ids: List[int]
    prompts: List[List[np.ndarray]]  # per wire, per prompt: int64 token ids
    wires: List[bytes]

    @property
    def n_sequences(self) -> int:
        return sum(len(p) for p in self.prompts)


def load_mix(name: str) -> Dict:
    mix = json.loads((HERE / "traffic" / f"{name}.json").read_text())
    lo, hi = mix["prompt_len"]
    if not 1 <= lo <= hi <= mix["pad_to"]:
        raise ValueError(f"{name}: prompt_len {mix['prompt_len']} must lie in "
                         f"[1, pad_to={mix['pad_to']}]")
    n = mix["wires"] * mix["prompts_per_wire"]
    if mix["prompts_per_wire"] % 2 and mix["prompts_per_wire"] != 1:
        raise ValueError(f"{name}: prompts_per_wire must be 1 or even")
    if n < 1 or mix["max_new"] < 1 or mix["slots"] < 1:
        raise ValueError(f"{name}: empty burst")
    return mix


def length_grid(mix: Dict) -> np.ndarray:
    """The burst's prompt lengths, ascending: evenly spread over [lo, hi]."""
    lo, hi = mix["prompt_len"]
    n = mix["wires"] * mix["prompts_per_wire"]
    if n == 1:
        return np.array([hi])
    k = np.arange(n)
    grid = lo + np.round(k * (hi - lo) / (n - 1)).astype(np.int64)
    # mirrored about (lo + hi) / 2, so that grid[k] + grid[n-1-k] == lo + hi
    return np.where(k < n // 2, grid, lo + hi - grid[::-1])


class Generator:
    """Bursts for one cell and seed; burst ``k`` is the same in every run."""

    def __init__(self, mix: Dict, vocab: int, seed: int):
        self.mix = mix
        self.vocab = vocab
        self.seed = seed
        self.grid = length_grid(mix)

    def burst(self, k: int) -> Burst:
        mix = self.mix
        rng = np.random.default_rng([self.seed, k + 1])
        W, P = mix["wires"], mix["prompts_per_wire"]
        n = W * P
        if P == 1:
            lens = rng.permutation(self.grid).reshape(W, 1)
        else:
            pairs = np.stack([self.grid[: n // 2], self.grid[::-1][: n // 2]], 1)
            pairs = pairs[rng.permutation(n // 2)]
            flip = rng.integers(0, 2, n // 2).astype(bool)
            pairs[flip] = pairs[flip][:, ::-1]
            lens = pairs.reshape(W, P)
        # token ids from 1: 0 is the id the entry pads prompts with
        toks = rng.integers(1, self.vocab, int(lens.sum()))
        cut = np.cumsum(lens.reshape(-1))[:-1]
        flat = np.split(toks, cut)
        prompts = [flat[w * P:(w + 1) * P] for w in range(W)]
        base = (k + 1) * 1_000_000
        req_ids = [base + w for w in range(W)]
        wires = [wire.encode_request(r, p) for r, p in zip(req_ids, prompts)]
        return Burst(k, req_ids, prompts, wires)
