"""How ``correct`` is decided: what the timed path served, against the
plain reference.

Two kinds of number, each beside its limit (``limits/<cell>.json``):

* exact counts, limit 0: response wires that do not parse (with the
  benchmark's own parser, ``wire.py``), name the wrong request, carry the
  wrong number of outputs or of tokens, or a token outside the vocabulary;
  on the streaming plane also tokens that reached the ingress through
  ``on_token`` and differ from the final wire;
* ``logit_gap``: over a sample of the sequences served in the window, drawn
  from the seed with the longest prompt in it, the widest gap by which a
  served token's reference logit lies below the reference's best at that
  position, in units of the standard deviation of the reference's logits
  there.  A greedy server that computes what the reference computes serves
  the best token or one that ties it to rounding; one that computes
  something else serves tokens the reference ranks lower.

The reference runs the served sequence as the entry ran it: the prompt right
padded with token 0 to ``pad_to`` (the entry attends to the padding; ROADMAP
Reach 3), then the served tokens; the logits at positions ``pad_to - 1 ..
pad_to + max_new - 2`` are those of the served tokens.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import spec, wire


@dataclass
class Served:
    """One prompt as the client sent it, and what came back."""

    prompt: np.ndarray
    tokens: List[int]


@dataclass
class Verdict:
    numbers: List[Tuple[str, float, float]] = field(default_factory=list)

    def add(self, name: str, value: float, limit: float) -> None:
        self.numbers.append((name, float(value), float(limit)))

    @property
    def correct(self) -> bool:
        return bool(self.numbers) and all(v <= lim for _, v, lim in self.numbers)


def check_wires(burst, responses: List[bytes], max_new: int, vocab: int,
                served: List[Served]) -> int:
    """Count the response wires at fault; append what each served."""
    bad = 0
    if len(responses) != len(burst.wires):
        return len(burst.wires)
    for rid, prompts, w in zip(burst.req_ids, burst.prompts, responses):
        try:
            got_rid, outs = wire.decode_response(w)
        except ValueError:
            bad += 1
            continue
        ok = got_rid == rid and len(outs) == len(prompts)
        ok = ok and all(len(o) == max_new and all(0 <= t < vocab for t in o)
                        for o in outs)
        if not ok:
            bad += 1
            continue
        served.extend(Served(p, o) for p, o in zip(prompts, outs))
    return bad


def sample(served: List[Served], k: int, seed: int) -> List[Served]:
    """``k`` served sequences drawn from the seed, the longest prompt first."""
    if not served:
        return []
    longest = max(range(len(served)), key=lambda i: len(served[i].prompt))
    rest = [i for i in range(len(served)) if i != longest]
    rng = np.random.default_rng([seed, 7])
    pick = rng.choice(rest, size=min(k - 1, len(rest)), replace=False) if k > 1 else []
    return [served[longest]] + [served[i] for i in sorted(pick)]


@jax.jit
def _gap(ref: jnp.ndarray, tok: jnp.ndarray) -> jnp.ndarray:
    """(n, V) reference logits, (n,) tokens -> (n,) gaps in logit stds."""
    best = jnp.max(ref, -1)
    got = jnp.take_along_axis(ref, tok[:, None], -1)[:, 0]
    return (best - got) / jnp.std(ref, -1)


def served_sequence(s: Served, pad_to: int) -> Tuple[np.ndarray, np.ndarray]:
    """(input tokens, rows whose logits predict the served tokens)."""
    prompt = np.zeros(pad_to, np.int64)
    n = min(len(s.prompt), pad_to)
    prompt[:n] = s.prompt[:n]
    seq = np.concatenate([prompt, np.asarray(s.tokens[:-1], np.int64)])
    rows = np.arange(pad_to - 1, pad_to - 1 + len(s.tokens))
    return seq, rows


def logit_gaps(cell, params, seqs: List[Served], control: bool = False) -> float:
    """Widest gap over ``seqs``: of the served tokens, or with ``control``
    of the tokens that the float8 control ranks first.  The cell's reference
    reads the weights through its family's ``view``."""
    ref_mod = spec.reference_module(cell.config)
    view = cell.family.view(params)
    worst = 0.0
    for s in seqs:
        seq, rows = served_sequence(s, cell.mix["pad_to"])
        ref = ref_mod.logits(cell.config, view, seq, rows)
        if control:
            low = ref_mod.logits(cell.config, view, seq, rows, quant=True)
            tok = jnp.argmax(low, -1).astype(jnp.int32)
        else:
            tok = jnp.asarray(s.tokens, jnp.int32)
        worst = max(worst, float(jnp.max(_gap(ref, tok))))
    return worst


def judge(limits: Dict, wires_bad: int, stream_bad: Optional[int],
          gap: Optional[float], failed: int) -> Verdict:
    v = Verdict()
    v.add("failed_requests", failed, 0)
    v.add("bad_wires", wires_bad, 0)
    if stream_bad is not None:
        v.add("stream_mismatch", stream_bad, 0)
    # no sequence to check is a failure, not a pass
    v.add("logit_gap", gap if gap is not None else float("inf"), limits["logit_gap"])
    return v
