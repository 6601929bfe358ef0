"""Continuous-batching serve scheduler: fixed KV slots, admit/evict per step.

The seed's ``serve_request`` answered one HGum wire at a time: fresh ROM
walk, per-request ``jax.jit`` of prefill/decode, one generate loop per
request.  This module is the compute half of the batched message plane
(ISSUE 1): a :class:`ContinuousBatcher` owns

* a **slot cache** — one KV cache of ``slots`` rows (``init_cache(cfg,
  slots, prompt_cap + max_new)``) that lives across requests;
* **cached jitted steps** — ``launch.steps.cached_serve_steps`` memoizes the
  jitted prefill/decode on (cfg, cache_len), so admission never re-traces;
* an **admit/evict loop** — every :meth:`step` first admits pending
  sequences into free slots (one fixed-shape prefill of ``admit_cap`` rows,
  scattered into the slot cache with an OOB-dropping ``.at[].set``), then
  runs ONE batched decode step for all live slots and evicts the finished
  ones.

:meth:`step` is split into :meth:`step_begin` / :meth:`step_finish` so a
driver can overlap fabric ticks with compute (ISSUE 3's streaming plane):
``step_begin`` dispatches the admit prefill and the batched decode — JAX
async dispatch returns before the device finishes, and nothing in it reads
a result back — and ``step_finish`` performs the one host sync (the
admitted sequences' first tokens and the decode step's tokens together),
records the tick's tokens, and returns them as ``(seq_id, position,
token)`` emissions for the per-sequence StreamWriters.  Between the two
calls the host is free to reap/dispatch ``Fabric.exchange_async`` ticks
while the decode step runs, and to dispatch the next batcher's tick: given
a ``device``, a batcher keeps its weights, slot cache and steps on that
device, so batchers on different devices compute at once.

Sequences are plain token lists; the wire plane (``launch.serve``) sits on
either side of this class — batched HGum DES in front, bulk SER behind.
"""
from __future__ import annotations

import functools
import weakref
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Deque, Dict, Hashable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from ..obs.trace import span

# NB: launch.steps / models are imported lazily inside ContinuousBatcher —
# models itself pulls in repro.runtime (actshard), so a module-level import
# here would be circular.

PyTree = Any


@dataclass(frozen=True)
class SchedulerConfig:
    """Knobs of the serve loop (documented in launch/serve.py's docstring)."""

    slots: int = 8  # fixed KV-cache rows = max concurrent sequences
    prompt_cap: int = 32  # prompts are padded/truncated to this length
    max_new: int = 16  # greedy tokens generated per sequence
    admit_cap: Optional[int] = None  # prefill width per tick (default: slots)

    def __post_init__(self) -> None:
        if self.slots < 1 or self.prompt_cap < 1 or self.max_new < 1:
            raise ValueError(
                f"slots/prompt_cap/max_new must be >= 1, got "
                f"{self.slots}/{self.prompt_cap}/{self.max_new}"
            )
        if self.admit_cap is not None and self.admit_cap < 1:
            raise ValueError(f"admit_cap must be >= 1 or None, got {self.admit_cap}")

    @property
    def admit_width(self) -> int:
        return self.admit_cap or self.slots

    @property
    def cache_len(self) -> int:
        return self.prompt_cap + self.max_new


@dataclass
class _Sequence:
    seq_id: Hashable
    tokens: List[int]
    out: List[int] = field(default_factory=list)
    remaining: int = 0


@dataclass
class _Admission:
    """One admit prefill whose first tokens ``step_finish`` reads back."""

    seqs: List[_Sequence]
    tok: jnp.ndarray  # (admit_width, 1) first token of each prefilled row
    lp: Optional[jnp.ndarray]  # its logprob, with ``logprobs=True``


#: (id of the first leaf, device) -> (treedef, weakrefs to the source
#: leaves, the copy); an entry leaves when its first source leaf is freed
_PLACED: Dict[Tuple[int, Any], Tuple[Any, List[weakref.ref], PyTree]] = {}


def params_on(params: PyTree, device) -> PyTree:
    """``params`` on ``device``, copied once per (params, device).

    A later call with the same leaves returns the same copy, with no new
    transfer.  The copy is dropped when ``params``' first leaf is freed.
    The transfer is asynchronous, so copies to several devices started one
    after another run at once.
    """
    leaves, treedef = jax.tree.flatten(params)
    if all(getattr(x, "devices", lambda: None)() == {device} for x in leaves):
        return params
    key = (id(leaves[0]), device)
    hit = _PLACED.get(key)
    if (hit is not None and hit[0] == treedef
            and all(r() is x for r, x in zip(hit[1], leaves))):
        return hit[2]
    placed = jax.device_put(params, device, may_alias=False)
    _PLACED[key] = (treedef, [weakref.ref(x) for x in leaves], placed)
    weakref.finalize(leaves[0], _PLACED.pop, key, None)
    return placed


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _scatter_rows(cache: PyTree, cur_tok: jnp.ndarray, new_cache: PyTree,
                  new_tok: jnp.ndarray, slot_ids: jnp.ndarray):
    """Insert prefilled rows into their slots.

    ``slot_ids`` is padded with an out-of-range id for unused admit rows, so
    ``mode="drop"`` discards them and the call keeps one static shape.
    """
    cache = jax.tree.map(
        lambda c, n: c.at[slot_ids].set(n.astype(c.dtype), mode="drop"),
        cache, new_cache,
    )
    cur_tok = cur_tok.at[slot_ids].set(new_tok, mode="drop")
    return cache, cur_tok


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_vec(vec: jnp.ndarray, new: jnp.ndarray, slot_ids: jnp.ndarray):
    """Slot-scatter for the per-slot logprob column (same drop rule)."""
    return vec.at[slot_ids].set(new.astype(vec.dtype), mode="drop")


class ContinuousBatcher:
    """Admit/decode/evict loop over a fixed-slot KV cache."""

    def __init__(self, params: PyTree, cfg: ModelConfig,
                 sched: SchedulerConfig, metrics=None, spans=None,
                 logprobs: bool = False, device=None):
        from ..launch.steps import cached_serve_steps

        #: where the weights, slot cache and step inputs live, and so where
        #: prefill, decode and the slot scatters run; None = JAX's default
        self.device = device
        if device is not None:
            params = params_on(params, device)
        self.params = params
        self.cfg = cfg
        self.sched = sched
        #: optional obs.metrics.MetricsRegistry (admit/evict counters,
        #: occupancy + queue-depth gauges); None = no-op telemetry
        self.metrics = metrics
        #: optional obs.spans.SpanTracker; seq_ids with an entry in
        #: :attr:`span_of` get "batcher.admit"/"batcher.evict" arc points
        self.spans = spans
        self.span_of: Dict[Hashable, int] = {}
        #: when True the steps also return the chosen token's logprob,
        #: surfaced per tick in :attr:`tick_logprobs` (the greedy pick is
        #: unchanged — token output is byte-identical either way)
        self.logprobs = logprobs
        with span("batcher.init", slots=sched.slots):
            self.prefill_step, self.decode_step = cached_serve_steps(
                cfg, cache_len=sched.cache_len, logprobs=logprobs
            )
            # The slot cache must be row-compatible with what prefill emits
            # — families can grow it beyond prompt_cap + max_new (e.g. vlm
            # KV includes the vision prefix) — so allocate it from
            # prefill's eval_shape with the batch dim widened to `slots`.
            # The cache is the last output either way (tok[, lp], cache).
            out_spec = jax.eval_shape(
                self.prefill_step, params, self._batch_specs(sched.admit_width)
            )
            cache_spec = out_spec[-1]
            self.cache = jax.tree.map(
                lambda s: jnp.zeros((sched.slots,) + s.shape[1:], s.dtype,
                                    device=device),
                cache_spec,
            )
            self.cur_tok = jnp.zeros((sched.slots, 1), jnp.int32, device=device)
            self.cur_lp = jnp.zeros((sched.slots, 1), jnp.float32, device=device)
        (dev,) = self.cur_tok.devices()
        self._device_id = dev.id
        #: (seq_id, position) -> logprob of every emission of the last tick
        #: (only filled when ``logprobs=True``); the step_finish triple API
        #: is unchanged so logprob-free callers never pay for it
        self.tick_logprobs: Dict[Tuple[Hashable, int], float] = {}
        # static non-token model inputs (vision/audio placeholders) are
        # allocated once, not per admit tick
        self._extra_inputs = {
            k: jnp.zeros(s.shape, s.dtype, device=device)
            for k, s in self._batch_specs(sched.admit_width).items()
            if k != "tokens"
        }
        self.active: List[Optional[_Sequence]] = [None] * sched.slots
        self.pending: Deque[_Sequence] = deque()
        self.done: Dict[Hashable, List[int]] = {}
        self.steps_run = 0
        #: admits dispatched since the last step_finish, first tokens unread
        self._admissions: List[_Admission] = []
        self._stepped = False

    def _batch_specs(self, A: int) -> Dict[str, jax.ShapeDtypeStruct]:
        S = self.sched.prompt_cap
        specs = {"tokens": jax.ShapeDtypeStruct((A, S), jnp.int32)}
        if self.cfg.family == "vlm":
            specs["vision"] = jax.ShapeDtypeStruct(
                (A, self.cfg.vision_tokens, self.cfg.vision_dim), jnp.float32
            )
        if self.cfg.family == "encdec":
            specs["audio"] = jax.ShapeDtypeStruct(
                (A, self.cfg.enc_seq, self.cfg.d_model), jnp.float32
            )
        return specs

    # -- queue -------------------------------------------------------------

    def submit(self, seq_id: Hashable, tokens: List[int]) -> None:
        self.pending.append(_Sequence(seq_id, list(tokens)))

    @property
    def n_active(self) -> int:
        return sum(s is not None for s in self.active)

    # -- scheduler tick ----------------------------------------------------

    def _admit(self) -> None:
        free = [i for i, s in enumerate(self.active) if s is None]
        if not free or not self.pending:
            return
        A = self.sched.admit_width
        take = min(len(free), A, len(self.pending))
        with span("batcher.admit", admitted=take):
            seqs = [self.pending.popleft() for _ in range(take)]
            S = self.sched.prompt_cap
            toks = np.zeros((A, S), np.int32)
            for j, seq in enumerate(seqs):
                toks[j, : min(len(seq.tokens), S)] = seq.tokens[:S]
            batch = dict(self._extra_inputs)
            batch["tokens"] = jnp.asarray(toks, device=self.device)
            if self.logprobs:
                next_tok, next_lp, new_cache = self.prefill_step(self.params, batch)
            else:
                next_tok, new_cache = self.prefill_step(self.params, batch)
                next_lp = None
            # unused admit rows -> OOB slot id, dropped by the scatter
            slot_ids = np.full(A, self.sched.slots, np.int32)
            slot_ids[:take] = free[:take]
            slot_ids = jnp.asarray(slot_ids, device=self.device)
            self.cache, self.cur_tok = _scatter_rows(
                self.cache, self.cur_tok, new_cache, next_tok, slot_ids
            )
            if next_lp is not None:
                self.cur_lp = _scatter_vec(self.cur_lp, next_lp, slot_ids)
            # the first tokens are read in step_finish, with the tick's one
            # sync; a sequence that wants no more than its first token
            # takes no slot
            self._admissions.append(_Admission(seqs, next_tok, next_lp))
            for j, seq in enumerate(seqs):
                seq.remaining = self.sched.max_new - 1
                if seq.remaining > 0:
                    self.active[free[j]] = seq
                if self.spans is not None and seq.seq_id in self.span_of:
                    self.spans.event(self.span_of[seq.seq_id], "batcher.admit",
                                     slot=free[j])
            if self.metrics is not None:
                self.metrics.counter("batcher.admitted").add(take)

    def _retire(self, seqs: List[_Sequence]) -> None:
        for seq in seqs:
            self.done[seq.seq_id] = seq.out
            if self.spans is not None and seq.seq_id in self.span_of:
                self.spans.event(self.span_of[seq.seq_id],
                                 "batcher.evict", n_out=len(seq.out))
        if self.metrics is not None and seqs:
            self.metrics.counter("batcher.evicted").add(len(seqs))

    def _evict(self) -> None:
        finished = []
        for i, seq in enumerate(self.active):
            if seq is not None and seq.remaining <= 0:
                self.active[i] = None
                finished.append(seq)
        self._retire(finished)

    def step_begin(self) -> bool:
        """Dispatch one scheduler tick: admit into free slots, then launch
        one batched decode step for every live slot.

        Returns immediately after dispatch (JAX async) — the host can run
        fabric work, or dispatch another batcher's tick, while the device
        computes.  Returns True when a decode step was dispatched.  Must be
        paired with :meth:`step_finish`.
        """
        self.tick_logprobs = {}
        self._admit()
        if self.metrics is not None:
            self.metrics.gauge("batcher.occupancy").set(self.n_active)
            self.metrics.gauge("batcher.queue_depth").set(len(self.pending))
        if self.n_active == 0:
            self._stepped = False
            return False
        with span("batcher.dispatch", live=self.n_active,
                  device=self._device_id):
            if self.logprobs:
                self.cur_tok, self.cur_lp, self.cache = self.decode_step(
                    self.params, self.cache, self.cur_tok
                )
            else:
                self.cur_tok, self.cache = self.decode_step(
                    self.params, self.cache, self.cur_tok
                )
        self.steps_run += 1
        self._stepped = True
        if self.metrics is not None:
            self.metrics.counter("batcher.steps").add(1)
        return True

    def step_finish(self) -> List[Tuple[Hashable, int, int]]:
        """Sync the dispatched tick and return its emissions.

        One host sync reads the admitted sequences' first tokens and the
        decode step's tokens; the return value is every token the tick
        produced — admit-time first tokens first, in admission order, then
        the decode step's in slot order — as ``(seq_id, position, token)``
        triples.
        """
        admissions, self._admissions = self._admissions, []
        stepped, self._stepped = self._stepped, False
        emitted: List[Tuple[Hashable, int, int]] = []
        if not admissions and not stepped:
            return emitted
        with span("batcher.finish"):
            with span("batcher.sync"):  # one host sync per tick
                firsts, last = jax.device_get((
                    [(a.tok, a.lp) for a in admissions],
                    (self.cur_tok, self.cur_lp if self.logprobs else None)
                    if stepped else None,
                ))
            for a, (tok, lp) in zip(admissions, firsts):
                for j, seq in enumerate(a.seqs):
                    seq.out.append(int(tok[j, 0]))
                    emitted.append((seq.seq_id, 0, int(tok[j, 0])))
                    if lp is not None:
                        self.tick_logprobs[(seq.seq_id, 0)] = float(lp[j, 0])
                self._retire([seq for seq in a.seqs if seq.remaining <= 0])
            if last is not None:
                toks, lps = last
                for i, seq in enumerate(self.active):
                    if seq is not None:
                        seq.out.append(int(toks[i, 0]))
                        seq.remaining -= 1
                        pos = len(seq.out) - 1
                        emitted.append((seq.seq_id, pos, int(toks[i, 0])))
                        if lps is not None:
                            self.tick_logprobs[(seq.seq_id, pos)] = float(lps[i, 0])
                self._evict()
        return emitted

    def step(self) -> None:
        """One synchronous scheduler tick (dispatch + sync back to back)."""
        self.step_begin()
        self.step_finish()

    def run(self) -> Dict[Hashable, List[int]]:
        """Drain the queue; returns seq_id -> generated tokens."""
        while self.pending or self.n_active:
            self.step()
        out, self.done = self.done, {}
        return out
