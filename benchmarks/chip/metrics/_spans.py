"""The program's host spans (``repro.obs.trace.span``) against the serving
chip's idle time, both read from the one profiler trace of the window.

* the serving chip: the chip with the most ``serve_step`` device time;
* its idle intervals: the complement of its busy union (every ``XLA Ops``
  interval) between its first operation and its last;
* a span set: the host events named exactly as the program's spans are
  (``repro.obs.trace.SPAN_NAMES``), so the harness's ``bench.call`` and the
  Python tracer's frames never count.

A trace without the spans (a program that does not open them) gives None.
"""
from typing import Iterable, Optional

import numpy as np

from benchmarks.chip import trace_reduce

try:  # the program's own list of its span names
    from repro.obs.trace import SPAN_NAMES as PROGRAM_SPANS
except ImportError:  # a program that opens no spans: every reader gives None
    PROGRAM_SPANS = frozenset()
BATCHER_SPANS = frozenset(n for n in PROGRAM_SPANS if n.startswith("batcher."))


def serving_chip(trace) -> Optional[int]:
    """The chip with the most ``serve_step`` device time, None without one."""
    best, chip = 0.0, None
    for c in trace.chips:
        t, _ = trace.program("serve_step", c)
        if t > best:
            best, chip = t, c
    return chip


def idle_intervals(trace, chip: int) -> np.ndarray:
    """(n, 2) ns intervals in which ``chip`` runs nothing, between its first
    operation and its last."""
    iv = trace.busy_intervals(chip)
    gaps = np.stack([iv[:-1, 1], iv[1:, 0]], 1) if len(iv) > 1 else np.zeros((0, 2), np.int64)
    return gaps[gaps[:, 1] > gaps[:, 0]]


def spans(trace, names: Iterable[str]) -> np.ndarray:
    """(n, 2) ns [start, end) of every host event named in ``names``."""
    names = frozenset(names)
    hs, he = trace.host.arrays()
    keep = np.fromiter((n in names for n in trace.host.names), bool, len(hs))
    return np.stack([hs[keep], he[keep]], 1) if len(hs) else np.zeros((0, 2), np.int64)


def below(iv: np.ndarray, x) -> np.ndarray:
    """Length of the sorted, disjoint intervals ``iv`` that lies below each
    point of ``x``."""
    x = np.asarray(x, np.int64)
    if not len(iv):
        return np.zeros(x.shape, np.int64)
    lens = iv[:, 1] - iv[:, 0]
    cum = np.concatenate([[0], np.cumsum(lens)])
    k = np.searchsorted(iv[:, 0], x, side="right")  # intervals that start at or before x
    j = np.maximum(k - 1, 0)
    part = np.where(k > 0, np.clip(x - iv[j, 0], 0, lens[j]), 0)
    return cum[j] + part


def inside(iv: np.ndarray, s, e) -> np.ndarray:
    """Length of the sorted, disjoint intervals ``iv`` inside each [s, e)."""
    return below(iv, e) - below(iv, s)


def idle_under(idle: np.ndarray, sp: np.ndarray) -> int:
    """Idle ns of ``idle`` (sorted, disjoint) under the union of the spans
    ``sp``."""
    cover = trace_reduce.union(sp[:, 0], sp[:, 1])
    return int(inside(idle, cover[:, 0], cover[:, 1]).sum())


def us_per_wire(run, name: str) -> Optional[float]:
    """Host microseconds inside the ``name`` spans per request wire answered
    in the window."""
    if run.trace is None:
        return None
    sp = spans(run.trace, (name,))
    wires = sum(len(burst.wires) for burst, _ in run.calls)
    if not len(sp) or not wires:
        return None
    return float((sp[:, 1] - sp[:, 0]).sum()) / 1e3 / wires
