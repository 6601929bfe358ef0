"""From a ``jax.profiler`` trace to the numbers the per-layer metrics read.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it.  On a TPU each chip is a plane named
``/device:TPU:<n>``, with a line ``XLA Ops`` (every operation, kernels
included, with its start and duration in ns) and a line ``XLA Modules`` (one
event per execution of a compiled program, named after the jitted function,
``jit_serve_step(...)``).  The host is ``/host:CPU``, one line per thread.

* busy time of a chip: the union of its ``XLA Ops`` intervals;
* a program's device time: the sum of its ``XLA Modules`` events, found by
  the jitted function's name;
* a kernel's device time: the sum of the ``XLA Ops`` events that carry its
  name;
* idle gaps: the spaces between busy intervals on a chip, each labelled
  with the innermost host event that covers it.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"


@dataclass
class Events:
    names: List[str] = field(default_factory=list)
    start: List[int] = field(default_factory=list)
    end: List[int] = field(default_factory=list)

    def add(self, name: str, start: int, dur: int) -> None:
        self.names.append(name)
        self.start.append(start)
        self.end.append(start + dur)

    def arrays(self) -> Tuple[np.ndarray, np.ndarray]:
        return np.asarray(self.start, np.int64), np.asarray(self.end, np.int64)


@dataclass
class Trace:
    ops: Dict[int, Events]  # chip id -> XLA Ops
    modules: Dict[int, Events]  # chip id -> XLA Modules
    host: Events

    # -- device time --------------------------------------------------------

    def busy_intervals(self, chip: int) -> np.ndarray:
        ev = self.ops.get(chip)
        if ev is None or not ev.names:
            ev = self.modules.get(chip, Events())
        s, e = ev.arrays()
        return union(s, e)

    def busy_s(self, chip: int) -> float:
        iv = self.busy_intervals(chip)
        return float((iv[:, 1] - iv[:, 0]).sum()) / 1e9 if len(iv) else 0.0

    @property
    def chips(self) -> List[int]:
        return sorted(set(self.ops) | set(self.modules))

    def program(self, name: str, chip: Optional[int] = None) -> Tuple[float, int]:
        """(device seconds, executions) of the jitted function ``name``."""
        return _match(self.modules, _program_re(name), chip)

    def kernel(self, name: str, chip: Optional[int] = None) -> Tuple[float, int]:
        """(device seconds, executions) of the ops that carry ``name``."""
        return _match(self.ops, re.compile(re.escape(name)), chip)

    # -- breakdown ------------------------------------------------------------

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        tot: Dict[str, float] = {}
        for ev in self.ops.values():
            s, e = ev.arrays()
            for name, d in zip(ev.names, (e - s)):
                key = _op_family(name)
                tot[key] = tot.get(key, 0.0) + float(d) / 1e9
        k = max(1, len(self.ops))
        return sorted(((a, b / k) for a, b in tot.items()), key=lambda x: -x[1])[:n]

    def idle_gaps(self, chip: int, lo: int, hi: int, n: int = 10) -> List[Tuple[str, float]]:
        """The ``n`` longest idle gaps on ``chip`` inside [lo, hi] ns, each
        named by the host event that overlaps it most."""
        iv = self.busy_intervals(chip)
        iv = iv[(iv[:, 1] > lo) & (iv[:, 0] < hi)] if len(iv) else iv
        edges = np.concatenate([[lo], iv.reshape(-1), [hi]]) if len(iv) else np.array([lo, hi])
        gaps = edges.reshape(-1, 2)
        gaps = gaps[gaps[:, 1] > gaps[:, 0]]
        order = np.argsort(-(gaps[:, 1] - gaps[:, 0]))[:n]
        hs, he = self.host.arrays()
        out = []
        for g0, g1 in gaps[order]:
            out.append((self._host_label(hs, he, g0, g1)[:80], float(g1 - g0) / 1e9))
        return out

    def _host_label(self, hs, he, g0, g1) -> str:
        """The innermost host event covering most of [g0, g1): the shortest
        of those that overlap at least half of it, else the one that
        overlaps it most."""
        if not len(hs):
            return "no host event"
        ov = np.minimum(he, g1) - np.maximum(hs, g0)
        cover = np.flatnonzero(ov >= (g1 - g0) / 2)
        if len(cover):
            return self.host.names[int(cover[np.argmin((he - hs)[cover])])]
        best = int(np.argmax(ov))
        return self.host.names[best] if ov[best] > 0 else "no host event"


def union(s: np.ndarray, e: np.ndarray) -> np.ndarray:
    """Merged, sorted (n, 2) intervals covering the union of [s_i, e_i)."""
    if len(s) == 0:
        return np.zeros((0, 2), np.int64)
    o = np.argsort(s, kind="stable")
    s, e = s[o], e[o]
    reach = np.maximum.accumulate(e)
    first = np.flatnonzero(np.concatenate([[True], s[1:] > reach[:-1]]))
    last = np.concatenate([first[1:] - 1, [len(s) - 1]])
    starts, ends = s[first], reach[last]
    return np.stack([starts, ends], 1)


def _program_re(name: str):
    # XLA names a jitted function's module jit_<name>, optionally with a
    # suffix such as (123) or .1
    return re.compile(rf"^jit_{re.escape(name)}(\W|$)")


def _op_family(name: str) -> str:
    """An XLA op's kind and output type, without its number or layouts:
    ``%fusion.946 = (f32[32]{...}, bf16[32,4096]{...}) fusion(...)`` becomes
    ``fusion (f32[32], bf16[32,4096])``, so that the same op of every layer
    and step adds up."""
    lhs, _, rhs = name.partition(" = ")
    op = re.sub(r"\.\d+$", "", lhs.lstrip("%"))
    m = re.match(r"^(.*?) [a-z][\w\-]*\(", re.sub(r"\{[^{}]*\}", "", rhs))
    return f"{op} {m.group(1)}" if m else op


def _match(table: Dict[int, Events], pat, chip: Optional[int]) -> Tuple[float, int]:
    total, count = 0, 0
    for c, ev in table.items():
        if chip is not None and c != chip:
            continue
        for name, s, e in zip(ev.names, ev.start, ev.end):
            if pat.search(name):
                total += e - s
                count += 1
    return total / 1e9, count


def find_xplane(directory: str) -> str:
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {directory}, found {files}")
    return files[0]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: Dict[int, Events] = {}
    modules: Dict[int, Events] = {}
    host = Events()
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                dest = {"XLA Ops": ops, "XLA Modules": modules}.get(line.name)
                if dest is None:
                    continue
                ev = dest.setdefault(chip, Events())
                for e in line.events:
                    ev.add(e.name, int(e.start_ns), int(e.duration_ns))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    host.add(e.name, int(e.start_ns), int(e.duration_ns))
    return Trace(ops, modules, host)
