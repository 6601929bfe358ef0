"""The ops of one mechanism inside the decode step, found by the shapes of
their arrays.

A trace's XLA ops carry no scope name: each is its HLO text alone,
``%name = <result types> opcode(<operand types> %a, ...), <attributes>``,
with a start and a duration.  A mechanism whose arrays have shapes no other
op of the step has is found by them: the ops whose result or whose operands
hold such an array, on the serving chip, inside the executions of the
``serve_step`` program.  The shapes come from the cell's family.
"""
import re
from typing import Callable, List, Tuple

import numpy as np

from benchmarks.chip import trace_reduce
from benchmarks.chip.metrics import _spans

Shape = Tuple[str, Tuple[int, ...]]  # (element type, dims)
_TYPE = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")
_OP = re.compile(r"^(.*?) ([a-z][\w\-]*)\(")


def _shapes(text: str) -> List[Shape]:
    return [(t, tuple(int(d) for d in dims.split(",") if d))
            for t, dims in _TYPE.findall(text)]


def parse(name: str) -> Tuple[List[Shape], List[Shape]]:
    """(result shapes, operand shapes) of one op's HLO text; layouts and
    attributes left out."""
    _, _, rhs = name.partition(" = ")
    while True:  # layouts {...}, innermost first
        bare = re.sub(r"\{[^{}]*\}", "", rhs)
        if bare == rhs:
            break
        rhs = bare
    m = _OP.match(rhs)
    if not m:
        return [], []
    depth, end = 1, m.end()
    while end < len(rhs) and depth:
        depth += {"(": 1, ")": -1}.get(rhs[end], 0)
        end += 1
    return _shapes(m.group(1)), _shapes(rhs[m.end():end - 1])


def step_op_seconds(trace, pick: Callable[[List[Shape], List[Shape]], bool]) -> float:
    """Device seconds of the ops that ``pick(results, operands)`` selects,
    on the serving chip, inside executions of ``serve_step``; 0 where none."""
    chip = _spans.serving_chip(trace)
    if chip is None or chip not in trace.ops:
        return 0.0
    mods = trace.modules.get(chip, trace_reduce.Events())
    pat = trace_reduce._program_re("serve_step")
    iv = np.array([(s, e) for n, s, e in zip(mods.names, mods.start, mods.end)
                   if pat.search(n)], np.int64).reshape(-1, 2)
    iv = trace_reduce.union(iv[:, 0], iv[:, 1])
    ops = trace.ops[chip]
    total = 0
    seen = {}
    for name, s, e in zip(ops.names, ops.start, ops.end):
        i = np.searchsorted(iv[:, 0], s, side="right") - 1
        if i < 0 or s >= iv[i, 1]:
            continue
        if name not in seen:
            seen[name] = pick(*parse(name))
        if seen[name]:
            total += e - s
    return total / 1e9
