"""Share of the serving chip's idle time, between its first operation and its
last, that lies under no span of the program (``_spans.PROGRAM_SPANS``; the
harness's ``bench.call`` and the Python tracer's frames do not count)."""
from benchmarks.chip.metrics import _spans


def read(run):
    if run.trace is None:
        return None
    chip = _spans.serving_chip(run.trace)
    sp = _spans.spans(run.trace, _spans.PROGRAM_SPANS)
    if chip is None or not len(sp):
        return None
    idle = _spans.idle_intervals(run.trace, chip)
    total = int((idle[:, 1] - idle[:, 0]).sum())
    return 100.0 * (total - _spans.idle_under(idle, sp)) / total if total else None
