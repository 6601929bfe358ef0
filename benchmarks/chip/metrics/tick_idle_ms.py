"""Idle time of the serving chip inside one tick of the streaming loop
(``launch/serve.py`` ``serve_requests_streaming``): its idle time inside each
``serve.tick`` span, averaged over the window's ticks."""
from benchmarks.chip.metrics import _spans


def read(run):
    if run.trace is None:
        return None
    chip = _spans.serving_chip(run.trace)
    ticks = _spans.spans(run.trace, ("serve.tick",))
    if chip is None or not len(ticks):
        return None
    idle = _spans.idle_intervals(run.trace, chip)
    return float(_spans.inside(idle, ticks[:, 0], ticks[:, 1]).mean()) / 1e6
