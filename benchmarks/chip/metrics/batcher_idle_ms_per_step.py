"""Host time per decode step that leaves the serving chip idle inside the
continuous batcher (``runtime/scheduler.py``): the serving chip's idle time
under the union of the ``batcher.*`` spans, over the number of
``batcher.dispatch`` spans (one per decode step)."""
from benchmarks.chip.metrics import _spans


def read(run):
    if run.trace is None:
        return None
    chip = _spans.serving_chip(run.trace)
    sp = _spans.spans(run.trace, _spans.BATCHER_SPANS)
    steps = len(_spans.spans(run.trace, ("batcher.dispatch",)))
    if chip is None or not steps:
        return None
    return _spans.idle_under(_spans.idle_intervals(run.trace, chip), sp) / 1e6 / steps
