"""SER inside the window: host time in the ``serve.ser`` spans
(``launch/serve.py`` ``encode_response_batch``), summed over the traced
window, per request wire answered in it."""
from benchmarks.chip.metrics import _spans


def read(run):
    return _spans.us_per_wire(run, "serve.ser")
