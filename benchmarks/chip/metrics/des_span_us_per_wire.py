"""DES inside the window: host time in the ``serve.des`` spans
(``launch/serve.py`` ``decode_request_batch``, wherever the serve path calls
it), summed over the traced window, per request wire answered in it."""
from benchmarks.chip.metrics import _spans


def read(run):
    return _spans.us_per_wire(run, "serve.des")
