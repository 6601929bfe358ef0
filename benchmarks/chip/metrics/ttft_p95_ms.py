"""95th percentile, over every sequence of the window's calls, of the time
from the call's start (every wire of the burst is due then) to the arrival of
the sequence's first token at the ingress."""
from benchmarks.chip.metrics import _tails


def read(run):
    if not run.streams:
        return None
    ttft = [ts[0] - t0 for t0, ts in _tails.arrivals(run) if ts]
    v = _tails.p95(ttft)
    return None if v is None else 1e3 * v
