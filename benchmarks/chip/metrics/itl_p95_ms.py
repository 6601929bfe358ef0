"""95th percentile, over every sequence of the window's calls, of the
sequence's inter-token latency: from its first token's arrival at the
ingress to its last's, over the gaps between them.  Each sample spans the
whole generation (seconds), not one tick, so the host clock's error stays
far below the bound."""
from benchmarks.chip.metrics import _tails


def read(run):
    if not run.streams:
        return None
    itl = [(ts[-1] - ts[0]) / (len(ts) - 1) for _, ts in _tails.arrivals(run) if len(ts) > 1]
    v = _tails.p95(itl)
    return None if v is None else 1e3 * v
