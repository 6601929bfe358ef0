"""Per-sequence token arrival times at the ingress, from the streaming
entry's ``on_token`` hook, and the tail statistic over raw samples."""
import numpy as np


def arrivals(run):
    """(call start, [arrival times]) of every sequence of the window's calls."""
    for burst, res in run.calls:
        for w, prompts in enumerate(burst.prompts):
            for j in range(len(prompts)):
                yield res.t0, [t for t, _ in res.arrivals.get((w, j), [])]


def p95(samples):
    """95th percentile of the raw samples, linear between order statistics."""
    return float(np.percentile(np.asarray(samples, np.float64), 95)) if samples else None
