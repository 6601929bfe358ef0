"""Least time of the window's prefills over their device time.

Device time: the ``prefill_step`` program's executions in the trace.  Least
time: the FLOPs of the prompts' own tokens (padding left out) over the peak,
or the weights each execution reads over the peak HBM bandwidth, whichever
is larger.
"""
from benchmarks.chip import flops


def read(run):
    if run.trace is None or run.peak is None:
        return None
    t, n = run.trace.program("prefill_step")
    if n == 0 or t <= 0:
        return None
    s = run.sizes
    fl = sum(flops.prefill_flops(s, n_p) for n_p, _ in run.sequences())
    least = flops.least_time(fl, n * flops.weight_bytes_per_step(s), run.peak)
    return 100.0 * least / t
