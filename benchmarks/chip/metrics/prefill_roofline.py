"""Least time of the window's prefills over their device time.

Device time: the ``prefill_step`` program's executions in the trace.  Least
time: the FLOPs of the prompts' own tokens (padding left out; the family's
``prefill_flops``) over the peak, or what each execution reads
(``prefill_bytes``, the weights) over the peak HBM bandwidth, whichever is
larger.
"""
from benchmarks.chip import flops


def read(run):
    if run.trace is None or run.peak is None:
        return None
    t, n = run.trace.program("prefill_step")
    if n == 0 or t <= 0:
        return None
    f, s = run.family, run.sizes
    fl = sum(f.prefill_flops(s, n_p) for n_p, _ in run.sequences())
    least = flops.least_time(fl, n * f.prefill_bytes(s), run.peak)
    return 100.0 * least / t
