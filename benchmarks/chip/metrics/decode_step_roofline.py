"""Least time of the window's decode steps over their device time.

Device time: the ``serve_step`` program's executions in the trace.  Least
time: what every step reads whatever its batch (the family's ``step_bytes``,
the weights) plus what each sequence's step reads and writes of its own
state (``token_bytes``: its live KV rows, its prompt's and what it has
generated, padding left out), over the peak HBM bandwidth, or the steps'
FLOPs over the peak, whichever is larger.
"""
from benchmarks.chip import flops


def read(run):
    if run.trace is None or run.peak is None:
        return None
    t, n = run.trace.program("serve_step")
    if n == 0 or t <= 0:
        return None
    f, s = run.family, run.sizes
    fl, own = 0, 0
    for n_p, n_new in run.sequences():
        for i in range(n_new - 1):  # the first token comes from the prefill
            fl += f.decode_flops(s, n_p + i)
            own += f.token_bytes(s, n_p + i)
    least = flops.least_time(fl, n * f.step_bytes(s) + own, run.peak)
    return 100.0 * least / t
