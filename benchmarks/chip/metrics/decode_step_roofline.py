"""Least time of the window's decode steps over their device time.

Device time: the ``serve_step`` program's executions in the trace.  Least
time: the weights every step reads plus the live KV rows each sequence
reads (its own prompt and what it has generated, padding left out), over
the peak HBM bandwidth, or the steps' FLOPs over the peak, whichever is
larger.
"""
from benchmarks.chip import flops


def read(run):
    if run.trace is None or run.peak is None:
        return None
    t, n = run.trace.program("serve_step")
    if n == 0 or t <= 0:
        return None
    s = run.sizes
    kv = flops.kv_bytes_per_token(s)
    fl, kv_bytes = 0, 0
    for n_p, n_new in run.sequences():
        for i in range(n_new - 1):  # the first token comes from the prefill
            fl += flops.decode_flops(s, n_p + i)
            kv_bytes += (n_p + i + 1) * kv
    least = flops.least_time(fl, n * flops.weight_bytes_per_step(s) + kv_bytes, run.peak)
    return 100.0 * least / t
