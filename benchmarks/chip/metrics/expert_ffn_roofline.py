"""The held experts in the decode step, against their roofline.

Least time: the larger of the held experts' operations at the expected
routed pairs (the family's ``expert_flops`` per token and layer, over the
window's decode tokens) over the peak FLOP/s, and their weights read once
per decode step (``expert_weight_bytes`` times the ``serve_step``
executions) over the peak HBM bandwidth.  Device time: the ops of
``serve_step`` that take a held expert weight as an operand (the family's
``expert_weight_shapes``: leading the held count, then the model and expert
widths, in either order).  None for a family without held experts, or where no op matches.
"""
from benchmarks.chip import flops
from benchmarks.chip.metrics import _shapes


def read(run):
    f, s = run.family, run.sizes
    if run.trace is None or run.peak is None or not hasattr(f, "expert_weight_shapes"):
        return None
    want = {(w[0], tuple(sorted(w[1:]))) for w in f.expert_weight_shapes(s)}
    t = _shapes.step_op_seconds(run.trace, lambda _, ops: any(
        len(dims) == 3 and (dims[0], tuple(sorted(dims[1:]))) in want for _, dims in ops))
    _, steps = run.trace.program("serve_step")
    if t <= 0 or not steps:
        return None
    tokens = sum(n_new - 1 for _, n_new in run.sequences())
    least = flops.least_time(tokens * s.layers * f.expert_flops(s),
                             steps * f.expert_weight_bytes(s), run.peak)
    return 100.0 * least / t
