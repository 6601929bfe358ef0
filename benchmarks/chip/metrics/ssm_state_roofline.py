"""The Mamba layers' state update in the decode step, against its roofline.

Least time: each live sequence's float32 SSM state and conv state read once
and written once on every Mamba layer, each decode step (the family's
``state_step_bytes``, summed over the window's sequences' decode steps),
over the peak HBM bandwidth.  Device time: the ops of ``serve_step`` whose
result holds the slot cache's SSM state of one layer (the family's
``ssm_state_shape`` at the cell's slots, float32, in any order of its
dimensions).  None for a family without Mamba layers, or where no op
matches.
"""
from benchmarks.chip.metrics import _shapes


def read(run):
    f = run.family
    if run.trace is None or run.peak is None or not hasattr(f, "ssm_state_shape"):
        return None
    want = sorted(f.ssm_state_shape(run.sizes, run.cell.mix["slots"]))
    t = _shapes.step_op_seconds(run.trace, lambda res, _: any(
        dt == "f32" and sorted(dims) == want for dt, dims in res))
    if t <= 0:
        return None
    steps = sum(n_new - 1 for _, n_new in run.sequences())
    least = steps * f.state_step_bytes(run.sizes) / run.peak["hbm_bytes_per_s"]
    return 100.0 * least / t
