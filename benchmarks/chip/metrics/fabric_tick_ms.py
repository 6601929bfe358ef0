"""Device time of one fabric tick, on the busiest chip: the fused router
tick (``fabric/router.py``, a ``jax.jit`` of ``shard_map(local)``, compiled
as ``jit_local``) summed per chip over the window, over its executions."""

PROGRAM = "local"


def read(run):
    if run.trace is None:
        return None
    per_chip = []
    for chip in run.trace.chips:
        t, n = run.trace.program(PROGRAM, chip)
        if n:
            per_chip.append(t / n)
    return 1e3 * max(per_chip) if per_chip else None
