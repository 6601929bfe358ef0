"""Device time of the chunk-pack kernel (``kernels/frame_pack.py``,
``hgum_pack_chunks``, which serializes every tick's token chunks) per fabric
tick, on the chip that runs it."""

KERNEL = "hgum_pack_chunks"
TICK = "local"


def read(run):
    if run.trace is None:
        return None
    best = None
    for chip in run.trace.chips:
        t, n = run.trace.kernel(KERNEL, chip)
        _, ticks = run.trace.program(TICK, chip)
        if n and ticks:
            best = max(best or 0.0, t / ticks)
    return None if best is None else 1e3 * best
