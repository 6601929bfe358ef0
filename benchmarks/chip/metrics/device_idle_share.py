"""Share of the traced window in which no operation runs on the chip:
1 - (union of the chip's XLA Ops intervals) / window, averaged over the
cell's chips."""


def read(run):
    if run.trace is None or run.window_s <= 0:
        return None
    chips = range(run.cell.chips)
    if not all(c in run.trace.chips for c in chips):
        return None  # no device trace for a chip: nothing to read
    busy = sum(run.trace.busy_s(c) for c in chips) / run.cell.chips
    return 100.0 * (1.0 - busy / run.window_s)
