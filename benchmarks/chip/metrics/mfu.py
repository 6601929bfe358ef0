"""Model FLOPs the window's requests need, over window x chips x the chip's
peak bf16 FLOP/s.  Counted from shapes by the cell's family
(``sequence_flops``): each prompt's own tokens under causal attention, and
each generated token at its live length; padding is not useful work."""


def read(run):
    if run.peak is None or run.window_s <= 0:
        return None
    f, s = run.family, run.sizes
    total = sum(f.sequence_flops(s, n_p, n_new) for n_p, n_new in run.sequences())
    return 100.0 * total / (run.window_s * run.cell.chips * run.peak["bf16_flops_per_s"])
