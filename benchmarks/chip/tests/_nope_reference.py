"""The plain reference of the test-only NoPE family (``_nope_family.py``):
the dense decoder's, with rotary on none of the head's dimensions, so that
queries and keys reach the scores as projected."""
from benchmarks.chip.references import dense_decoder as dense


def logits(config, view, tokens, rows, quant=False):
    return dense.logits(dict(config, partial_rotary_factor=0.0), view, tokens, rows, quant)
