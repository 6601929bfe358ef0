"""The roofline's least time, from operations and bytes.

The operations and bytes themselves are counted from shapes by each
architecture's family module (``families/<reference>.py``: ``prefill_flops``,
``decode_flops``, ``sequence_flops``, ``step_bytes``, ``token_bytes``,
``prefill_bytes``); this is the part that is not per family.
"""
from __future__ import annotations


def least_time(flops: float, bytes_: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["bf16_flops_per_s"], bytes_ / peak["hbm_bytes_per_s"])
