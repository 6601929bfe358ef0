"""Operations and bytes that serving a sequence needs, counted from shapes.

Counts are of the algorithm, not of the compiled program: a change to the
implementation does not change them.  A multiply-add is 2 operations.
Padding, and positions a program computes but nobody asked for, are not
useful work and are not counted.

For a dense decoder of ``L`` layers, a token at position ``t`` (so it
attends to ``t + 1`` positions, itself included) costs

    2 * L * (d * (H + 2 * K) * hd + H * hd * d + (3 if GLU else 2) * d * d_ff)
  + 4 * L * H * hd * (t + 1)           (scores and weighted values)

and every token whose next-token logits are wanted a further ``2 * d * V``.
"""
from __future__ import annotations

from .spec import Sizes


def layer_matmul_params(s: Sizes) -> int:
    attn = s.d * (s.heads + 2 * s.kv_heads) * s.head_dim + s.heads * s.head_dim * s.d
    ffn = (3 if s.glu else 2) * s.d * s.d_ff
    return s.layers * (attn + ffn)


def param_count(s: Sizes) -> int:
    """Weights of the whole model, norms included."""
    norms = (2 * s.layers + 1) * s.d * (2 if s.layernorm else 1)
    embed = s.vocab * s.d * (1 if s.tied else 2)
    return layer_matmul_params(s) + embed + norms


def attn_flops(s: Sizes, first: int, n: int) -> int:
    """Scores and weighted values of ``n`` consecutive tokens at positions
    ``first .. first + n - 1`` under causal attention."""
    # sum over t of (t + 1) for t in [first, first + n)
    ctx = n * first + n * (n + 1) // 2
    return 4 * s.layers * s.heads * s.head_dim * ctx


def prefill_flops(s: Sizes, n_prompt: int) -> int:
    """A prompt of ``n_prompt`` tokens and the logits of its last position."""
    return (2 * layer_matmul_params(s) * n_prompt + attn_flops(s, 0, n_prompt)
            + 2 * s.d * s.vocab)


def decode_flops(s: Sizes, pos: int) -> int:
    """One generated token fed back at position ``pos``, with its logits."""
    return 2 * layer_matmul_params(s) + attn_flops(s, pos, 1) + 2 * s.d * s.vocab


def sequence_flops(s: Sizes, n_prompt: int, n_new: int) -> int:
    """Serving one prompt for ``n_new`` tokens: the prefill gives the first
    token, and each later one is a decode step fed the token before it."""
    total = prefill_flops(s, n_prompt)
    for i in range(n_new - 1):
        total += decode_flops(s, n_prompt + i)
    return total


def weight_bytes_per_step(s: Sizes) -> int:
    """Weights one decode step has to read: every layer matrix, the norms and
    the output head; of the embedding table only a row per sequence, which is
    left out."""
    head = s.vocab * s.d
    norms = (2 * s.layers + 1) * s.d * (2 if s.layernorm else 1)
    return s.dtype_bytes * (layer_matmul_params(s) + head + norms)


def kv_bytes_per_token(s: Sizes) -> int:
    return s.dtype_bytes * 2 * s.layers * s.kv_heads * s.head_dim


def least_time(flops: float, bytes_: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["bf16_flops_per_s"], bytes_ / peak["hbm_bytes_per_s"])
