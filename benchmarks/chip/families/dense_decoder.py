"""The dense decoder family: Llama (Yi) and StableLM.

Everything the benchmark knows of this architecture, beside its plain
reference (``references/dense_decoder.py``): the shapes it counts with, the
program fields that must agree with the config file, how each weight starts,
what the reference reads, the operations and bytes of serving, and the cut
for CPU tests.  The harness, the check and the metric readers reach it only
through the names below; a family of another architecture is another module
with the same names, found by the config file's ``reference`` key.

Counts are of the algorithm, not of the compiled program: a change to the
implementation does not change them.  A multiply-add is 2 operations.
Padding, and positions a program computes but nobody asked for, are not
useful work and are not counted.  For ``L`` layers, a token at position
``t`` (so it attends to ``t + 1`` positions, itself included) costs

    2 * L * (d * (H + 2 * K) * hd + H * hd * d + (3 if GLU else 2) * d * d_ff)
  + 4 * L * H * hd * (t + 1)           (scores and weighted values)

and every token whose next-token logits are wanted a further ``2 * d * V``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Tuple

NORMS = ("ln1", "ln2", "final_norm")


@dataclass(frozen=True)
class Sizes:
    """The shapes the yardstick counts with, read from the config file alone."""

    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    glu: bool
    tied: bool
    layernorm: bool  # norms carry a bias beside the scale
    dtype_bytes: int


def sizes(c: Dict) -> Sizes:
    d, h = c["hidden_size"], c["num_attention_heads"]
    return Sizes(
        layers=c["num_hidden_layers"], d=d, heads=h,
        kv_heads=c["num_key_value_heads"],
        head_dim=c.get("head_dim") or d // h,
        d_ff=c["intermediate_size"], vocab=c["vocab_size"],
        glu=c["hidden_act"] == "silu",
        tied=bool(c["tie_word_embeddings"]),
        layernorm=c["norm"] == "layernorm",
        dtype_bytes={"bfloat16": 2, "float32": 4}[c["torch_dtype"]],
    )


def program_fields(config: Dict, s: Sizes) -> Dict:
    """The program's ``ModelConfig`` fields, and the values the file implies."""
    eps = config.get("rms_norm_eps", config.get("layer_norm_eps"))
    return {
        "n_layers": s.layers, "d_model": s.d, "n_heads": s.heads,
        "n_kv": s.kv_heads, "hd": s.head_dim, "d_ff": s.d_ff, "vocab": s.vocab,
        "tie_embeddings": s.tied, "rope_theta": float(config["rope_theta"]),
        "norm_eps": float(eps), "norm": config["norm"], "dtype": config["torch_dtype"],
        "act": "swiglu" if s.glu else "gelu", "family": "lm", "window": None,
        "moe_experts": 0, "layer_pattern": "attn", "use_rope": True,
        "embed_scale": False, "sandwich_norm": False, "attn_softcap": None,
        "final_softcap": None, "padded_vocab": s.vocab,
    }


def init_kind(keys: List, cfg) -> str:
    """Every matrix N(0, initializer_range**2); every norm at the published
    identity, scale 1 and bias 0."""
    if any(k in NORMS for k in keys):
        # the program keeps an RMSNorm weight as an offset from 1
        if keys[-1] == "bias" or (cfg.norm == "rmsnorm" and keys[-1] == "scale"):
            return "zeros"
        return "ones"
    return "normal"


def view(params) -> Dict:
    """What the reference reads: ``embed (V, d)``; per layer the matrices in
    ``x @ W`` form, ``wq (d, H*hd)``, ``wk``/``wv (d, K*hd)``, ``wo (H*hd,
    d)``, ``w_gate``/``w_up (d, d_ff)``, ``w_down (d_ff, d)``; and ``head (d,
    V)``, the untied head or the embedding's transpose when tied.  Norms are
    not read: they start at the identity."""
    layers = [{"wq": lp["attn"]["wq"], "wk": lp["attn"]["wk"], "wv": lp["attn"]["wv"],
               "wo": lp["attn"]["wo"], "w_gate": lp["ffn"]["wg"],
               "w_up": lp["ffn"]["wi"], "w_down": lp["ffn"]["wo"]}
              for lp in params["layers"]]
    head = params["lm_head"] if "lm_head" in params else params["embed"].T
    return {"embed": params["embed"], "layers": layers, "head": head}


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


def step_bytes(s: Sizes) -> int:
    """Weights one decode step has to read whatever its batch: every layer
    matrix, the norms and the output head; of the embedding table only a row
    per sequence, which is left out."""
    head = s.vocab * s.d
    norms = (2 * s.layers + 1) * s.d * (2 if s.layernorm else 1)
    return s.dtype_bytes * (layer_matmul_params(s) + head + norms)


def prefill_bytes(s: Sizes) -> int:
    """Weights one prefill execution reads: those of a decode step."""
    return step_bytes(s)


def kv_bytes_per_token(s: Sizes) -> int:
    return s.dtype_bytes * 2 * s.layers * s.kv_heads * s.head_dim


def token_bytes(s: Sizes, pos: int) -> int:
    """What one sequence's decode step at ``pos`` reads of its own state: the
    live K/V rows, its prompt's and what it has generated, itself included."""
    return (pos + 1) * kv_bytes_per_token(s)


TINY = dict(hidden_size=128, num_attention_heads=4, intermediate_size=256,
            vocab_size=512, num_hidden_layers=2)


def tiny(config: Dict) -> Tuple[Dict, Dict]:
    """The cut for CPU tests: (config file at that size, ``ModelConfig``
    replace arguments), every width and depth cut to what a CPU test holds,
    grouped-query attention kept grouped."""
    s = sizes(config)
    conf = dict(config, **TINY)
    # the published init scaled so that activations keep their full-size
    # statistics at the small width: std * sqrt(d) is held fixed
    conf["initializer_range"] = config["initializer_range"] * (
        s.d / TINY["hidden_size"]) ** 0.5
    conf["num_key_value_heads"] = 2 if s.kv_heads < s.heads else 4
    replace = dict(n_layers=2, d_model=128, n_heads=4, n_kv=conf["num_key_value_heads"],
                   head_dim=32, d_ff=256, vocab=512)
    return conf, replace
