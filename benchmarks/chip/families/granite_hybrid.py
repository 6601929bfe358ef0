"""The hybrid Mamba-2 / MoE family: granite-4.0-h-small (``granitemoehybrid``).

Everything the benchmark knows of this architecture, beside its plain
reference (``references/granite_hybrid.py``), under the names the harness
and the readers use (``families/dense_decoder.py`` says what each is for).

Counts are of the algorithm, not of the compiled program.  A multiply-add
is 2 operations.  Per token and layer:

* a Mamba-2 layer: its two projections, ``2 * d * (2 * d_in + 2 * N + nh)
  + 2 * d_in * d``, and the recurrence, ``5 * nh * P * N`` (the state's
  decay, the outer product ``dt x B`` and their sum, 3 per element; the
  read-out ``C . state``, 2 per element).  The chunked scan that prefill
  runs does more than this; the count is of the recurrence it computes;
* an attention layer: ``2 * (d * (H + 2 K) * hd + H * hd * d)`` and
  ``4 * H * hd * (t + 1)`` at position ``t``;
* every layer's MoE: the router, ``2 * d * E`` over all ``E`` experts; the
  shared expert, ``6 * d * shared``; and the experts held here at the
  expected number of routed pairs, ``topk * held / E`` (10 * 9 / 72 = 1.25)
  per token, ``6 * d * ff`` each.  Which expert a token picks depends on
  the weights; the expectation is the count of a router that spreads its
  picks evenly;

and every token whose next-token logits are wanted ``2 * d * V``.  Bytes:
every weight held here, once per step (``step_bytes``); each sequence's own
state, read and written once per step (``token_bytes``): the float32 SSM
state and the conv state of every Mamba layer, and the live K/V rows of the
attention layers only.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

NORMS = ("ln1", "ln2", "final_norm", "norm")  # "norm": Mamba-2's gated norm


@dataclass(frozen=True)
class Sizes:
    """The shapes the yardstick counts with, read from the config file alone."""

    layer_types: Tuple[str, ...]
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    experts: int  # the router's width: every expert of the deployment
    held: int  # experts held here
    first_held: int
    topk: int
    ff: int  # one expert's width
    shared_ff: int
    ssm_heads: int
    ssm_head_dim: int
    ssm_state: int
    conv: int
    d_inner: int
    vocab: int
    dtype_bytes: int

    @property
    def layers(self) -> int:
        return len(self.layer_types)

    @property
    def n_mamba(self) -> int:
        return self.layer_types.count("mamba")

    @property
    def n_attn(self) -> int:
        return self.layer_types.count("attention")

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.ssm_state  # x, B and C of one group

    @property
    def routed_pairs(self) -> float:
        """Expected (token, expert) pairs per token and layer that fall on
        the experts held here."""
        return self.topk * self.held / self.experts


def sizes(c: Dict) -> Sizes:
    if c["mamba_n_groups"] != 1 or c["position_embedding_type"] != "nope":
        raise ValueError("the program runs Mamba-2 with one group, and NoPE attention")
    d, h = c["hidden_size"], c["num_attention_heads"]
    nh, p = c["mamba_n_heads"], c["mamba_d_head"]
    if abs(c["initializer_range"] * d ** 0.5 - INIT_STD_SQRT_D) > 1e-9:
        raise ValueError("the embedding's draw assumes initializer_range 0.02 at d 4096")
    if nh * p != c["mamba_expand"] * d:
        raise ValueError("mamba_n_heads * mamba_d_head must be mamba_expand * hidden_size")
    return Sizes(
        layer_types=tuple(c["layer_types"][: c["num_hidden_layers"]]),
        d=d, heads=h, kv_heads=c["num_key_value_heads"], head_dim=d // h,
        experts=c["published"]["num_local_experts"], held=c["num_local_experts"],
        first_held=c["first_expert_held"], topk=c["num_experts_per_tok"],
        ff=c["intermediate_size"], shared_ff=c["shared_intermediate_size"],
        ssm_heads=nh, ssm_head_dim=p, ssm_state=c["mamba_d_state"],
        conv=c["mamba_d_conv"], d_inner=nh * p, vocab=c["vocab_size"],
        dtype_bytes={"bfloat16": 2, "float32": 4}[c["torch_dtype"]],
    )


def program_fields(config: Dict, s: Sizes) -> Dict:
    """The program's ``ModelConfig`` fields, and the values the file implies."""
    return {
        "n_layers": s.layers, "layer_types": s.layer_types,
        "d_model": s.d, "n_heads": s.heads, "n_kv": s.kv_heads, "hd": s.head_dim,
        "vocab": s.vocab, "padded_vocab": s.vocab, "family": "lm", "dtype": config["torch_dtype"],
        "use_rope": False, "attn_scale": float(config["attention_multiplier"]),
        "window": None, "attn_softcap": None, "final_softcap": None,
        "moe_experts": s.experts, "moe_held": s.held, "moe_held_first": s.first_held,
        "moe_topk": s.topk, "moe_dff": s.ff, "d_ff": s.ff, "moe_every": 1,
        "moe_shared_dff": s.shared_ff, "act": "swiglu",
        "ssm_head": s.ssm_head_dim, "ssm_state": s.ssm_state, "ssm_conv": s.conv,
        "ssm_expand": config["mamba_expand"],
        "norm": "rmsnorm", "norm_eps": float(config["rms_norm_eps"]),
        "embed_mult": float(config["embedding_multiplier"]),
        "residual_mult": float(config["residual_multiplier"]),
        "logits_div": float(config["logits_scaling"]),
        # tied as published, the head held as its own copy (``init_kind``)
        "tie_embeddings": not config["tie_word_embeddings"],
        "rope_theta": float(config["rope_theta"]),  # unused: NoPE
        "embed_scale": False, "sandwich_norm": False,
    }


# --- weights ---------------------------------------------------------------


def _uniform(lo: float, hi: float):
    def draw(key, shape, dtype):
        return jax.random.uniform(key, shape, jnp.float32, lo, hi).astype(dtype)
    return draw


def _a_log(key, shape, dtype):
    """Mamba-2: A drawn uniform on [1, 16] per head, kept as log A."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)).astype(dtype)


def _dt_bias(key, shape, dtype):
    """Mamba-2: dt drawn log-uniform on [1e-3, 1e-1], floored at 1e-4, and
    the bias set to its inverse softplus, so that softplus(bias) = dt."""
    lo, hi = math.log(1e-3), math.log(1e-1)
    dt = jnp.maximum(jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi)), 1e-4)
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


#: the file's initializer_range at the published width; the CPU cut
#: (``tiny``) keeps ``initializer_range * sqrt(d)`` fixed, and so does this
INIT_STD_SQRT_D = 0.02 * 4096 ** 0.5


#: the key the embedding was drawn from, for the head's draw in the same
#: trace of the weight maker, which draws leaves in flatten order: "embed"
#: before "lm_head"
_EMBED_KEY = {}


def _embedding(cfg):
    """The embedding, drawn N(0, (initializer_range / embedding_multiplier)^2),
    so that what it puts into the residual stream (times the multiplier) has
    the spread of every other weight.  At initializer_range it would be 12
    times that, and the head, which is this table, would then rank each
    position's own input token first by several standard deviations whatever
    the layers computed: every sequence would repeat its last token, and the
    check would compare nothing."""
    std = INIT_STD_SQRT_D / cfg.d_model ** 0.5 / cfg.embed_mult

    def draw(key, shape, dtype):
        _EMBED_KEY[cfg] = key
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)

    def head(key, shape, dtype):
        """The head: the embedding's transpose, bit for bit (tied)."""
        e = jax.random.normal(_EMBED_KEY.pop(cfg), shape[::-1], jnp.float32) * std
        return e.T.astype(dtype)
    return draw, head


def init_kind(keys: List, cfg):
    """Matrices N(0, initializer_range**2); the embedding as ``_embedding``
    says, and the head as its transpose: the published model ties them, and
    the program holds the head as an array of its own, as the last stage of
    a pipeline holds a tied head; norms at the identity (the
    program keeps an RMSNorm weight as an offset from 1, so zeros); Mamba-2's
    own parameters as its published initialisation: A_log and dt_bias drawn
    as ``_a_log`` and ``_dt_bias`` say, D ones, and the depthwise conv and
    its bias at PyTorch's default for a conv of fan-in 4, uniform on
    [-1/2, 1/2]."""
    last = keys[-1]
    if last == "embed":
        return _embedding(cfg)[0]
    if last == "lm_head":
        return _embedding(cfg)[1]
    if any(k in NORMS for k in keys):
        return "zeros"
    if "mamba" in keys:
        if last == "A_log":
            return _a_log
        if last == "dt_bias":
            return _dt_bias
        if last == "D":
            return "ones"
        if last in ("conv_w", "conv_b"):
            bound = 1.0 / math.sqrt(cfg.ssm_conv)
            return _uniform(-bound, bound)
    return "normal"


def view(params) -> Dict:
    """What the reference reads: ``embed (V, d)``, which is also the head
    (the program's ``lm_head`` is its transpose); per layer
    its ``kind`` and, in ``x @ W`` form, for a Mamba layer ``in_proj (d,
    2 d_in + 2 N + nh)``, ``conv_w (K, conv_dim)``, ``conv_b``, ``A_log``,
    ``D``, ``dt_bias``, ``out_proj (d_in, d)``; for an attention layer ``wq``,
    ``wk``, ``wv``, ``wo``; and for both the MoE: ``router (d, E)``, the held
    experts' ``w_gate``/``w_up (held, d, ff)`` and ``w_down (held, ff, d)``,
    and the shared expert's ``s_gate``/``s_up (d, shared)``, ``s_down``.
    Norms are not read: they start at the identity."""
    layers = []
    for lp in params["layers"]:
        moe = lp["moe"]
        w = {"router": moe["router"], "w_gate": moe["wg"], "w_up": moe["wi"],
             "w_down": moe["wo"], "s_gate": moe["shared"]["wg"],
             "s_up": moe["shared"]["wi"], "s_down": moe["shared"]["wo"]}
        if "mamba" in lp:
            w.update(kind="mamba", **lp["mamba"])
            del w["norm"]
        else:
            w.update(kind="attention", **lp["attn"])
        layers.append(w)
    return {"embed": params["embed"], "layers": layers}


# --- counts ------------------------------------------------------------------


def mamba_params(s: Sizes) -> int:
    """One Mamba-2 mixer: in_proj, conv and its bias, A_log, D, dt_bias, the
    gated norm and out_proj."""
    nh = s.ssm_heads
    return (s.d * (2 * s.d_inner + 2 * s.ssm_state + nh) + s.conv_dim * (s.conv + 1)
            + 3 * nh + s.d_inner + s.d_inner * s.d)


def attn_params(s: Sizes) -> int:
    return s.d * (s.heads + 2 * s.kv_heads) * s.head_dim + s.heads * s.head_dim * s.d


def moe_params(s: Sizes) -> int:
    """Router, held experts, shared expert and the layer's two norms."""
    return (s.d * s.experts + s.held * 3 * s.d * s.ff + 3 * s.d * s.shared_ff + 2 * s.d)


def model_params(s: Sizes) -> int:
    """Weights of the model as published at this cut: the layers, the final
    norm and the embedding, which is also the head."""
    return (s.n_mamba * mamba_params(s) + s.n_attn * attn_params(s)
            + s.layers * moe_params(s) + s.d + s.vocab * s.d)


def param_count(s: Sizes) -> int:
    """Weights held here: the model's, and the head's copy of the embedding."""
    return model_params(s) + s.vocab * s.d


def expert_flops(s: Sizes) -> float:
    """The held experts' operations per token and layer, at the expected
    routed pairs."""
    return s.routed_pairs * 6 * s.d * s.ff


def _token_flops(s: Sizes) -> float:
    """Operations of one token through every layer, attention scores left out."""
    nh = s.ssm_heads
    mamba = (2 * s.d * (2 * s.d_inner + 2 * s.ssm_state + nh) + 2 * s.d_inner * s.d
             + 5 * nh * s.ssm_head_dim * s.ssm_state)
    moe = 2 * s.d * s.experts + 6 * s.d * s.shared_ff + expert_flops(s)
    return s.n_mamba * mamba + s.n_attn * 2 * attn_params(s) + s.layers * moe


def attn_flops(s: Sizes, first: int, n: int) -> int:
    """Scores and weighted values of ``n`` consecutive tokens at positions
    ``first .. first + n - 1``, on the attention layers."""
    ctx = n * first + n * (n + 1) // 2
    return 4 * s.n_attn * s.heads * s.head_dim * ctx


def prefill_flops(s: Sizes, n_prompt: int) -> float:
    """A prompt of ``n_prompt`` tokens and the logits of its last position."""
    return _token_flops(s) * n_prompt + attn_flops(s, 0, n_prompt) + 2 * s.d * s.vocab


def decode_flops(s: Sizes, pos: int) -> float:
    """One generated token fed back at position ``pos``, with its logits."""
    return _token_flops(s) + attn_flops(s, pos, 1) + 2 * s.d * s.vocab


def sequence_flops(s: Sizes, n_prompt: int, n_new: int) -> float:
    total = prefill_flops(s, n_prompt)
    for i in range(n_new - 1):
        total += decode_flops(s, n_prompt + i)
    return total


def step_bytes(s: Sizes) -> int:
    """Weights each decode step reads whatever its batch: every layer, the
    final norm and the head; of the embedding only a row per sequence, which
    is left out."""
    return s.dtype_bytes * model_params(s)


def prefill_bytes(s: Sizes) -> int:
    """Weights one prefill execution reads: those of a decode step."""
    return step_bytes(s)


def ssm_state_bytes(s: Sizes) -> int:
    """One sequence's Mamba state on one layer: the float32 SSM state and
    the conv state of the last ``K - 1`` inputs."""
    return (4 * s.ssm_heads * s.ssm_head_dim * s.ssm_state
            + s.dtype_bytes * (s.conv - 1) * s.conv_dim)


def state_step_bytes(s: Sizes) -> int:
    """What one sequence's decode step reads and writes of its Mamba state:
    every Mamba layer's, read once and written once."""
    return 2 * s.n_mamba * ssm_state_bytes(s)


def token_bytes(s: Sizes, pos: int) -> int:
    """What one sequence's decode step at ``pos`` reads and writes of its
    own state: the Mamba state (``state_step_bytes``) and the live K/V rows
    of the attention layers, its prompt's and what it has generated."""
    kv = s.dtype_bytes * 2 * s.n_attn * s.kv_heads * s.head_dim
    return state_step_bytes(s) + (pos + 1) * kv


def expert_weight_bytes(s: Sizes) -> int:
    """The held experts' weights of every layer, read once a step."""
    return s.dtype_bytes * s.layers * s.held * 3 * s.d * s.ff


# --- what the per-layer readers match in the device trace --------------------


def ssm_state_shape(s: Sizes, slots: int) -> Tuple[int, ...]:
    """The slot cache's SSM state of one layer, as the program holds it."""
    return (slots, s.ssm_heads, s.ssm_head_dim, s.ssm_state)


def expert_weight_shapes(s: Sizes) -> List[Tuple[int, ...]]:
    """The held experts' weights of one layer, as the program holds them."""
    return [(s.held, s.d, s.ff), (s.held, s.ff, s.d)]


# --- the CPU cut ------------------------------------------------------------

TINY = dict(hidden_size=128, num_attention_heads=4, num_key_value_heads=2,
            intermediate_size=32, shared_intermediate_size=64, vocab_size=512,
            mamba_n_heads=8, mamba_d_head=32, mamba_d_state=16, num_hidden_layers=6)


def tiny(config: Dict) -> Tuple[Dict, Dict]:
    """The cut for CPU tests: (config file at that size, ``ModelConfig``
    replace arguments).  Six layers, so that one attention layer follows
    five Mamba layers as in the published pattern; the router's 72 experts,
    the 9 held and the top-10 kept; every width cut."""
    s = sizes(config)
    conf = dict(config, **TINY)
    # std * sqrt(d) held fixed, so activations keep their full-size statistics
    conf["initializer_range"] = config["initializer_range"] * (
        s.d / TINY["hidden_size"]) ** 0.5
    conf["attention_multiplier"] = 1.0 / (TINY["hidden_size"] // TINY["num_attention_heads"])
    prog = config["program"]
    conf["program"] = dict(prog, replace=dict(prog["replace"], n_layers=6))
    replace = dict(d_model=128, n_heads=4, n_kv=2, head_dim=32, d_ff=32,
                   moe_dff=32, moe_shared_dff=64, vocab=512, ssm_head=32, ssm_state=16,
                   attn_scale=conf["attention_multiplier"])
    return conf, replace
