"""Plain float32 reference of granite-4.0-h-small (HF ``granitemoehybrid``).

Straight from the published equations, in ``jax.numpy`` and float32 with
every matrix product at ``Precision.HIGHEST``, one sequence and one layer at
a time, with no cache, no chunking, no sorting and no kernels.  It imports
nothing of the program: the weights come from ``weights.py`` through
``families/granite_hybrid.py`` ``view``, and the sizes from the config file.

    x = embed[tokens] * embedding_multiplier
    per layer, pre-norm (RMSNorm, rms_norm_eps, weight 1):
        x = x + residual_multiplier * mixer(norm(x))
        x = x + residual_multiplier * (moe(norm(x)) + shared(norm(x)))
    logits = norm(x) embed^T / logits_scaling

A Mamba-2 mixer (one group), as a sequential recurrence over time:

    z, xBC, dt = h W_in                   split d_in, d_in + 2 N, heads
    xBC = silu(causal depthwise conv of width K over xBC, with bias)
    x, B, C = xBC                         split d_in, N, N
    dt = softplus(dt + dt_bias);  A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T       per head, S_0 = 0
    y_t = S_t C_t + D x_t
    out = (norm(y * silu(z))) W_out       the gated norm at rms_norm_eps

An attention mixer: GQA with no positional encoding (NoPE), scores
``q k^T * attention_multiplier`` under a causal mask, then ``W_o``.

The MoE: router logits over all ``E`` experts of the deployment (the
``published`` count), the top ``num_experts_per_tok`` kept and softmaxed
over themselves; each expert held here (ids ``first_expert_held`` on, the
file's ``num_local_experts`` of them) computed for every token as
``(silu(h W_gate) * h W_up) W_down`` and weighted by the token's gate for
it, 0 where it did not pick it; the shared expert the same SwiGLU, for
every token.

Departures from the published model: the experts not held here are left
out, here as in the program, since they lie on other chips of the stated
deployment (``model-configs`` section 4); the depth is the file's
``num_hidden_layers``.  Nothing else.

``quant=True`` is the control: the same forward with the two operands of
every matrix product rounded to float8 e4m3 (weights and keys per tensor,
activations per row, each scaled to the format's range), the precision
below the bfloat16 the configuration states.  The recurrence, the conv and
the gates stay float32.
"""
from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from .dense_decoder import HIGHEST, _fp8, _mm


def _norm(x, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def _mamba(h, w, c: Dict, quant: bool):
    T = h.shape[0]
    nh, P, N = c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"]
    d_in = nh * P
    zxbcdt = _mm(h, w["in_proj"], quant)
    z, xbc, dt = zxbcdt[:, :d_in], zxbcdt[:, d_in:2 * d_in + 2 * N], zxbcdt[:, 2 * d_in + 2 * N:]
    cw = w["conv_w"].astype(jnp.float32)  # (K, conv_dim): tap i reads input t - (K-1) + i
    K = cw.shape[0]
    pad = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), jnp.float32), xbc], 0)
    conv = sum(pad[i:i + T] * cw[i] for i in range(K)) + w["conv_b"].astype(jnp.float32)
    xbc = jax.nn.silu(conv)
    xs, B, C = xbc[:, :d_in].reshape(T, nh, P), xbc[:, d_in:d_in + N], xbc[:, d_in + N:]
    dt = jax.nn.softplus(dt + w["dt_bias"].astype(jnp.float32))  # (T, nh)
    A = -jnp.exp(w["A_log"].astype(jnp.float32))

    def step(S, inp):
        x_t, B_t, C_t, dt_t = inp
        S = (jnp.exp(dt_t * A)[:, None, None] * S
             + (dt_t[:, None] * x_t)[:, :, None] * B_t[None, None, :])
        return S, jnp.einsum("hpn,n->hp", S, C_t, precision=HIGHEST)

    _, y = jax.lax.scan(step, jnp.zeros((nh, P, N), jnp.float32), (xs, B, C, dt))
    y = (y + w["D"].astype(jnp.float32)[None, :, None] * xs).reshape(T, d_in)
    g = _norm(y * jax.nn.silu(z), c["rms_norm_eps"])
    return _mm(g, w["out_proj"], quant)


def _attention(h, w, c: Dict, quant: bool):
    T = h.shape[0]
    H, Kv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c["hidden_size"] // H
    q = _mm(h, w["wq"], quant).reshape(T, H, hd)
    k = jnp.repeat(_mm(h, w["wk"], quant).reshape(T, Kv, hd), H // Kv, axis=1)
    v = jnp.repeat(_mm(h, w["wv"], quant).reshape(T, Kv, hd), H // Kv, axis=1)
    if quant:
        q, k, v = _fp8(q, True), _fp8(k, False), _fp8(v, False)
    s = jnp.einsum("thd,shd->hts", q, k, precision=HIGHEST) * c["attention_multiplier"]
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    if quant:
        p = _fp8(p, True)
    o = jnp.einsum("hts,shd->thd", p, v, precision=HIGHEST).reshape(T, H * hd)
    return _mm(o, w["wo"], quant)


def _swiglu(h, w_gate, w_up, w_down, quant: bool):
    return _mm(jax.nn.silu(_mm(h, w_gate, quant)) * _mm(h, w_up, quant), w_down, quant)


def _moe(h, w, c: Dict, quant: bool):
    k, first = c["num_experts_per_tok"], c["first_expert_held"]
    top_l, top_i = jax.lax.top_k(_mm(h, w["router"], quant), k)
    gates = jax.nn.softmax(top_l, axis=-1)  # (T, k), over the chosen logits
    out = _swiglu(h, w["s_gate"], w["s_up"], w["s_down"], quant)
    for j in range(w["w_gate"].shape[0]):
        g = jnp.sum(jnp.where(top_i == first + j, gates, 0.0), -1)  # (T,)
        out = out + g[:, None] * _swiglu(h, w["w_gate"][j], w["w_up"][j], w["w_down"][j], quant)
    return out


@partial(jax.jit, static_argnames=("ckey", "kind", "quant"))
def _layer(x, w, ckey, kind, quant):
    c = dict(ckey)
    eps, r = c["rms_norm_eps"], c["residual_multiplier"]
    mixer = _mamba if kind == "mamba" else _attention
    x = x + r * mixer(_norm(x, eps), w, c, quant)
    return x + r * _moe(_norm(x, eps), w, c, quant)


@partial(jax.jit, static_argnames=("ckey",))
def _embed(embed, tokens, ckey):
    return embed[tokens].astype(jnp.float32) * dict(ckey)["embedding_multiplier"]


@partial(jax.jit, static_argnames=("ckey", "quant"))
def _head(x, embed, rows, ckey, quant):
    c = dict(ckey)
    return _mm(_norm(x[rows], c["rms_norm_eps"]), embed.T, quant) / c["logits_scaling"]


_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads", "attention_multiplier",
         "mamba_n_heads", "mamba_d_head", "mamba_d_state", "rms_norm_eps",
         "embedding_multiplier", "residual_multiplier", "logits_scaling",
         "num_experts_per_tok", "first_expert_held")


def config_key(c: Dict):
    return tuple((k, c[k]) for k in _KEYS)


def logits(config: Dict, view: Dict, tokens: np.ndarray, rows: np.ndarray,
           quant: bool = False) -> jax.Array:
    """float32 logits ``(len(rows), V)`` at positions ``rows`` of one sequence
    ``tokens (T,)``, computed layer by layer from ``view`` (the family's:
    ``embed`` and ``layers``, each with its ``kind``)."""
    ckey = config_key(config)
    with jax.default_matmul_precision("highest"):
        x = _embed(view["embed"], jnp.asarray(tokens, jnp.int32), ckey)
        for w in view["layers"]:
            w = dict(w)
            kind = w.pop("kind")
            x = _layer(x, w, ckey, kind, quant)
        return _head(x, view["embed"], jnp.asarray(rows, jnp.int32), ckey, quant)
