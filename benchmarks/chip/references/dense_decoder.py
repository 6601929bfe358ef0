"""Plain float32 reference of a dense decoder: Llama (Yi) and StableLM.

Straight from the published equations (HF ``LlamaForCausalLM`` and
``StableLmForCausalLM``), in ``jax.numpy`` and float32 with every matrix
product at ``Precision.HIGHEST``, one sequence and one layer at a time, with
no cache, no blocking and no kernels.  It imports nothing of the program:
the weights come from ``weights.py`` through ``families/dense_decoder.py``
``view``, and the sizes from the config file.

Per layer, pre-norm:

    h = norm(x);  q, k, v = h Wq, h Wk, h Wv
    q, k = rope(q), rope(k)                 rotary on the first
                                            partial_rotary_factor * hd dims,
                                            rotate_half pairing
    a = softmax(q k^T / sqrt(hd) + causal) v, query head i reading kv head
        i // (H / K)
    x = x + a Wo
    x = x + (silu(norm(x) Wgate) * norm(x) Wup) Wdown

then ``logits = norm(x) Whead``.  ``norm`` is RMSNorm (``x / sqrt(mean(x^2)
+ eps) * w``) or LayerNorm (``(x - mean) / sqrt(var + eps) * w + b``) as the
file says, with ``w = 1`` and ``b = 0``, the identity the weights start at.

Departures from the published model: none in the equations.  The config file
states what is run; stablelm-3b runs with rotary on the whole head, because
the program has no partial rotary, and the reference follows the file.

``quant=True`` is the control: the same forward with the two operands of
every matrix product rounded to float8 e4m3 (weights and keys per tensor,
activations per row, each scaled to the format's range), the precision below
the bfloat16 the configurations state.
"""
from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0  # largest finite float8_e4m3fn


def _fp8(x: jnp.ndarray, per_row: bool) -> jnp.ndarray:
    axis = -1 if per_row else None
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=per_row)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, quant: bool):
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if quant:
        x, w = _fp8(x, True), _fp8(w, False)
    return jnp.matmul(x, w, precision=HIGHEST)


def _norm(x, c: Dict):
    if c["norm"] == "rmsnorm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + c["rms_norm_eps"])
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + c["layer_norm_eps"])


def _rope(x, c: Dict):
    """x: (T, heads, hd); positions 0 .. T-1."""
    T, _, hd = x.shape
    rot = int(hd * c["partial_rotary_factor"])
    inv = 1.0 / (float(c["rope_theta"]) ** (np.arange(0, rot, 2, dtype=np.float64) / rot))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    emb = jnp.concatenate([ang, ang], -1)[:, None, :]
    xr, xp = x[..., :rot], x[..., rot:]
    half = jnp.concatenate([-xr[..., rot // 2:], xr[..., : rot // 2]], -1)
    return jnp.concatenate([xr * jnp.cos(emb) + half * jnp.sin(emb), xp], -1)


def _attention(q, k, v, quant: bool):
    """q: (T, H, hd), k/v: (T, K, hd) -> (T, H*hd), causal."""
    T, H, hd = q.shape
    rep = H // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    if quant:
        q, k, v = _fp8(q, True), _fp8(k, False), _fp8(v, False)
    s = jnp.einsum("thd,shd->hts", q, k, precision=HIGHEST) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    if quant:
        p = _fp8(p, True)
    o = jnp.einsum("hts,shd->thd", p, v, precision=HIGHEST)
    return o.reshape(T, H * hd)


@partial(jax.jit, static_argnames=("ckey", "quant"))
def _layer(x, w, ckey, quant):
    c = dict(ckey)
    T = x.shape[0]
    H, K = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c["hidden_size"] // H
    h = _norm(x, c)
    q = _rope(_mm(h, w["wq"], quant).reshape(T, H, hd), c)
    k = _rope(_mm(h, w["wk"], quant).reshape(T, K, hd), c)
    v = _mm(h, w["wv"], quant).reshape(T, K, hd)
    x = x + _mm(_attention(q, k, v, quant), w["wo"], quant)
    h = _norm(x, c)
    g = jax.nn.silu(_mm(h, w["w_gate"], quant)) * _mm(h, w["w_up"], quant)
    return x + _mm(g, w["w_down"], quant)


@partial(jax.jit, static_argnames=("ckey", "quant"))
def _embed(embed, tokens, ckey, quant):
    return embed[tokens].astype(jnp.float32)


@partial(jax.jit, static_argnames=("ckey", "quant"))
def _head(x, head, rows, ckey, quant):
    return _mm(_norm(x[rows], dict(ckey)), head, quant)


_KEYS = ("norm", "rms_norm_eps", "layer_norm_eps", "rope_theta", "partial_rotary_factor",
         "num_attention_heads", "num_key_value_heads", "hidden_size")


def config_key(c: Dict):
    return tuple((k, c[k]) for k in _KEYS if k in c)


def logits(config: Dict, view: Dict, tokens: np.ndarray, rows: np.ndarray,
           quant: bool = False) -> jax.Array:
    """float32 logits ``(len(rows), V)`` at positions ``rows`` of one sequence
    ``tokens (T,)``, computed layer by layer from ``view`` (the family's:
    ``embed``, ``layers``, ``head``)."""
    ckey = config_key(config)
    with jax.default_matmul_precision("highest"):
        x = _embed(view["embed"], jnp.asarray(tokens, jnp.int32), ckey, quant)
        for w in view["layers"]:
            x = _layer(x, w, ckey, quant)
        return _head(x, view["head"], jnp.asarray(rows, jnp.int32), ckey, quant)
