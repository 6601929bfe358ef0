"""Attention layers: GQA/MQA/MHA with RoPE, sliding windows, softcaps.

Init + three entry points per layer:
  * ``attn_forward``      — full-sequence (train / prefill), returns new KV.
  * ``attn_decode``       — one token against a KV cache.
  * ``cross_attn_forward``— encoder-decoder cross attention.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .common import (
    apply_rope,
    decode_attention,
    dense_init,
    flash_attention,
)
from ..configs.base import ModelConfig


def init_attn(key, cfg: ModelConfig, dtype) -> Dict:
    d, hd, nq, nkv = cfg.d_model, cfg.hd, cfg.n_heads, cfg.n_kv
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": dense_init(kq, (d, nq * hd), dtype=dtype),
        "wk": dense_init(kk, (d, nkv * hd), dtype=dtype),
        "wv": dense_init(kv, (d, nkv * hd), dtype=dtype),
        "wo": dense_init(ko, (nq * hd, d), dtype=dtype, scale=1.0 / (2 * cfg.n_layers) ** 0.5),
    }


def _split_heads(x, n, hd):
    return x.reshape(*x.shape[:-1], n, hd)


def _qkv(p: Dict, x: jnp.ndarray, cfg: ModelConfig):
    nq, nkv, hd = cfg.n_heads, cfg.n_kv, cfg.hd
    q = _split_heads(x @ p["wq"], nq, hd)  # (B,S,nq,hd)
    k = _split_heads(x @ p["wk"], nkv, hd)
    v = _split_heads(x @ p["wv"], nkv, hd)
    # group q heads by kv head: (B,S,K,G,D)
    B, S = x.shape[:2]
    q = q.reshape(B, S, nkv, nq // nkv, hd)
    return q, k, v


def attn_forward(
    p: Dict,
    x: jnp.ndarray,  # (B,S,d)
    cfg: ModelConfig,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    positions: Optional[jnp.ndarray] = None,  # (B,S)
    segment_ids: Optional[jnp.ndarray] = None,  # (B,S)
    q_offset: int | jnp.ndarray = 0,
) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
    """Full-sequence attention; returns (out, (k, v)) for cache priming."""
    B, S, _ = x.shape
    q, k, v = _qkv(p, x, cfg)
    if cfg.use_rope:
        if positions is None:
            positions = q_offset + jnp.arange(S, dtype=jnp.int32)[None, :]
        q = apply_rope(q.reshape(B, S, cfg.n_heads, cfg.hd), positions, cfg.rope_theta)
        q = q.reshape(B, S, cfg.n_kv, cfg.n_heads // cfg.n_kv, cfg.hd)
        k = apply_rope(k, positions, cfg.rope_theta)
    out = flash_attention(
        q, k, v,
        causal=causal,
        window=window,
        logit_cap=cfg.attn_softcap,
        q_offset=q_offset,
        segment_q=segment_ids,
        segment_k=segment_ids,
        scale=cfg.attn_scale,
        p_bf16=cfg.attn_p_bf16,
    )
    out = out.reshape(B, S, cfg.n_heads * cfg.hd) @ p["wo"]
    return out, (k, v)


def write_decode_rows(cache: jnp.ndarray, rows: jnp.ndarray,
                      slot: jnp.ndarray) -> jnp.ndarray:
    """cache[b, slot[b]] = rows[b] for every b of a (B,T,K,D) cache; a row
    with slot >= T is dropped.

    The write depends on the head dim because the TPU stores the cache in
    the layout that fills its 128-lane tiles.  Where D is a multiple of 128
    the cache is stored D-minor and the batch-indexed scatter writes it in
    place.  Otherwise (D = 80) T takes the lanes, the scatter is emitted only
    D-minor, and the compiler relayouts the whole cache around it: two
    full-cache copies per array per layer, every step.  There each row is
    written by its own dynamic_update_slice, which stays in the stored
    layout.  Its batch index is static: a vmapped per-row DUS would defeat
    SPMD batch partitioning of the cache and replicate it.
    """
    B, T = cache.shape[:2]
    if cache.shape[-1] % 128 == 0:
        b_idx = jnp.arange(B, dtype=jnp.int32)
        return cache.at[b_idx, slot].set(rows, mode="drop")
    # dynamic_update_slice clamps its start to T - 1 where the scatter drops:
    # such a row writes back what row T - 1 held.  A static slice reads it;
    # a dynamic read at a static b would all-gather a batch-sharded cache.
    keep = (slot >= T).reshape(B, 1, 1, 1)
    start = jnp.minimum(slot, T - 1)
    rows = jnp.where(keep, cache[:, T - 1:], rows.astype(cache.dtype)[:, None])
    for b in range(B):
        cache = jax.lax.dynamic_update_slice(cache, rows[b:b + 1], (b, start[b], 0, 0))
    return cache


def attn_decode(
    p: Dict,
    x: jnp.ndarray,  # (B,1,d)
    cfg: ModelConfig,
    cache: Dict,  # {"k": (B,T,K,D), "v": (B,T,K,D)}
    pos: jnp.ndarray,  # (B,) current absolute position (== kv_len)
    *,
    window: Optional[int] = None,
) -> Tuple[jnp.ndarray, Dict]:
    """One-token decode; appends to the cache at `pos` (ring for windows)."""
    B = x.shape[0]
    T = cache["k"].shape[1]
    q, k, v = _qkv(p, x, cfg)
    if cfg.use_rope:
        q = apply_rope(q.reshape(B, 1, cfg.n_heads, cfg.hd), pos[:, None], cfg.rope_theta)
        q = q.reshape(B, 1, cfg.n_kv, cfg.n_heads // cfg.n_kv, cfg.hd)
        k = apply_rope(k, pos[:, None], cfg.rope_theta)
    slot = pos % T if window is not None else pos  # ring buffer for SWA
    kc = write_decode_rows(cache["k"], k[:, 0], slot)
    vc = write_decode_rows(cache["v"], v[:, 0], slot)
    kv_len = jnp.minimum(pos + 1, T) if window is not None else pos + 1
    out = decode_attention(q, kc, vc, kv_len, logit_cap=cfg.attn_softcap,
                           scale=cfg.attn_scale)
    out = out.reshape(B, 1, cfg.n_heads * cfg.hd) @ p["wo"]
    return out, {"k": kc, "v": vc}


# ---------------------------------------------------------------------------
# Cross attention (encoder-decoder)
# ---------------------------------------------------------------------------


def init_cross_attn(key, cfg: ModelConfig, dtype) -> Dict:
    return init_attn(key, cfg, dtype)


def cross_attn_forward(
    p: Dict,
    x: jnp.ndarray,  # (B,S,d) decoder states
    enc_kv: Tuple[jnp.ndarray, jnp.ndarray],  # precomputed (k, v): (B,T,K,D)
    cfg: ModelConfig,
) -> jnp.ndarray:
    B, S, _ = x.shape
    nq, nkv, hd = cfg.n_heads, cfg.n_kv, cfg.hd
    q = _split_heads(x @ p["wq"], nq, hd).reshape(B, S, nkv, nq // nkv, hd)
    k, v = enc_kv
    out = flash_attention(q, k, v, causal=False, logit_cap=cfg.attn_softcap,
                          scale=cfg.attn_scale)
    return out.reshape(B, S, nq * hd) @ p["wo"]


def cross_kv(p: Dict, enc_out: jnp.ndarray, cfg: ModelConfig):
    """Precompute cross-attention K/V from encoder output (cache once)."""
    k = _split_heads(enc_out @ p["wk"], cfg.n_kv, cfg.hd)
    v = _split_heads(enc_out @ p["wv"], cfg.n_kv, cfg.hd)
    return k, v
