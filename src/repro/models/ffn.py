"""Feed-forward layers: dense GLU variants and top-k MoE.

Two MoE dispatches.  Serving takes :func:`dropless_moe_ffn`: it routes over
every expert, computes the share of the experts held here for each token
routed to them, drops no token, and so gives each token the same output
whatever shares its batch.  Training takes :func:`moe_ffn`, below.

The training dispatch is *sort-based* (argsort tokens by expert, rank within
expert, capacity-bounded scatter into (E, C, d) buffers).  This is the
HGum-framed-List view of expert dispatch (DESIGN.md §5): per-expert token
groups are variable-length lists packed into fixed-capacity frames with
per-frame counts — the device analogue of the paper's §IV-C framing.

Tokens are processed in **groups** (default 8192): each group's dispatch is
independent with a group-local capacity.  This bounds the (E, C, d) frame
to a few hundred MB regardless of sequence length — a single global
dispatch at prefill_32k scale materializes replicated (E, 327k, d) buffers
that the SPMD partitioner cannot recover from (measured 60 GiB/instance,
3.7 TiB/device peak on mixtral; EXPERIMENTS.md §Perf).  Group-local
capacity also matches how production MoE systems enforce locality.
"""
from __future__ import annotations

import math
from dataclasses import replace
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .common import act_fn, dense_init
from ..configs.base import ModelConfig
from ..runtime.actshard import constrain as act_constrain

#: tokens per dispatch group (perf-iteration surface; see EXPERIMENTS.md)
TOKEN_GROUP = 8192


def init_dense_ffn(key, cfg: ModelConfig, dtype) -> Dict:
    d, ff = cfg.d_model, cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    glu = cfg.act in ("swiglu", "geglu")
    p = {
        "wi": dense_init(k1, (d, ff), dtype=dtype),
        "wo": dense_init(k2, (ff, d), dtype=dtype, scale=1.0 / (2 * cfg.n_layers) ** 0.5),
    }
    if glu:
        p["wg"] = dense_init(k3, (d, ff), dtype=dtype)
    return p


def dense_ffn(p: Dict, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    act = act_fn(cfg.act)
    h = x @ p["wi"]
    if "wg" in p:
        h = act(x @ p["wg"]) * h
    else:
        h = act(h)
    return h @ p["wo"]


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------


def init_moe_ffn(key, cfg: ModelConfig, dtype) -> Dict:
    """Router over all ``moe_experts``; weights of the experts held here
    (``moe_held``, all when 0); a shared expert where ``moe_shared_dff``."""
    d = cfg.d_model
    ff = cfg.moe_dff or cfg.d_ff
    E = cfg.moe_experts
    held = cfg.moe_held or E
    kr, k1, k2, k3 = jax.random.split(key, 4)
    glu = cfg.act in ("swiglu", "geglu")
    p = {
        "router": dense_init(kr, (d, E), dtype=jnp.float32),  # router in fp32
        "wi": dense_init(k1, (held, d, ff), in_axis=1, dtype=dtype),
        "wo": dense_init(k2, (held, ff, d), in_axis=1, dtype=dtype,
                         scale=1.0 / (2 * cfg.n_layers) ** 0.5),
    }
    if glu:
        p["wg"] = dense_init(k3, (held, d, ff), in_axis=1, dtype=dtype)
    if cfg.moe_shared_dff:
        p["shared"] = init_dense_ffn(jax.random.fold_in(key, 4),
                                     replace(cfg, d_ff=cfg.moe_shared_dff), dtype)
    return p


def routed_experts(p: Dict, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """The held experts' share of the routed output, dropping no token.

    The router scores all ``moe_experts``; each token keeps its top
    ``moe_topk`` logits and softmaxes over them.  The (token, choice) pairs
    that name a held expert are sorted by expert into one buffer of
    ``T * min(topk, held)`` rows, the most that can hit the held experts
    without dropping any, and each expert's rows go through its weights by
    ``jax.lax.ragged_dot``; pairs of absent experts sort past the groups and
    are not computed.  Each pair's output comes back to its (token, choice)
    place and the gates sum them in a fixed order, so a token's output
    depends only on the token.
    """
    B, S, d = x.shape
    T = B * S
    E, k = cfg.moe_experts, cfg.moe_topk
    held = cfg.moe_held or E
    act = act_fn(cfg.act)
    xf = x.reshape(T, d)

    logits = xf.astype(jnp.float32) @ p["router"]  # (T, E)
    top_l, top_i = jax.lax.top_k(logits, k)
    gates = jax.nn.softmax(top_l, axis=-1)  # over the chosen logits
    local = top_i - cfg.moe_held_first
    mine = ((local >= 0) & (local < held)).reshape(-1)  # (T*k,)
    key = jnp.where(mine, local.reshape(-1), held)
    order = jnp.argsort(key, stable=True)
    M = T * min(k, held)
    sizes = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
    rows = xf[order[:M] // k]  # (M, d), grouped by expert
    h = jax.lax.ragged_dot(rows, p["wi"], sizes)
    if "wg" in p:
        h = act(jax.lax.ragged_dot(rows, p["wg"], sizes)) * h
    else:
        h = act(h)
    out = jax.lax.ragged_dot(h.astype(x.dtype), p["wo"], sizes)  # (M, d)

    where = jnp.argsort(order)  # each pair's row in the sorted buffer
    pair = jnp.where(mine[:, None], out[jnp.minimum(where, M - 1)], 0).reshape(T, k, d)
    y = jnp.sum(gates[:, :, None] * pair.astype(jnp.float32), axis=1)
    return y.astype(x.dtype).reshape(B, S, d)


def dropless_moe_ffn(p: Dict, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """The serving MoE layer: the held experts' routed share, with the
    shared expert beside it."""
    y = routed_experts(p, x, cfg)
    if "shared" in p:
        y = y + dense_ffn(p["shared"], x, cfg)
    return y


def moe_capacity(cfg: ModelConfig, n_tokens: int) -> int:
    E, k = cfg.moe_experts, cfg.moe_topk
    cap = int(math.ceil(cfg.capacity_factor * n_tokens * k / E))
    return max(8, -(-cap // 8) * 8)  # round up to 8 for tiling


def _moe_group(p: Dict, xf: jnp.ndarray, cfg: ModelConfig, C: int, act):
    """Dispatch+experts+combine for one token group.  xf: (G, d)."""
    G, d = xf.shape
    E, topk = cfg.moe_experts, cfg.moe_topk

    logits = xf.astype(jnp.float32) @ p["router"]  # (G,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, topk)  # (G,k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # (token, slot) pairs, sorted by expert (stable keeps token order)
    pair_expert = gate_idx.reshape(-1)
    pair_token = jnp.repeat(jnp.arange(G, dtype=jnp.int32), topk)
    pair_gate = gate_vals.reshape(-1)
    order = jnp.argsort(pair_expert, stable=True)
    se, st, sg = pair_expert[order], pair_token[order], pair_gate[order]
    counts = jnp.bincount(se, length=E)
    starts = jnp.cumsum(counts) - counts
    rank = jnp.arange(G * topk, dtype=jnp.int32) - starts[se]
    keep = rank < C

    # pack into per-expert frames (HGum Lists with count headers)
    dest = jnp.where(keep, se * C + rank, E * C)
    buf = jnp.zeros((E * C, d), xf.dtype).at[dest].set(xf[st], mode="drop")
    buf = buf.reshape(E, C, d)

    h = jnp.einsum("ecd,edf->ecf", buf, p["wi"])
    if "wg" in p:
        h = act(jnp.einsum("ecd,edf->ecf", buf, p["wg"])) * h
    else:
        h = act(h)
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["wo"]).reshape(E * C, d)

    src = jnp.where(keep, se * C + rank, 0)
    pair_out = out_buf[src] * (sg * keep).astype(xf.dtype)[:, None]
    yf = jnp.zeros((G, d), xf.dtype).at[st].add(pair_out)

    frac_tokens = counts.astype(jnp.float32) / jnp.maximum(G * topk, 1)
    balance = cfg.moe_experts * jnp.sum(frac_tokens * probs.mean(axis=0))
    return yf, balance, 1.0 - keep.mean()


def moe_ffn(
    p: Dict, x: jnp.ndarray, cfg: ModelConfig, capacity: Optional[int] = None,
    token_group: int = TOKEN_GROUP,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Top-k capacity-bounded MoE over token groups (see module docstring),
    for training: every expert held, tokens past a capacity dropped."""
    if cfg.moe_held and cfg.moe_held != cfg.moe_experts:
        raise ValueError("the capacity-bound dispatch holds every expert")
    B, S, d = x.shape
    T = B * S
    act = act_fn(cfg.act)
    xf = x.reshape(T, d)

    if T <= token_group:
        C = capacity or moe_capacity(cfg, T)
        yf, balance, dropped = _moe_group(p, xf, cfg, C, act)
        return yf.reshape(B, S, d), {
            "moe_balance_loss": balance, "moe_dropped": dropped,
        }

    n_groups = -(-T // token_group)
    pad = n_groups * token_group - T
    xg = jnp.pad(xf, ((0, pad), (0, 0))).reshape(n_groups, token_group, d)
    C = capacity or moe_capacity(cfg, token_group)

    def body(_, xg_i):
        yf, balance, dropped = _moe_group(p, xg_i, cfg, C, act)
        return None, (yf, balance, dropped)

    _, (yg, bal, drp) = jax.lax.scan(body, None, xg)
    yf = yg.reshape(n_groups * token_group, d)[:T]
    yf = act_constrain(yf, "tokens_flat")
    return yf.reshape(B, S, d), {
        "moe_balance_loss": bal.mean(),
        "moe_dropped": drp.mean(),
    }
