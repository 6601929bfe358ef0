"""Unified model: init / forward / prefill / decode for all 10 architectures.

One parameter pytree + three entry points:

* ``forward(params, cfg, batch)``            — full-sequence logits (``train=True``
  for the loss, with the training MoE dispatch).
* ``prefill(params, cfg, batch, cache_len)`` — forward + primed KV/SSM cache.
* ``decode_step(params, cfg, cache, batch)`` — one token, updated cache.

Layer plan comes from ``cfg.layer_kinds() × cfg.ffn_kinds()``; families:
``lm`` (decoder-only), ``encdec`` (whisper: encoder + cross-attn decoder),
``vlm`` (phi-3-vision: patch-embedding stream prepended to token stream).

``scan_layers=True`` groups layers into the minimal repeating period and
scans over stacked parameters (small HLO for the multi-pod dry-run);
``False`` unrolls (exact per-layer cost attribution).  Decode always unrolls.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..runtime.actshard import constrain as act_constrain
from . import attention as attn_mod
from . import ffn as ffn_mod
from . import ssm as ssm_mod
from .common import (
    apply_norm,
    cross_entropy,
    dtype_of,
    embed_init,
    init_norm,
    softcap,
)

PyTree = Any


# ---------------------------------------------------------------------------
# Layer plan
# ---------------------------------------------------------------------------


def layer_plan(cfg: ModelConfig) -> List[Tuple[str, str]]:
    return list(zip(cfg.layer_kinds(), cfg.ffn_kinds()))


def plan_period(cfg: ModelConfig) -> int:
    """Smallest period p (dividing n_layers) such that the layer plan — and
    the local/global attention alternation — repeats with period p."""
    plan = [
        (s, f, cfg.attn_is_local(i))
        for i, (s, f) in enumerate(layer_plan(cfg))
    ]
    n = len(plan)
    for p in range(1, n + 1):
        if n % p == 0 and all(plan[i] == plan[i % p] for i in range(n)):
            return p
    return n


# ---------------------------------------------------------------------------
# Per-layer init / forward
# ---------------------------------------------------------------------------


def init_layer(key, cfg: ModelConfig, seq_kind: str, ffn_kind: str, dtype) -> Dict:
    ks = jax.random.split(key, 4)
    p: Dict = {"ln1": init_norm(cfg.norm, cfg.d_model, dtype)}
    if seq_kind == "attn":
        p["attn"] = attn_mod.init_attn(ks[0], cfg, dtype)
    elif seq_kind == "mamba":
        p["mamba"] = ssm_mod.init_mamba(ks[0], cfg, dtype)
    elif seq_kind == "mlstm":
        p["mlstm"] = ssm_mod.init_mlstm(ks[0], cfg, dtype)
    elif seq_kind == "slstm":
        p["slstm"] = ssm_mod.init_slstm(ks[0], cfg, dtype)
    else:
        raise ValueError(seq_kind)
    if cfg.sandwich_norm:
        p["ln1_post"] = init_norm(cfg.norm, cfg.d_model, dtype)
    if ffn_kind != "none":
        p["ln2"] = init_norm(cfg.norm, cfg.d_model, dtype)
        if ffn_kind == "dense":
            p["ffn"] = ffn_mod.init_dense_ffn(ks[1], cfg, dtype)
        else:
            p["moe"] = ffn_mod.init_moe_ffn(ks[1], cfg, dtype)
        if cfg.sandwich_norm:
            p["ln2_post"] = init_norm(cfg.norm, cfg.d_model, dtype)
    return p


def layer_forward(
    p: Dict,
    x: jnp.ndarray,
    cfg: ModelConfig,
    layer_idx: int,
    seq_kind: str,
    ffn_kind: str,
    *,
    mode: str,  # "full" | "decode"
    cache: Optional[Dict] = None,
    pos: Optional[jnp.ndarray] = None,  # (B,) decode positions
    positions: Optional[jnp.ndarray] = None,  # (B,S) full-seq positions
    segment_ids: Optional[jnp.ndarray] = None,
    q_offset: int | jnp.ndarray = 0,
    train: bool = False,
) -> Tuple[jnp.ndarray, Optional[Dict], Dict]:
    """Returns (x, new_cache, aux).  An MoE layer takes the capacity-bound
    dispatch when ``train`` and the dropless one otherwise."""
    aux: Dict = {}
    h = apply_norm(cfg.norm, p["ln1"], x, cfg.norm_eps)
    new_cache: Dict = {}
    window = cfg.window if cfg.attn_is_local(layer_idx) else None
    if seq_kind == "attn":
        if mode == "decode":
            out, kv = attn_mod.attn_decode(p["attn"], h, cfg, cache, pos, window=window)
            new_cache = kv
        else:
            out, (k, v) = attn_mod.attn_forward(
                p["attn"], h, cfg, window=window,
                positions=positions, segment_ids=segment_ids, q_offset=q_offset,
            )
            new_cache = {"k": k, "v": v}
    elif seq_kind == "mamba":
        fn = ssm_mod.mamba_decode if mode == "decode" else ssm_mod.mamba_forward
        out, new_cache = fn(p["mamba"], h, cfg, cache)
    elif seq_kind == "mlstm":
        fn = ssm_mod.mlstm_decode if mode == "decode" else ssm_mod.mlstm_forward
        out, new_cache = fn(p["mlstm"], h, cfg, cache)
    elif seq_kind == "slstm":
        fn = ssm_mod.slstm_decode if mode == "decode" else ssm_mod.slstm_forward
        out, new_cache = fn(p["slstm"], h, cfg, cache)
    else:
        raise ValueError(seq_kind)
    if cfg.sandwich_norm:
        out = apply_norm(cfg.norm, p["ln1_post"], out, cfg.norm_eps)
    x = x + _residual(out, cfg)

    if ffn_kind != "none":
        h = apply_norm(cfg.norm, p["ln2"], x, cfg.norm_eps)
        if ffn_kind == "dense":
            out = ffn_mod.dense_ffn(p["ffn"], h, cfg)
        elif train:
            out, aux = ffn_mod.moe_ffn(p["moe"], h, cfg)
            if "shared" in p["moe"]:
                out = out + ffn_mod.dense_ffn(p["moe"]["shared"], h, cfg)
        else:
            out = ffn_mod.dropless_moe_ffn(p["moe"], h, cfg)
        if cfg.sandwich_norm:
            out = apply_norm(cfg.norm, p["ln2_post"], out, cfg.norm_eps)
        x = x + _residual(out, cfg)
    return x, new_cache, aux


def _residual(out: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """A residual branch scaled by ``residual_mult`` (granite), where set."""
    if cfg.residual_mult != 1.0:
        out = out * jnp.asarray(cfg.residual_mult, out.dtype)
    return out


# ---------------------------------------------------------------------------
# Whole-model init
# ---------------------------------------------------------------------------


def init_params(cfg: ModelConfig, key) -> PyTree:
    dtype = dtype_of(cfg.dtype)
    keys = jax.random.split(key, cfg.n_layers + 8)
    params: Dict = {
        "embed": embed_init(keys[0], (cfg.padded_vocab, cfg.d_model), dtype),
        "final_norm": init_norm(cfg.norm, cfg.d_model, dtype),
        "layers": [
            init_layer(keys[2 + i], cfg, s, f, dtype)
            for i, (s, f) in enumerate(layer_plan(cfg))
        ],
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = embed_init(keys[1], (cfg.d_model, cfg.padded_vocab), dtype)
    if cfg.family == "vlm":
        params["vision_proj"] = embed_init(
            keys[-1], (cfg.vision_dim, cfg.d_model), dtype
        )
    if cfg.family == "encdec":
        ekeys = jax.random.split(keys[-2], cfg.enc_layers + 1)
        params["encoder"] = {
            "layers": [
                init_layer(ekeys[i], cfg, "attn", "dense", dtype)
                for i in range(cfg.enc_layers)
            ],
            "final_norm": init_norm(cfg.norm, cfg.d_model, dtype),
        }
        ckeys = jax.random.split(keys[-3], cfg.n_layers)
        params["cross"] = [
            {
                "ln": init_norm(cfg.norm, cfg.d_model, dtype),
                "attn": attn_mod.init_cross_attn(ckeys[i], cfg, dtype),
            }
            for i in range(cfg.n_layers)
        ]
    return params


def param_count(params: PyTree) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# Embedding front-ends (modality stubs live in input_specs, DESIGN.md §5)
# ---------------------------------------------------------------------------


def _embed_tokens(params, cfg: ModelConfig, tokens: jnp.ndarray) -> jnp.ndarray:
    x = params["embed"][tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model**0.5, x.dtype)
    if cfg.embed_mult != 1.0:
        x = x * jnp.asarray(cfg.embed_mult, x.dtype)
    return x


def _front_end(params, cfg: ModelConfig, batch: Dict) -> Tuple[jnp.ndarray, int]:
    """Token (+modality) embedding.  Returns (x, n_prefix_positions)."""
    x = _embed_tokens(params, cfg, batch["tokens"])
    if cfg.family == "vlm" and "vision" in batch:
        vis = batch["vision"].astype(x.dtype) @ params["vision_proj"]
        x = jnp.concatenate([vis, x], axis=1)
        return x, vis.shape[1]
    return x, 0


def _unembed(params, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    logits = x @ head
    logits = act_constrain(logits, "logits")
    if cfg.padded_vocab != cfg.vocab:  # mask the pad rows (see padded_vocab)
        pad = jnp.arange(cfg.padded_vocab, dtype=jnp.int32) >= cfg.vocab
        logits = jnp.where(pad, jnp.asarray(-1e30, logits.dtype), logits)
    logits = logits.astype(jnp.float32)
    if cfg.logits_div != 1.0:
        logits = logits / cfg.logits_div
    return softcap(logits, cfg.final_softcap)


def _sinusoidal(S: int, d: int, offset=0) -> jnp.ndarray:
    pos = offset + jnp.arange(S, dtype=jnp.float32)[:, None]
    dim = jnp.arange(0, d, 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10_000.0, dim / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)[None]


# ---------------------------------------------------------------------------
# Encoder (whisper)
# ---------------------------------------------------------------------------


def encode(params, cfg: ModelConfig, audio: jnp.ndarray) -> jnp.ndarray:
    """audio: (B, enc_seq, d_model) — precomputed conv-frontend embeddings."""
    x = audio.astype(dtype_of(cfg.dtype)) + _sinusoidal(
        audio.shape[1], cfg.d_model
    ).astype(dtype_of(cfg.dtype))
    enc = params["encoder"]
    for i, lp in enumerate(enc["layers"]):
        h = apply_norm(cfg.norm, lp["ln1"], x, cfg.norm_eps)
        out, _ = attn_mod.attn_forward(lp["attn"], h, cfg, causal=False)
        x = x + out
        h = apply_norm(cfg.norm, lp["ln2"], x, cfg.norm_eps)
        x = x + ffn_mod.dense_ffn(lp["ffn"], h, cfg)
    return apply_norm(cfg.norm, enc["final_norm"], x, cfg.norm_eps)


# ---------------------------------------------------------------------------
# Full-sequence forward (train / prefill body)
# ---------------------------------------------------------------------------


def forward(
    params: PyTree,
    cfg: ModelConfig,
    batch: Dict,
    *,
    want_cache: bool = False,
    cache_len: Optional[int] = None,
    last_only: bool = False,
    train: bool = False,
) -> Tuple[jnp.ndarray, Optional[Dict], Dict]:
    """Returns (logits, cache | None, aux).  ``batch["tokens"]``: (B,S).
    ``train`` selects the training MoE dispatch (``layer_forward``).

    ``last_only`` computes logits for the final position only — prefill
    serving needs just the next token, and (B, S, V) logits at 32k
    context are the single largest prefill buffer (measured 10+ GiB/device
    on granite prefill_32k)."""
    x, n_prefix = _front_end(params, cfg, batch)
    B, S, _ = x.shape
    positions = batch.get("positions")
    segment_ids = batch.get("segment_ids")
    if segment_ids is not None and n_prefix:
        pre = jnp.ones((B, n_prefix), segment_ids.dtype) * segment_ids[:, :1]
        segment_ids = jnp.concatenate([pre, segment_ids], axis=1)
    if positions is not None and n_prefix:
        # vision prefix occupies positions [0, n_prefix); text shifts up
        pre = jnp.tile(jnp.arange(n_prefix, dtype=positions.dtype)[None], (B, 1))
        positions = jnp.concatenate([pre, positions + n_prefix], axis=1)
    if not cfg.use_rope and cfg.family == "encdec":
        x = x + _sinusoidal(S, cfg.d_model).astype(x.dtype)

    enc_kv = None
    if cfg.family == "encdec":
        enc_out = encode(params, cfg, batch["audio"])
        enc_kv = [attn_mod.cross_kv(cp["attn"], enc_out, cfg) for cp in params["cross"]]

    plan = layer_plan(cfg)
    aux_acc: Dict[str, jnp.ndarray] = {}
    caches: List[Dict] = []

    x = act_constrain(x, "residual")

    def run_layer(x, i, lp):
        s, f = plan[i]
        x, kv, aux = layer_forward(
            lp, x, cfg, i, s, f,
            mode="full", positions=positions, segment_ids=segment_ids, train=train,
        )
        if cfg.family == "encdec":
            cp = params["cross"][i]
            h = apply_norm(cfg.norm, cp["ln"], x, cfg.norm_eps)
            x = x + attn_mod.cross_attn_forward(cp["attn"], h, enc_kv[i], cfg)
        return act_constrain(x, "residual"), kv, aux

    if cfg.scan_layers and not want_cache and cfg.family == "lm":
        x, aux_acc = _forward_scanned(params, cfg, x, positions, segment_ids, train)
    else:
        for i, lp in enumerate(params["layers"]):
            fn = run_layer
            if cfg.remat:
                fn = jax.checkpoint(
                    run_layer, policy=_remat_policy(cfg), static_argnums=(1,),
                )
            x, kv, aux = fn(x, i, lp)
            for k, v in aux.items():
                aux_acc[k] = aux_acc.get(k, 0.0) + v / cfg.n_layers
            if want_cache:
                caches.append(kv)

    x = apply_norm(cfg.norm, params["final_norm"], x, cfg.norm_eps)
    if n_prefix:
        x = x[:, n_prefix:]
    if last_only:
        x = x[:, -1:]
    logits = _unembed(params, cfg, x)

    cache = None
    if want_cache:
        total = batch["tokens"].shape[1] + n_prefix  # vision prefix holds slots
        want_len = (cache_len + n_prefix) if cache_len is not None else total
        cache = _grow_cache(cfg, caches, batch, total, want_len, enc_kv)
    return logits, cache, aux_acc


def _forward_scanned(params, cfg, x, positions, segment_ids, train):
    """Scan over stacked layer periods (see module docstring)."""
    p = plan_period(cfg)
    n_periods = cfg.n_layers // p
    plan = layer_plan(cfg)
    stacked = stack_layers(params["layers"], p)

    def body(x, period_params):
        for j in range(p):
            s, f = plan[j]
            x, _, aux = layer_forward(
                period_params[f"pos{j}"], x, cfg, j, s, f,
                mode="full", positions=positions, segment_ids=segment_ids, train=train,
            )
            x = act_constrain(x, "residual")
        return x, aux.get("moe_balance_loss", jnp.zeros(()))

    body_fn = jax.checkpoint(body, policy=_remat_policy(cfg)) if cfg.remat else body
    x, bal = jax.lax.scan(body_fn, x, stacked, length=n_periods)
    return x, {"moe_balance_loss": bal.mean()} if bal.size else {}


def _remat_policy(cfg: ModelConfig):
    """Activation-checkpoint policy (perf-iteration surface).

    "nothing": recompute everything in backward (min memory, max recompute).
    "dots": save dot/matmul outputs — trades HBM for a large cut in
    recomputed FLOPs and re-read traffic (EXPERIMENTS.md §Perf).
    """
    if cfg.remat_policy == "dots":
        return jax.checkpoint_policies.checkpoint_dots
    return jax.checkpoint_policies.nothing_saveable


def stack_layers(layers: List[Dict], period: int) -> Dict:
    """[L0..Ln] -> {"pos j": stacked over periods} for scan-over-layers."""
    groups = {
        f"pos{j}": [layers[i] for i in range(j, len(layers), period)]
        for j in range(period)
    }
    return {
        k: jax.tree.map(lambda *xs: jnp.stack(xs), *v) for k, v in groups.items()
    }


def _grow_cache(cfg, caches, batch, total, cache_len, enc_kv):
    """Pad prefill KV to `cache_len` slots (decode appends in place).
    `total` = positions already consumed (text + modality prefix).

    Sliding-window layers keep only the last `window` keys (a ring cache;
    alignment holds because window divides the sequence length) — storing
    the full 32k KV for SWA layers costs 7.5 GiB/device on mixtral."""
    out_layers = []
    for i, ((s, f), kv) in enumerate(zip(layer_plan(cfg), caches)):
        if s == "attn":
            k, v = kv["k"], kv["v"]
            want = cache_len
            if cfg.window is not None and cfg.attn_is_local(i):
                want = min(want, cfg.window)
                if k.shape[1] > want:
                    if k.shape[1] % want != 0:
                        raise ValueError(
                            f"SWA ring alignment needs window|seq, got "
                            f"{want} vs {k.shape[1]}"
                        )
                    k, v = k[:, -want:], v[:, -want:]
            if want > k.shape[1]:
                pad = ((0, 0), (0, want - k.shape[1]), (0, 0), (0, 0))
                k, v = jnp.pad(k, pad), jnp.pad(v, pad)
            out_layers.append({"k": k, "v": v})
        else:
            out_layers.append(kv)
    cache = {
        "layers": out_layers,
        "pos": jnp.full((batch["tokens"].shape[0],), total, jnp.int32),
    }
    if enc_kv is not None:
        cache["enc_kv"] = enc_kv
    return cache


# ---------------------------------------------------------------------------
# Cache init / decode
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, cache_len: int) -> Dict:
    dtype = dtype_of(cfg.dtype)
    layers = []
    for i, (s, f) in enumerate(layer_plan(cfg)):
        if s == "attn":
            T = cache_len
            if cfg.window is not None and cfg.attn_is_local(i):
                T = min(T, cfg.window)
            layers.append({
                "k": jnp.zeros((batch, T, cfg.n_kv, cfg.hd), dtype),
                "v": jnp.zeros((batch, T, cfg.n_kv, cfg.hd), dtype),
            })
        elif s == "mamba":
            layers.append(ssm_mod.mamba_init_state(cfg, batch, dtype))
        elif s == "mlstm":
            layers.append(ssm_mod.mlstm_init_state(cfg, batch))
        elif s == "slstm":
            layers.append(ssm_mod.slstm_init_state(cfg, batch))
    cache = {"layers": layers, "pos": jnp.zeros((batch,), jnp.int32)}
    if cfg.family == "encdec":
        cache["enc_kv"] = [
            (
                jnp.zeros((batch, cfg.enc_seq, cfg.n_kv, cfg.hd), dtype),
                jnp.zeros((batch, cfg.enc_seq, cfg.n_kv, cfg.hd), dtype),
            )
            for _ in range(cfg.n_layers)
        ]
    return cache


def decode_step(
    params: PyTree, cfg: ModelConfig, cache: Dict, tokens: jnp.ndarray
) -> Tuple[jnp.ndarray, Dict]:
    """One decode step.  tokens: (B,1).  Returns (logits (B,1,V), cache)."""
    pos = cache["pos"]
    x = _embed_tokens(params, cfg, tokens)
    if not cfg.use_rope and cfg.family == "encdec":
        # per-example position offset of the sinusoid
        x = x + jax.vmap(lambda p: _sinusoidal(1, cfg.d_model, offset=p)[0])(pos).astype(x.dtype)

    plan = layer_plan(cfg)
    new_layers = []
    for i, lp in enumerate(params["layers"]):
        s, f = plan[i]
        window = cfg.window if cfg.attn_is_local(i) else None
        x, kv, _ = layer_forward(
            lp, x, cfg, i, s, f, mode="decode", cache=cache["layers"][i], pos=pos
        )
        if cfg.family == "encdec":
            cp = params["cross"][i]
            h = apply_norm(cfg.norm, cp["ln"], x, cfg.norm_eps)
            x = x + attn_mod.cross_attn_forward(cp["attn"], h, cache["enc_kv"][i], cfg)
        new_layers.append(kv)
    x = apply_norm(cfg.norm, params["final_norm"], x, cfg.norm_eps)
    logits = _unembed(params, cfg, x)
    new_cache = dict(cache)
    new_cache["layers"] = new_layers
    new_cache["pos"] = pos + 1
    return logits, new_cache


def prefill(
    params: PyTree, cfg: ModelConfig, batch: Dict, cache_len: Optional[int] = None,
    last_only: bool = False,
) -> Tuple[jnp.ndarray, Dict]:
    logits, cache, _ = forward(
        params, cfg, batch, want_cache=True, cache_len=cache_len,
        last_only=last_only,
    )
    return logits, cache


# ---------------------------------------------------------------------------
# Loss / train step
# ---------------------------------------------------------------------------


def loss_fn(
    params: PyTree, cfg: ModelConfig, batch: Dict
) -> Tuple[jnp.ndarray, Dict]:
    logits, _, aux = forward(params, cfg, batch, train=True)
    loss, metrics = cross_entropy(
        logits, batch["labels"], batch.get("loss_mask"), z_loss=1e-4
    )
    if "moe_balance_loss" in aux:
        loss = loss + 0.01 * aux["moe_balance_loss"]
        metrics["moe_balance_loss"] = aux["moe_balance_loss"]
    metrics["loss"] = loss
    return loss, metrics
