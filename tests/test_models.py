"""Per-arch smoke + decode-consistency + scan-equivalence + grad-sanity."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import all_archs, get_config, smoke_config
from repro.models import (
    decode_step, forward, init_params, loss_fn,     plan_period, prefill,
)

KEY = jax.random.PRNGKey(0)


def make_batch(cfg, B, S, train=True, key=KEY):
    batch = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab)}
    if train:
        batch["labels"] = batch["tokens"]
        batch["loss_mask"] = jnp.ones((B, S), jnp.float32)
    if cfg.family == "vlm":
        batch["vision"] = jax.random.normal(key, (B, cfg.vision_tokens, cfg.vision_dim))
    if cfg.family == "encdec":
        batch["audio"] = jax.random.normal(key, (B, cfg.enc_seq, cfg.d_model))
    return batch


@pytest.mark.parametrize("arch", all_archs())
def test_forward_shapes_no_nans(arch):
    cfg = smoke_config(get_config(arch))
    params = init_params(cfg, KEY)
    B, S = 2, 16
    batch = make_batch(cfg, B, S)
    logits, _, _ = forward(params, cfg, batch)
    assert logits.shape == (B, S, cfg.vocab)
    assert np.all(np.isfinite(np.asarray(logits, np.float32)))
    loss, metrics = loss_fn(params, cfg, batch)
    assert np.isfinite(float(loss))


@pytest.mark.parametrize("arch", all_archs())
def test_train_step_reduces_loss(arch):
    """A few AdamW steps on one small batch must reduce the loss.
    (AdamW, not raw SGD: the SSM families' exponential-gate parameters
    diverge under naive SGD at any useful step size.)"""
    from repro.optim import AdamWConfig, adamw_init, adamw_update
    cfg = smoke_config(get_config(arch))
    params = init_params(cfg, KEY)
    batch = make_batch(cfg, 2, 16)
    g_fn = jax.jit(jax.value_and_grad(lambda p: loss_fn(p, cfg, batch)[0]))
    opt_cfg = AdamWConfig(lr=3e-3, weight_decay=0.0)
    st = adamw_init(params)
    l0, _ = g_fn(params)
    for _ in range(8):
        l, g = g_fn(params)
        params, st, _ = adamw_update(g, st, params, opt_cfg, opt_cfg.lr)
    l1, _ = g_fn(params)
    assert float(l1) < float(l0), (float(l0), float(l1))


@pytest.mark.parametrize("arch", all_archs())
def test_decode_matches_forward(arch):
    cfg = dataclasses.replace(smoke_config(get_config(arch)), capacity_factor=16.0)
    params = init_params(cfg, KEY)
    B, S, P = 2, 12, 8
    batch = make_batch(cfg, B, S, train=False, key=jax.random.PRNGKey(1))
    full_logits, _, _ = forward(params, cfg, batch)
    b0 = dict(batch)
    b0["tokens"] = batch["tokens"][:, :P]
    logits_p, cache = prefill(params, cfg, b0, cache_len=S)
    errs = [np.abs(np.asarray(logits_p) - np.asarray(full_logits[:, :P])).max()]
    for t in range(P, S):
        lg, cache = decode_step(params, cfg, cache, batch["tokens"][:, t : t + 1])
        errs.append(np.abs(np.asarray(lg[:, 0]) - np.asarray(full_logits[:, t])).max())
    assert max(errs) < 2e-2, errs


@pytest.mark.parametrize("arch", ["gemma2-27b", "jamba-1.5-large-398b",
                                   "mixtral-8x22b", "xlstm-125m", "granite-34b"])
def test_scan_layers_equivalence(arch):
    cfg = smoke_config(get_config(arch))
    params = init_params(cfg, KEY)
    batch = make_batch(cfg, 2, 16, train=False)
    l0, _, _ = forward(params, cfg, batch)
    l1, _, _ = forward(params, dataclasses.replace(cfg, scan_layers=True), batch)
    np.testing.assert_allclose(
        np.asarray(l0, np.float32), np.asarray(l1, np.float32), atol=1e-4
    )


def test_plan_periods():
    assert plan_period(smoke_config(get_config("gemma2-27b"))) == 2
    assert plan_period(smoke_config(get_config("yi-6b"))) == 1
    assert plan_period(smoke_config(get_config("jamba-1.5-large-398b"))) == 8
    assert plan_period(smoke_config(get_config("xlstm-125m"))) == 2


def test_full_param_counts_match_published_class():
    """6ND bookkeeping: total params within 25% of the advertised size."""
    expect = {
        "gemma2-27b": 27e9, "granite-34b": 34e9, "yi-6b": 6e9,
        "mixtral-8x22b": 141e9, "jamba-1.5-large-398b": 398e9,
        "phi3.5-moe-42b-a6.6b": 42e9, "phi-3-vision-4.2b": 4.2e9,
        "xlstm-125m": 125e6,
    }
    for arch, want in expect.items():
        got = get_config(arch).param_counts()["total"]
        assert 0.7 < got / want < 1.35, (arch, got, want)


def test_moe_capacity_drops_and_balance():
    from repro.models.ffn import init_moe_ffn, moe_ffn
    cfg = dataclasses.replace(
        smoke_config(get_config("mixtral-8x22b")), capacity_factor=0.5
    )
    p = init_moe_ffn(KEY, cfg, jnp.float32)
    x = jax.random.normal(KEY, (2, 32, cfg.d_model))
    y, aux = moe_ffn(p, x, cfg)
    assert y.shape == x.shape
    assert float(aux["moe_dropped"]) > 0  # capacity 0.5 must drop
    cfg2 = dataclasses.replace(cfg, capacity_factor=8.0)
    y2, aux2 = moe_ffn(p, x, cfg2)
    assert float(aux2["moe_dropped"]) == 0.0


def _scatter_rows(cache, rows, slot):
    b_idx = jnp.arange(cache.shape[0], dtype=jnp.int32)
    return cache.at[b_idx, slot].set(rows, mode="drop")


@pytest.mark.parametrize("hd", [80, 128])
@pytest.mark.parametrize("case", ["in_range", "last_row", "past_end", "ring"])
def test_decode_row_write_matches_scatter(hd, case, monkeypatch):
    """The per-row cache write (head dim 80) and the batched scatter (128)
    give the batched scatter's cache bit for bit, and attn_decode's output
    does not change; a row past the cache's end leaves the cache as it was."""
    from repro.models import attention

    B, T = 4, 16
    pos = {
        "in_range": [3, 7, 0, 5],
        "last_row": [T - 1, 2, T - 1, 9],
        "past_end": [T, 4, T + 5, T - 1],
        "ring": [T + 3, 2 * T - 1, 5, 3 * T],
    }[case]
    pos = jnp.asarray(pos, jnp.int32)
    window = T if case == "ring" else None
    cfg = dataclasses.replace(smoke_config(get_config("stablelm-3b")),
                              head_dim=hd, dtype="bfloat16")
    params = init_params(cfg, KEY)
    p = params["layers"][0]["attn"]
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    shape = (B, T, cfg.n_kv, hd)
    cache = {"k": jax.random.normal(ks[0], shape, jnp.bfloat16),
             "v": jax.random.normal(ks[1], shape, jnp.bfloat16)}
    x = jax.random.normal(ks[2], (B, 1, cfg.d_model), jnp.bfloat16)

    slot = pos % T if window is not None else pos
    rows = jax.random.normal(ks[0], (B, cfg.n_kv, hd), jnp.bfloat16)
    got = attention.write_decode_rows(cache["k"], rows, slot)
    want = _scatter_rows(cache["k"], rows, slot)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    for b in np.flatnonzero(np.asarray(slot) >= T):
        np.testing.assert_array_equal(np.asarray(got[b]), np.asarray(cache["k"][b]))

    out, new = attention.attn_decode(p, x, cfg, cache, pos, window=window)
    monkeypatch.setattr(attention, "write_decode_rows", _scatter_rows)
    out_ref, new_ref = attention.attn_decode(p, x, cfg, cache, pos, window=window)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out_ref))
    for name in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(new[name]), np.asarray(new_ref[name]))
