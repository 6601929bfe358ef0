"""The hybrid Mamba-2 / MoE block of granite-4.0-h-small, at a CPU size.

* Mamba-2's chunked scan (prefill) and its one-step update (decode) compute
  the same recurrence, across a chunk boundary and from a carried state;
* the serving expert layer drops no token: a request's logits are bit for
  bit the same alone and inside a full batch, where the capacity-bound
  training dispatch's are not;
* a slot of the continuous batcher's cache reused after eviction holds
  nothing of the sequence that held it before.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_config
from repro.models import decode_step, init_params, prefill
from repro.models import ssm

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def cfg():
    """Six layers (five Mamba-2, one attention), 72 experts of which 9 are
    held, top-10, and the shared expert, in float32."""
    c = smoke_config(get_config("granite-4.0-h-small"))
    return dataclasses.replace(c, moe_experts=72, moe_topk=10, moe_held=9, moe_held_first=9,
                               d_ff=32, moe_dff=32, remat=False)


def test_layer_plan_from_layer_types(cfg):
    full = get_config("granite-4.0-h-small")
    kinds = full.layer_kinds()
    assert len(kinds) == 40 and [i for i, k in enumerate(kinds) if k == "attn"] == [5, 15, 25, 35]
    cut = dataclasses.replace(full, n_layers=20)
    assert cut.layer_kinds() == kinds[:20] and cut.layer_types == full.layer_types[:20]
    assert cfg.layer_kinds() == ("mamba",) * 5 + ("attn",)
    with pytest.raises(ValueError, match="layer_types"):
        dataclasses.replace(cut, n_layers=21)
    with pytest.raises(ValueError, match="held"):
        dataclasses.replace(full, moe_held=9, moe_held_first=64)


def _sequential(p, x, cfg, state):
    """The recurrence one token at a time: Mamba-2's decode update."""
    outs = []
    for t in range(x.shape[1]):
        y, state = ssm.mamba_decode(p, x[:, t:t + 1], cfg, state)
        outs.append(y)
    return jnp.concatenate(outs, 1), state


@pytest.mark.parametrize("S", [20, ssm.CHUNK + 37])
def test_chunked_scan_matches_the_recurrence(cfg, S):
    """One chunk, and two with the boundary at 256; then a second stretch
    from the carried state (conv and SSM)."""
    p = init_params(cfg, KEY)["layers"][0]["mamba"]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, S + 9, cfg.d_model))
    st0 = ssm.mamba_init_state(cfg, 2, jnp.float32)
    assert st0["conv"].shape == (2, cfg.ssm_conv - 1, ssm.mamba_conv_dim(cfg))
    with jax.default_matmul_precision("highest"):
        y1, st1 = ssm.mamba_forward(p, x[:, :S], cfg, None)
        y2, st2 = ssm.mamba_forward(p, x[:, S:], cfg, st1)
        want, want_st = _sequential(p, x, cfg, st0)
    got = np.concatenate([np.asarray(y1), np.asarray(y2)], 1)
    scale = float(np.asarray(want).std())
    # float32 sums in another order: chunked cumulative decays against a
    # product of per-step decays
    np.testing.assert_allclose(got, np.asarray(want), rtol=0, atol=2e-5 * scale)
    np.testing.assert_allclose(np.asarray(st2["ssm"]), np.asarray(want_st["ssm"]), rtol=0,
                               atol=2e-5 * float(np.asarray(want_st["ssm"]).std()))
    np.testing.assert_array_equal(np.asarray(st2["conv"]), np.asarray(want_st["conv"]))


def test_conv_runs_over_x_b_and_c(cfg):
    """Mamba-2 convolves x, B and C together: a change to the conv weights of
    B's channels changes the output."""
    p = init_params(cfg, KEY)["layers"][0]["mamba"]
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 8, cfg.d_model))
    d_in = cfg.ssm_expand * cfg.d_model
    y0, _ = ssm.mamba_forward(p, x, cfg)
    q = dict(p, conv_w=p["conv_w"].at[:, d_in:d_in + cfg.ssm_state].multiply(2.0))
    y1, _ = ssm.mamba_forward(q, x, cfg)
    assert np.abs(np.asarray(y1) - np.asarray(y0)).max() > 1e-3


def _serve(params, cfg, rows, steps=4):
    """Prefill ``rows`` (B, S) then decode ``steps`` greedy tokens; every
    step's logits."""
    lg, cache = prefill(params, cfg, {"tokens": jnp.asarray(rows)}, cache_len=rows.shape[1] + steps)
    out = [lg[:, -1]]
    tok = jnp.argmax(lg[:, -1:], -1).astype(jnp.int32)
    for _ in range(steps):
        lg, cache = decode_step(params, cfg, cache, tok)
        out.append(lg[:, 0])
        tok = jnp.argmax(lg, -1).astype(jnp.int32)
    return np.stack([np.asarray(o) for o in out], 1)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_request_logits_bit_equal_alone_and_in_a_full_batch(cfg, dtype):
    c = dataclasses.replace(cfg, dtype=dtype)
    params = init_params(c, KEY)
    rng = np.random.default_rng(5)
    B, S = 8, 16
    full = rng.integers(1, c.vocab, (B, S)).astype(np.int32)
    alone = np.zeros_like(full)
    alone[3] = full[3]
    a, b = _serve(params, c, alone)[3], _serve(params, c, full)[3]
    np.testing.assert_array_equal(a, b)
    # the capacity-bound dispatch of training drops tokens past a capacity
    # that the rest of the batch uses up: the same request's logits move
    from repro.models import forward
    tight = dataclasses.replace(c, moe_held=0, moe_held_first=0, capacity_factor=0.5)
    p0 = init_params(tight, KEY)
    la, _, _ = forward(p0, tight, {"tokens": jnp.asarray(alone)}, train=True)
    lb, _, _ = forward(p0, tight, {"tokens": jnp.asarray(full)}, train=True)
    assert np.abs(np.asarray(la[3]) - np.asarray(lb[3])).max() > 0


def test_reused_slot_carries_nothing_of_its_last_sequence(cfg):
    """Two slots; three sequences, so the third is admitted into a slot
    that another sequence held and left.  Its tokens and log-probabilities,
    and the slot's Mamba, conv and K/V state after admission, are those of
    the same sequence admitted into a fresh batcher."""
    from repro.runtime.scheduler import ContinuousBatcher, SchedulerConfig

    params = init_params(cfg, KEY)
    rng = np.random.default_rng(6)
    seqs = [list(rng.integers(1, cfg.vocab, n)) for n in (12, 9, 14)]
    sched = SchedulerConfig(slots=2, prompt_cap=16, max_new=5, admit_cap=1)

    used = ContinuousBatcher(params, cfg, sched, logprobs=True)
    used.submit("a", seqs[0])
    used.submit("b", seqs[1])
    lps = {}
    while used.pending or used.n_active:
        used.step_begin()
        used.step_finish()
        lps.update(used.tick_logprobs)
        if "a" in used.done and not any(k[0] == "c" for k in lps):
            break
    slot = [i for i, s in enumerate(used.active) if s is None][0]
    dirty = jax.tree.map(lambda a: np.asarray(a[slot]), used.cache["layers"])
    used.submit("c", seqs[2])
    used._admit()
    got_state = jax.tree.map(lambda a: np.asarray(a[slot]), used.cache["layers"])

    fresh = ContinuousBatcher(params, cfg, sched, logprobs=True)
    fresh.submit("c", seqs[2])
    fresh._admit()
    fslot = [i for i, s in enumerate(fresh.active) if s is not None][0]
    want_state = jax.tree.map(lambda a: np.asarray(a[fslot]), fresh.cache["layers"])
    for g, w, d in zip(jax.tree.leaves(got_state), jax.tree.leaves(want_state),
                       jax.tree.leaves(dirty)):
        np.testing.assert_array_equal(g, w)
    # the slot did hold another sequence's state before
    assert any(np.abs(d.astype(np.float32)).max() > 0 for d in jax.tree.leaves(dirty))

    out_used, lp_used = _drain(used, "c")
    out_fresh, lp_fresh = _drain(fresh, "c")
    assert out_used == out_fresh
    assert lp_used == lp_fresh


def _drain(batcher, sid):
    lps = {}
    while batcher.pending or batcher.n_active:
        batcher.step_begin()
        batcher.step_finish()
        lps.update({k: v for k, v in batcher.tick_logprobs.items() if k[0] == sid})
    return batcher.done[sid], lps
