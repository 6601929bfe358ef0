"""A run with the timed path broken underneath must come out not correct.

Each test drives the rest of a run (weights, warm-up, window, check) at a
tiny size on the CPU, past the harness's look for a chip, with one fault
planted in the program the window drives:

* a decode step that returns its state (the KV cache) unchanged;
* half of the batch left out: the decode step's second half of the slots
  keeps the token it was fed;
* a token altered where it is produced: the third decode step's tokens + 1;
* on the streaming plane, the exchange between chips left out: routed
  ticks are never delivered.

The float8 control of the reference must also fail the cells' limit where
the program passes it.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import time

import jax
import jax.numpy as jnp
import pytest

from _tiny import tiny_cell

from benchmarks.chip import control, harness


def _measure(cell, cfg):
    run, verdict, _ = harness.measure(cell, 2**33 + 17, 0.0, False, time.perf_counter(),
                                      require_chip=False, cfg=cfg, log=lambda s: None,
                                      compile_cache=False)
    return run, verdict


def _plant(monkeypatch, fault):
    from repro.launch import steps

    orig = steps.cached_serve_steps

    def patched(cfg, cache_len, logprobs=False):
        prefill, _ = orig(cfg, cache_len, logprobs)
        step = steps.make_serve_step(cfg)

        def broken(params, cache, tok):
            nxt, new = step(params, cache, tok)
            if fault == "state_unchanged":
                return nxt, cache
            if fault == "half_batch":
                keep = jnp.arange(tok.shape[0]) < tok.shape[0] // 2
                return jnp.where(keep[:, None], nxt, tok), new
            # token altered: the step that feeds position pad_to + 2
            third = cache["pos"][:, None] == cache_len - cfg_max_new[0] + 2
            return jnp.where(third, (nxt + 1) % cfg.vocab, nxt), new

        return prefill, jax.jit(broken)

    cfg_max_new = [0]
    monkeypatch.setattr(steps, "cached_serve_steps", patched)
    return cfg_max_new


def test_clean_run_is_correct():
    cell, cfg = tiny_cell("yi6b.chat")
    run, verdict = _measure(cell, cfg)
    assert verdict.correct, verdict.numbers
    assert run.calls and run.failed == 0


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "token_altered"])
def test_fault_is_not_correct(monkeypatch, fault):
    cell, cfg = tiny_cell("yi6b.chat")
    _plant(monkeypatch, fault)[0] = cell.mix["max_new"]
    _, verdict = _measure(cell, cfg)
    assert not verdict.correct, verdict.numbers
    gap = dict((n, v) for n, v, _ in verdict.numbers)["logit_gap"]
    assert gap > cell.limits["logit_gap"]


def test_exchange_left_out_is_not_correct(monkeypatch):
    from repro.fabric.mailbox import Fabric

    cell, cfg = tiny_cell("yi6b.stream4")
    clean_run, clean = _measure(cell, cfg)
    assert clean.correct, clean.numbers
    orig = Fabric.exchange_async

    def dropped(self):
        sent = orig(self)
        self._inflight = None  # the routed tick never arrives
        return sent

    monkeypatch.setattr(Fabric, "exchange_async", dropped)
    run, verdict = _measure(cell, cfg)
    assert not verdict.correct and run.failed > 0


def test_control_fails_where_program_passes():
    cell, cfg = tiny_cell("yi6b.chat")
    rows = list(control.readings(cell, [1, 2, 3], {1, 2, 3}, require_chip=False, cfg=cfg))
    limit = cell.limits["logit_gap"]
    assert all(r["bad_wires"] == 0 for r in rows)
    assert all(r["program"] <= limit for r in rows), rows
    assert all(r["control"] > 3 * max(x["program"] for x in rows) for r in rows), rows
