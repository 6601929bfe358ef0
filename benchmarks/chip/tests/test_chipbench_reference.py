"""The plain float32 reference against the program's own forward, at a small
size on the CPU, for a Yi-style (GQA, RMSNorm) and a StableLM-style (MHA,
LayerNorm) config; and its partial rotary against a direct rotation."""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _tiny import tiny_cell

from benchmarks.chip import weights


@pytest.mark.parametrize("name", ["yi6b.chat", "stablelm3b.longctx"])
def test_reference_matches_program_forward(name):
    from repro.models.model import forward

    cell, cfg = tiny_cell(name)
    cfg = dataclasses.replace(cfg, dtype="float32", remat=False)
    params = weights.make_params(cfg, 0.02, 5, cell.family.init_kind)
    toks = np.random.default_rng(1).integers(1, cfg.vocab, 24)
    with jax.default_matmul_precision("highest"):
        prog, _, _ = forward(params, cfg, {"tokens": jnp.asarray(toks[None], jnp.int32)})
    from benchmarks.chip.references import dense_decoder as ref

    got = ref.logits(cell.config, cell.family.view(params), toks, np.arange(24))
    np.testing.assert_allclose(np.asarray(got), np.asarray(prog[0]), rtol=2e-4, atol=2e-4)


def test_control_departs_from_reference():
    cell, cfg = tiny_cell("yi6b.chat")
    params = weights.make_params(cfg, 0.02, 5, cell.family.init_kind)
    from benchmarks.chip.references import dense_decoder as ref

    toks = np.random.default_rng(2).integers(1, cfg.vocab, 24)
    args = (cell.config, cell.family.view(params), toks, np.arange(24))
    hi, lo = np.asarray(ref.logits(*args)), np.asarray(ref.logits(*args, quant=True))
    err = np.abs(hi - lo).max() / hi.std()
    assert 1e-3 < err < 0.5


def test_partial_rotary():
    from benchmarks.chip.references import dense_decoder as ref

    x = np.random.default_rng(3).normal(size=(6, 2, 8)).astype(np.float32)
    c = {"rope_theta": 100.0, "partial_rotary_factor": 0.5}
    y = np.asarray(ref._rope(jnp.asarray(x), c))
    np.testing.assert_array_equal(y[..., 4:], x[..., 4:])  # the rest passes through
    for t in range(6):  # rotate_half pairing on the first 4 dims: (0, 2) and (1, 3)
        for i in range(2):
            a = t / 100.0 ** (2 * i / 4)
            cs, sn = np.cos(a), np.sin(a)
            x1, x2 = x[t, :, i], x[t, :, i + 2]
            np.testing.assert_allclose(y[t, :, i], x1 * cs - x2 * sn, rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(y[t, :, i + 2], x2 * cs + x1 * sn, rtol=1e-5, atol=1e-6)
