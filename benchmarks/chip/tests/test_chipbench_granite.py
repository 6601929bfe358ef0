"""granite-4.0-h-small through the program's serving path against its plain
reference (``references/granite_hybrid.py``), at the family's CPU cut.

* prefill, then decode through the slot cache, gives the reference's logits;
* the eight shares of a layer's experts, each held by its own chip, with
  the shared expert counted once, add up to the uncut layer;
* the family runs through the harness to a ``correct`` verdict, its counts
  are those of the published shapes, and the two readers of its mechanisms
  find their ops by shape in a trace's op text.
"""
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _tiny import tiny_cell

from benchmarks.chip import check, harness, peaks, spec, trace_reduce, weights
from benchmarks.chip.families import granite_hybrid as fam
from benchmarks.chip.metrics import _shapes
from benchmarks.chip.references import granite_hybrid as ref

V5E = peaks.peak("TPU v5 lite")
SEED = 2**33 + 41


@pytest.fixture(scope="module")
def tiny():
    """(cell, program config in float32, weights) at the CPU cut."""
    cell, cfg = tiny_cell("granite4h.chat")
    cfg = dataclasses.replace(cfg, dtype="float32", remat=False)
    params = weights.make_params(cfg, cell.config["initializer_range"], SEED,
                                 cell.family.init_kind)
    return cell, cfg, params


def _scale(a):
    return float(np.asarray(a).std())


def test_prefill_then_decode_through_slots_matches_reference(tiny):
    """The serving path, as ``ContinuousBatcher`` runs it: a prefill of
    ``admit`` rows scattered into a slot of a wider slot cache, then decode
    steps of every slot, reading the slot's row."""
    from repro.models import decode_step, init_cache, prefill
    from repro.runtime.scheduler import _scatter_rows

    cell, cfg, params = tiny
    rng = np.random.default_rng(3)
    seq = rng.integers(1, cfg.vocab, 40)
    P, slots, slot = 24, 4, 2
    prompts = np.zeros((2, P), np.int32)
    prompts[0], prompts[1] = seq[:P], rng.integers(1, cfg.vocab, P)
    with jax.default_matmul_precision("highest"):
        lg, new = prefill(params, cfg, {"tokens": jnp.asarray(prompts)}, cache_len=len(seq))
        cache = init_cache(cfg, slots, len(seq))
        cache, _ = _scatter_rows(cache, jnp.zeros((slots, 1), jnp.int32), new,
                                 jnp.zeros((2, 1), jnp.int32), jnp.asarray([slot, 0], jnp.int32))
        got = [np.asarray(lg[0, P - 1])]
        for t in range(P, len(seq) - 1):
            toks = np.zeros((slots, 1), np.int32)
            toks[slot, 0] = seq[t]
            out, cache = decode_step(params, cfg, cache, jnp.asarray(toks))
            got.append(np.asarray(out[slot, 0]))
    want = np.asarray(ref.logits(cell.config, cell.family.view(params), seq,
                                 np.arange(P - 1, len(seq) - 1)))
    # Both in float32: the program's chunked scan and the reference's
    # sequential recurrence sum in another order, and the program's router
    # and gates round once more; that is float32 rounding, 1e-4 of the
    # logits' spread (it reads about 4e-6).  A bfloat16 program misses by
    # about 0.1 of the spread (the next test).
    np.testing.assert_allclose(np.stack(got), want, rtol=0, atol=1e-4 * _scale(want))


def test_bfloat16_program_misses_the_float32_tolerance(tiny):
    """The tolerance above is tight enough to tell the precision the
    configuration states from float32."""
    from repro.models.model import forward

    cell, cfg, params = tiny
    toks = np.random.default_rng(4).integers(1, cfg.vocab, 24)
    bf = dataclasses.replace(cfg, dtype="bfloat16")
    p16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16) if a.dtype == jnp.float32
                       and a.ndim > 1 and a.shape[-1] != cfg.moe_experts else a, params)
    got, _, _ = forward(p16, bf, {"tokens": jnp.asarray(toks[None], jnp.int32)})
    want = np.asarray(ref.logits(cell.config, cell.family.view(params), toks, np.arange(24)))
    assert np.abs(np.asarray(got[0]) - want).max() > 1e-4 * _scale(want)


def test_eight_shares_add_up_to_the_uncut_layer(tiny):
    """Each of eight chips holds 9 of the 72 experts (ids 9r .. 9r + 8) and
    computes their share of the routed output; the shares and the shared
    expert, counted once, make the layer the reference computes with all
    72 experts held."""
    from repro.models.ffn import dense_ffn, init_moe_ffn, routed_experts

    cell, cfg, _ = tiny
    full = dataclasses.replace(cfg, moe_held=0)
    p = init_moe_ffn(jax.random.PRNGKey(7), full, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 12, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        total = dense_ffn(p["shared"], x, cfg)
        for r in range(8):
            share = dataclasses.replace(cfg, moe_held=9, moe_held_first=9 * r)
            pr = dict(p, **{k: p[k][9 * r: 9 * r + 9] for k in ("wi", "wg", "wo")})
            total = total + routed_experts(pr, x, share)
    w = {"router": p["router"], "w_gate": p["wg"], "w_up": p["wi"], "w_down": p["wo"],
         "s_gate": p["shared"]["wg"], "s_up": p["shared"]["wi"], "s_down": p["shared"]["wo"]}
    c = dict(cell.config, first_expert_held=0)
    with jax.default_matmul_precision("highest"):
        want = jax.vmap(lambda xi: ref._moe(xi, w, c, False))(x)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=0,
                               atol=1e-5 * _scale(want))  # float32 sums in another order


def test_family_runs_to_correct():
    cell, cfg = tiny_cell("granite4h.chat")
    run, verdict, _ = harness.measure(cell, SEED, 0.0, False, time.perf_counter(),
                                      require_chip=False, cfg=cfg, log=lambda s: None,
                                      compile_cache=False)
    assert verdict.correct, verdict.numbers
    assert run.calls and run.failed == 0 and run.compiles_in_window == 0
    # the served tokens are not one token repeated: the check compares something
    served = []
    for burst, res in run.calls:
        check.check_wires(burst, res.responses, cell.mix["max_new"], cfg.vocab, served)
    assert len({t for s in served for t in s.tokens}) > len(served)
    params = weights.make_params(cfg, cell.config["initializer_range"], SEED,
                                 cell.family.init_kind)
    seqs = check.sample(served, 3, 5)
    program = check.logit_gaps(cell, params, seqs)
    control = check.logit_gaps(cell, params, seqs, control=True)
    assert program < 0.2 < control  # the float8 control reads far above the program


def test_init_kind_as_published():
    cell, cfg = tiny_cell("granite4h.chat")
    params = weights.make_params(cfg, cell.config["initializer_range"], SEED,
                                 cell.family.init_kind)
    m = params["layers"][0]["mamba"]
    A = np.exp(np.asarray(m["A_log"]))
    dt = np.log1p(np.exp(np.asarray(m["dt_bias"])))  # softplus
    assert 1.0 <= A.min() and A.max() <= 16.0
    assert 1e-3 * 0.999 <= dt.min() and dt.max() <= 0.1 * 1.001
    np.testing.assert_array_equal(np.asarray(m["D"]), 1.0)
    assert np.abs(np.asarray(m["conv_w"], np.float32)).max() <= 0.5
    for k in ("norm",):
        np.testing.assert_array_equal(np.asarray(m[k], np.float32), 0.0)
    np.testing.assert_array_equal(np.asarray(params["layers"][0]["ln1"]["scale"], np.float32), 0.0)
    e = np.asarray(params["embed"], np.float32)
    assert e.std() == pytest.approx(cell.config["initializer_range"] / 12, rel=0.05)
    # tied as published: the program's head is the embedding's transpose, bit for bit
    np.testing.assert_array_equal(np.asarray(params["lm_head"]), np.asarray(params["embed"]).T)


def test_counts_of_the_published_shapes():
    cell = spec.load_cell("granite4h.chat")
    s, f = cell.sizes, cell.family
    assert (s.layers, s.n_mamba, s.n_attn, s.held, s.experts, s.topk) == (20, 18, 2, 9, 72, 10)
    assert f.mamba_params(s) == 102_286_976
    assert f.attn_params(s) == 41_943_040
    assert f.moe_params(s) == 104_112_128
    assert f.model_params(s) == 4_418_340_096
    assert f.param_count(s) == 4_418_340_096 + 100352 * 4096  # the head's copy
    assert f.step_bytes(s) == 2 * 4_418_340_096
    assert s.routed_pairs == 1.25
    # 32 slots of float32 SSM state on 18 layers: 2.42 GB, each read and written
    assert 32 * s.n_mamba * 4 * 128 * 64 * 128 == 2_415_919_104
    assert f.state_step_bytes(s) == 2 * 18 * (4 * 128 * 64 * 128 + 2 * 3 * 8448)
    kv_row = 2 * 2 * 2 * 8 * 128  # bf16, K and V, two attention layers
    assert f.token_bytes(s, 99) - f.token_bytes(s, 98) == kv_row
    assert f.ssm_state_shape(s, 32) == (32, 128, 64, 128)
    assert f.expert_weight_shapes(s) == [(9, 4096, 768), (9, 768, 4096)]
    # the program's config is the file's: spec refuses one that departs
    cfg = spec.program_config(cell.config)
    assert (cfg.n_layers, cfg.moe_held, cfg.moe_experts) == (20, 9, 72)
    bad = json.loads(json.dumps(cell.config))
    bad["program"]["replace"]["moe_held"] = 8
    with pytest.raises(ValueError, match="departs"):
        spec.program_config(bad)


# --- the readers of the two mechanisms, on a hand-made trace -----------------

def _ev(*rows):
    ev = trace_reduce.Events()
    for name, start, dur in rows:
        ev.add(name, start, dur)
    return ev


STATE = ("%fusion.7 = (f32[32,128,64,128]{3,2,1,0:T(8,128)}, bf16[32,8192]{1,0}) "
         "fusion(f32[32,128,64,128]{3,2,1,0} %p.1, bf16[32,8192]{1,0} %p.2), kind=kLoop")
STATE_T = ("%copy.3 = f32[32,64,128,128]{3,2,1,0} copy(f32[32,128,64,128]{3,2,1,0} %p.1)")
EXPERT = ("%custom-call.2 = bf16[288,768]{1,0} custom-call(bf16[288,4096]{1,0} %g, "
          "bf16[9,4096,768]{2,1,0:T(8,128)(2,1)} %p.9, s32[9]{0} %s), custom_call_target=\"x\"")
EXPERT_DOWN = ("%custom-call.5 = bf16[288,4096]{1,0} custom-call(bf16[288,768]{1,0} %h, "
               "bf16[9,768,4096]{2,1,0} %p.11, s32[9]{0} %s)")
OTHER = "%fusion.9 = bf16[32,4096]{1,0} fusion(bf16[32,4096]{1,0} %x), kind=kLoop"


def _trace():
    """Two serve_step executions and a prefill; ops in ns."""
    modules = {0: _ev(("jit_prefill_step(1)", 0, 1000), ("jit_serve_step(2)", 2000, 1000),
                      ("jit_serve_step(2)", 4000, 1000))}
    ops = {0: _ev((STATE, 100, 300), (EXPERT, 500, 100),  # inside the prefill: not read
                  (STATE, 2100, 200), (STATE_T, 2300, 50), (EXPERT, 2400, 70),
                  (EXPERT_DOWN, 2500, 30), (OTHER, 2600, 100),
                  (STATE, 4100, 200), (EXPERT, 4400, 70), (EXPERT_DOWN, 4500, 30))}
    return trace_reduce.Trace(ops, modules, trace_reduce.Events())


@dataclasses.dataclass
class _Run(harness.Run):
    seqs: tuple = ()

    def sequences(self):
        return iter(self.seqs)


def _read(run, name):
    return harness._metric_reader(name)(run)


def test_op_text_parsed_to_shapes():
    res, ops = _shapes.parse(STATE)
    assert res == [("f32", (32, 128, 64, 128)), ("bf16", (32, 8192))]
    assert ops == [("f32", (32, 128, 64, 128)), ("bf16", (32, 8192))]
    res, ops = _shapes.parse(EXPERT)
    assert res == [("bf16", (288, 768))]
    assert ops[1] == ("bf16", (9, 4096, 768)) and len(ops) == 3


def test_mechanism_readers_find_their_ops_by_shape():
    cell = spec.load_cell("granite4h.chat")
    run = _Run(cell, V5E, setup_s=1.0, t0=0.0, t1=1.0, trace=_trace(),
               seqs=((200, 128), (150, 2)))
    s = cell.sizes
    steps = 127 + 1
    t_state = (200 + 50 + 200) / 1e9  # both results, any order of dims
    assert _read(run, "ssm_state_roofline") == pytest.approx(
        100.0 * steps * fam.state_step_bytes(s) / V5E["hbm_bytes_per_s"] / t_state)
    t_exp = (70 + 30 + 70 + 30) / 1e9
    least = max(steps * 20 * fam.expert_flops(s) / V5E["bf16_flops_per_s"],
                2 * fam.expert_weight_bytes(s) / V5E["hbm_bytes_per_s"])
    assert _read(run, "expert_ffn_roofline") == pytest.approx(100.0 * least / t_exp)
    # a dense cell has neither mechanism: nothing to read
    dense = dataclasses.replace(run, cell=spec.load_cell("yi6b.chat"))
    assert _read(dense, "ssm_state_roofline") is None
    assert _read(dense, "expert_ffn_roofline") is None
    # a trace with no op of the shapes reads nothing
    bare = dataclasses.replace(run, trace=trace_reduce.Trace(
        {0: _ev((OTHER, 2100, 10))}, _trace().modules, trace_reduce.Events()))
    assert _read(bare, "ssm_state_roofline") is None
    assert _read(bare, "expert_ffn_roofline") is None
