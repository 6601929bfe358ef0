"""Architecture lives in the family module alone.

* A family that the dense decoder's fields refuse (``_nope_family.py``:
  yi-6b with no positional encoding, and its reference
  ``_nope_reference.py``) is found by its name, from a temporary
  ``BENCHMARK.json`` and config directory, runs through the harness at a
  tiny size to a ``correct`` verdict, and its counts feed the metric
  readers.
* The dense decoder's weights are bit for bit those of the weight maker
  before families existed (a frozen copy below), and the three readers of
  counts give exactly the values of the formulas they had.
"""
import dataclasses
import json
import shutil
import sys
import time
from types import SimpleNamespace

import _nope_family
import _nope_reference
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _tiny import cut, tiny_cell

from benchmarks.chip import check, harness, peaks, spec, weights

FAMILY_API = ("sizes", "program_fields", "init_kind", "view", "prefill_flops",
              "decode_flops", "sequence_flops", "step_bytes", "token_bytes",
              "prefill_bytes", "tiny")
V5E = peaks.peak("TPU v5 lite")


class _Trace:
    """Program device times: name -> (seconds, executions)."""

    def __init__(self, **programs):
        self.programs = programs

    def program(self, name):
        return self.programs.get(name, (0.0, 0))


def _read(run, name):
    return harness._metric_reader(name)(run)


@pytest.mark.parametrize("name", sorted(p.stem for p in (spec.HERE / "families").glob("*.py")
                                        if p.stem != "__init__"))
def test_family_has_the_interface(name):
    fam = spec.family_module({"reference": name})
    assert all(callable(getattr(fam, a, None)) for a in FAMILY_API)
    assert callable(spec.reference_module({"reference": name}).logits)


@pytest.fixture
def nope(tmp_path, monkeypatch):
    """The cell ``nope.chat``: yi-6b's file under the NoPE family, with the
    chat traffic and yi6b.chat's limits, in a BENCHMARK.json and config
    directory of its own."""
    monkeypatch.setitem(sys.modules, "benchmarks.chip.families.nope_decoder", _nope_family)
    monkeypatch.setitem(sys.modules, "benchmarks.chip.references.nope_decoder",
                        _nope_reference)
    conf = json.loads((spec.HERE / "configs" / "yi-6b.json").read_text())
    conf.update(name="yi-6b-nope", reference="nope_decoder", position_embedding_type="nope")
    prog = conf["program"]
    conf["program"] = dict(prog, replace=dict(prog["replace"], use_rope=False))
    for d in ("configs", "limits"):
        (tmp_path / d).mkdir()
    (tmp_path / "configs" / "yi-6b-nope.json").write_text(json.dumps(conf))
    shutil.copy(spec.HERE / "limits" / "yi6b.chat.json", tmp_path / "limits" / "nope.chat.json")
    bench = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    bench["workloads"] = [{"name": "nope.chat", "config": "yi-6b-nope", "traffic": "chat",
                           "chips": 1, "why": "test only"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(spec, "HERE", tmp_path)
    return spec.load_cell("nope.chat", tmp_path / "BENCHMARK.json")


def test_nope_family_found_and_checked(nope):
    assert nope.family is _nope_family
    assert spec.reference_module(nope.config) is _nope_reference
    cfg = spec.program_config(nope.config)
    assert cfg.use_rope is False and cfg.d_model == nope.sizes.d == 4096
    # the dense decoder's fields refuse the same file
    with pytest.raises(ValueError, match="departs.*use_rope"):
        spec.program_config(dict(nope.config, reference="dense_decoder"))
    with pytest.raises(ValueError, match="departs"):
        spec.program_config(dict(nope.config, rope_theta=1.0))


def test_nope_reference_matches_program_forward(nope):
    from repro.models.model import forward

    from benchmarks.chip.references import dense_decoder

    cell, cfg = cut(nope)
    cfg = dataclasses.replace(cfg, dtype="float32", remat=False)
    params = weights.make_params(cfg, 0.02, 5, cell.family.init_kind)
    toks = np.random.default_rng(1).integers(1, cfg.vocab, 24)
    with jax.default_matmul_precision("highest"):
        prog, _, _ = forward(params, cfg, {"tokens": jnp.asarray(toks[None], jnp.int32)})
    view = cell.family.view(params)
    got = _nope_reference.logits(cell.config, view, toks, np.arange(24))
    np.testing.assert_allclose(np.asarray(got), np.asarray(prog[0]), rtol=2e-4, atol=2e-4)
    rotary = dense_decoder.logits(cell.config, view, toks, np.arange(24))
    assert np.abs(np.asarray(rotary) - np.asarray(prog[0])).max() > 1e-2


def test_nope_family_runs_to_correct(nope):
    cell, cfg = cut(nope)
    assert cfg.use_rope is False
    run, verdict, _ = harness.measure(cell, 2**33 + 29, 0.0, False, time.perf_counter(),
                                      require_chip=False, cfg=cfg, log=lambda s: None,
                                      compile_cache=False)
    assert verdict.correct, verdict.numbers
    assert run.calls and run.failed == 0
    # the head was set by the family's own function: uniform on [-1, 1]
    params = weights.make_params(cfg, cell.config["initializer_range"], 2**33 + 29,
                                 cell.family.init_kind)
    head = np.asarray(params["lm_head"], np.float32)
    assert np.abs(head).max() <= 1.0 and 0.5 < head.std() < 0.65
    # the served sequences again, through the family's view and reference
    served = []
    for burst, res in run.calls:
        check.check_wires(burst, res.responses, cell.mix["max_new"], cfg.vocab, served)
    gap = check.logit_gaps(cell, params, check.sample(served, 2, 1))
    assert gap <= cell.limits["logit_gap"]

    run.peak, run.trace = V5E, _Trace(serve_step=(0.5, 7), prefill_step=(0.25, 2))
    seqs = list(run.sequences())
    f, s = cell.family, cell.sizes
    assert seqs and all(n == cell.mix["max_new"] for _, n in seqs)
    fl = sum(f.sequence_flops(s, p, n) for p, n in seqs)
    assert _read(run, "mfu") == 100.0 * fl / (run.window_s * 197e12) > 0
    dec = sum(f.decode_flops(s, p + i) for p, n in seqs for i in range(n - 1))
    own = sum(f.token_bytes(s, p + i) for p, n in seqs for i in range(n - 1))
    want = 100.0 * max(dec / 197e12, (7 * f.step_bytes(s) + own) / 819e9) / 0.5
    assert _read(run, "decode_step_roofline") == pytest.approx(want, rel=1e-12)
    pre = sum(f.prefill_flops(s, p) for p, _ in seqs)
    want = 100.0 * max(pre / 197e12, 2 * f.prefill_bytes(s) / 819e9) / 0.25
    assert _read(run, "prefill_roofline") == pytest.approx(want, rel=1e-12)


# --- the weight maker and the counts as they were before families ----------

def _frozen_kind(path, norm):
    keys = [getattr(k, "key", None) for k in path]
    if any(k in ("ln1", "ln2", "final_norm") for k in keys):
        if keys[-1] == "bias" or (norm == "rmsnorm" and keys[-1] == "scale"):
            return "zeros"
        return "ones"
    return "normal"


def _frozen_make_params(cfg, std, seed):
    from repro.models import init_params

    shapes = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    kinds = [_frozen_kind(p, cfg.norm) for p, _ in flat]
    specs = [s for _, s in flat]

    def gen(key):
        out = []
        for i, (s, kind) in enumerate(zip(specs, kinds)):
            if kind == "zeros":
                out.append(jnp.zeros(s.shape, s.dtype))
            elif kind == "ones":
                out.append(jnp.ones(s.shape, s.dtype))
            else:
                x = jax.random.normal(jax.random.fold_in(key, i), s.shape, jnp.float32)
                out.append((x * std).astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    key = jax.random.key(weights.seed32(seed), impl="rbg")
    return jax.jit(gen)(key)


@pytest.mark.parametrize("name", ["yi6b.chat", "stablelm3b.longctx"])
def test_dense_weights_bit_identical(name):
    cell, cfg = tiny_cell(name)
    std, seed = cell.config["initializer_range"], 2**33 + 17
    got = weights.make_params(cfg, std, seed, cell.family.init_kind)
    old = _frozen_make_params(cfg, std, seed)
    assert jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(old)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(old)):
        a, b = np.asarray(a), np.asarray(b)
        bits = np.dtype(f"u{a.dtype.itemsize}")
        assert a.dtype == b.dtype and np.array_equal(a.view(bits), b.view(bits))


def _old_counts(s):
    """The dense formulas of the readers before families, verbatim."""
    mm = s.layers * (s.d * (s.heads + 2 * s.kv_heads) * s.head_dim
                     + s.heads * s.head_dim * s.d + (3 if s.glu else 2) * s.d * s.d_ff)

    def attn(first, n):
        return 4 * s.layers * s.heads * s.head_dim * (n * first + n * (n + 1) // 2)

    def prefill(n_p):
        return 2 * mm * n_p + attn(0, n_p) + 2 * s.d * s.vocab

    def decode(pos):
        return 2 * mm + attn(pos, 1) + 2 * s.d * s.vocab

    norms = (2 * s.layers + 1) * s.d * (2 if s.layernorm else 1)
    weight_bytes = s.dtype_bytes * (mm + s.vocab * s.d + norms)
    kv = s.dtype_bytes * 2 * s.layers * s.kv_heads * s.head_dim
    return prefill, decode, weight_bytes, kv


@dataclasses.dataclass
class _FixedRun(harness.Run):
    seqs: tuple = ()

    def sequences(self):
        return iter(self.seqs)


def _fixed_run(cell):
    return _FixedRun(cell, V5E, setup_s=1.0, t0=3.0, t1=23.5,
                     trace=_Trace(serve_step=(13.25, 381), prefill_step=(2.5, 4)),
                     seqs=((128, 128), (256, 128), (200, 1), (1000, 64), (777, 2)))


@pytest.mark.parametrize("name", ["yi6b.chat", "stablelm3b.longctx"])
def test_readers_give_the_old_formulas(name):
    run = _fixed_run(spec.load_cell(name))
    prefill, decode, weight_bytes, kv = _old_counts(run.sizes)
    total = sum(prefill(n_p) + sum(decode(n_p + i) for i in range(n_new - 1))
                for n_p, n_new in run.seqs)
    assert _read(run, "mfu") == 100.0 * total / (run.window_s * 1 * V5E["bf16_flops_per_s"])
    fl, kv_bytes = 0, 0
    for n_p, n_new in run.seqs:
        for i in range(n_new - 1):
            fl += decode(n_p + i)
            kv_bytes += (n_p + i + 1) * kv
    least = max(fl / V5E["bf16_flops_per_s"],
                (381 * weight_bytes + kv_bytes) / V5E["hbm_bytes_per_s"])
    assert _read(run, "decode_step_roofline") == 100.0 * least / 13.25
    fl = sum(prefill(n_p) for n_p, _ in run.seqs)
    least = max(fl / V5E["bf16_flops_per_s"], 4 * weight_bytes / V5E["hbm_bytes_per_s"])
    assert _read(run, "prefill_roofline") == 100.0 * least / 2.5


def test_readers_count_through_the_family():
    """A family whose every count is a constant: the readers give what the
    constants make, and nothing of the dense decoder's arithmetic."""
    fam = SimpleNamespace(sequence_flops=lambda s, p, n: 1e12, decode_flops=lambda s, p: 1e9,
                          token_bytes=lambda s, p: 1e6, step_bytes=lambda s: 1e10,
                          prefill_flops=lambda s, p: 1e13, prefill_bytes=lambda s: 1e11)
    run = _fixed_run(dataclasses.replace(spec.load_cell("yi6b.chat"), family=fam))
    steps = sum(n - 1 for _, n in run.seqs)
    assert _read(run, "mfu") == 100.0 * 5e12 / (20.5 * 197e12)
    assert _read(run, "decode_step_roofline") == pytest.approx(
        100.0 * max(steps * 1e9 / 197e12, (381 * 1e10 + steps * 1e6) / 819e9) / 13.25)
    assert _read(run, "prefill_roofline") == pytest.approx(
        100.0 * max(5e13 / 197e12, 4e11 / 819e9) / 2.5)
