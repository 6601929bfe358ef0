"""The benchmark harness on the CPU: cells, configurations and metrics found
by name, the traffic generator, the wire codec, whole-call windows and tails,
and the refusal to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys
import time

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np
import pytest

from _tiny import ROOT, tiny_cell  # noqa: F401  (puts the checkout on sys.path)

from benchmarks.chip import harness, spec, traffic, wire
from benchmarks.chip.entries.serve_requests import CallResult

BENCH = json.loads(open(os.path.join(ROOT, "BENCHMARK.json")).read())
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_found_by_name(name):
    cell = spec.load_cell(name)
    assert cell.mix["entry"] and spec.entry_module(cell.mix["entry"]).Entry
    assert "logit_gap" in cell.limits
    assert "setup_s" in cell.e2e and len(cell.e2e) >= 2 and cell.per_layer
    assert spec.reference_module(cell.config).logits
    assert cell.family is spec.family_module(cell.config)
    assert cell.sizes == cell.family.sizes(cell.config)


@pytest.mark.parametrize("name", METRICS)
def test_metric_reader_found_by_name(name):
    assert callable(harness._metric_reader(name))


@pytest.mark.parametrize("name", sorted({w["config"] for w in BENCH["workloads"]}))
def test_config_file_matches_program(name):
    entry = next(c for c in BENCH["configs"] if c["name"] == name)
    conf = json.loads(open(os.path.join(ROOT, entry["file"])).read())
    assert conf["name"] == name and conf["reduced"] == entry["reduced"]
    cfg = spec.program_config(conf)
    assert cfg.d_model == conf["hidden_size"] and not cfg.tie_embeddings
    bad = dict(conf, rope_theta=1.0)
    with pytest.raises(ValueError, match="departs"):
        spec.program_config(bad)


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        spec.load_cell("no.such.cell")


@pytest.mark.parametrize("name", CELLS)
def test_traffic_same_work_every_seed(name):
    mix = spec.load_cell(name).mix
    g1, g2 = traffic.Generator(mix, 1000, 2**33 + 1), traffic.Generator(mix, 1000, 7)
    a, b = g1.burst(0), g1.burst(0)
    assert a.wires == b.wires  # the same seed gives the same burst
    c = g2.burst(0)
    lens = lambda x: sorted(len(p) for w in x.prompts for p in w)  # noqa: E731
    assert lens(a) == lens(c) == sorted(traffic.length_grid(mix).tolist())
    assert a.wires != c.wires
    lo, hi = mix["prompt_len"]
    assert min(lens(a)) == lo and max(lens(a)) == hi
    sizes = {len(w) for w in a.wires}
    if mix["prompts_per_wire"] > 1:
        assert len(sizes) == 1  # every wire the same byte length
    assert max(sizes) == max(len(w) for w in c.wires)
    toks = np.concatenate([p for w in a.prompts for p in w])
    assert toks.min() >= 1 and toks.max() < 1000


def test_wire_codec_matches_program():
    from repro.launch.serve import decode_request, encode_request, encode_response_batch

    prompts = [np.array([5, 6, 7]), np.array([], np.int64), np.array([2**31 + 3])]
    w = wire.encode_request(2**40 + 9, prompts)
    assert w == encode_request(2**40 + 9, [p.tolist() for p in prompts])
    assert decode_request(w) == (2**40 + 9, [p.tolist() for p in prompts])
    outs = [[1, 2, 3], [], [70000]]
    (r,) = encode_response_batch([(123, outs)])
    assert wire.decode_response(r) == (123, outs)
    with pytest.raises(ValueError):
        wire.decode_response(r[:-1])


class _SleepEntry:
    """Calls that take a fixed host time; every sequence gets 3 tokens, one
    per ``gap`` seconds after ``first`` seconds."""

    streams = True

    def __init__(self, cost, first, gap):
        self.cost, self.first, self.gap = cost, first, gap

    def call(self, wires):
        t0 = time.perf_counter()
        time.sleep(self.cost)
        arrivals, responses = {}, []
        for w, wi in enumerate(wires):
            n = int(np.frombuffer(wi, "<u4", 1, 8)[0])
            for j in range(n):
                arrivals[(w, j)] = [(t0 + self.first + k * self.gap, 9) for k in range(3)]
            responses.append(b"")
        return CallResult(t0, time.perf_counter(), responses, arrivals)


def test_window_is_whole_calls():
    cell, _ = tiny_cell("yi6b.stream4")
    gen = traffic.Generator(cell.mix, 512, 1)
    run = harness.Run(cell, None, setup_s=1.0)
    harness.window(_SleepEntry(0.06, 0.01, 0.02), gen, 0.2, run, log=lambda s: None)
    assert len(run.calls) == 4  # the call running at 0.2 s finishes
    assert run.window_s >= 0.24
    assert run.window_s == run.calls[-1][1].t1 - run.t0
    assert run.attempted == 4 * cell.mix["wires"] and run.failed == 0


class _FailingEntry(_SleepEntry):
    def call(self, wires):
        raise RuntimeError("lost")


def test_failed_call_ends_window_and_counts():
    cell, _ = tiny_cell("yi6b.chat")
    run = harness.Run(cell, None, setup_s=1.0)
    gen = traffic.Generator(cell.mix, 512, 1)
    harness.window(_FailingEntry(0, 0, 0), gen, 1.0, run, log=lambda s: None)
    assert run.calls == [] and run.failed == run.attempted == cell.mix["wires"]


def test_tails_over_all_samples():
    cell, _ = tiny_cell("yi6b.stream4")
    run = harness.Run(cell, None, setup_s=1.0, streams=True)
    rng = np.random.default_rng(0)
    ttft, itl = [], []
    for k in range(3):
        burst = traffic.Generator(cell.mix, 512, 1).burst(k)
        t0 = 100.0 * k
        arr = {}
        for w, prompts in enumerate(burst.prompts):
            for j in range(len(prompts)):
                first, gaps = rng.uniform(0, 5), rng.uniform(0.01, 0.2, 4)
                ts = t0 + first + np.concatenate([[0], np.cumsum(gaps)])
                arr[(w, j)] = [(t, 1) for t in ts]
                ttft.append(first)
                itl.append(gaps.mean())
        run.calls.append((burst, CallResult(t0, t0 + 10, [], arr)))
    read = lambda n: harness._metric_reader(n)(run)  # noqa: E731
    assert read("ttft_p95_ms") == pytest.approx(1e3 * np.percentile(ttft, 95), rel=1e-12)
    assert read("itl_p95_ms") == pytest.approx(1e3 * np.percentile(itl, 95), rel=1e-9)
    assert len(ttft) == 3 * cell.mix["wires"] * cell.mix["prompts_per_wire"]


def test_tails_absent_on_one_chip():
    cell, _ = tiny_cell("yi6b.chat")
    run = harness.Run(cell, None, setup_s=1.0, streams=False)
    assert harness._metric_reader("ttft_p95_ms")(run) is None
    assert harness._metric_reader("itl_p95_ms")(run) is None


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", "yi6b.chat", "--seed",
         "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(p):
    lines = p.stdout.strip().splitlines()
    return not lines or not lines[-1].startswith("{")


def test_run_refuses_without_tpu():
    p = _run_py(ROOT)
    assert p.returncode != 0 and _no_result(p), p.stderr[-2000:]
    assert "no TPU" in p.stderr


def test_run_refuses_alone(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks", "chip"),
                    tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0 and _no_result(p)
