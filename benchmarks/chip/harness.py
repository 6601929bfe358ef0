"""One run of one cell: set-up, a measured window of whole calls, the check
against the reference, and the result line.

The client is a closed loop: each call of the cell's entry answers one
burst, and the next call starts when it returns.  The window opens with the
first timed call and closes when the call still running at ``--seconds``
returns, so every rate is all the work of whole calls over all of their
time.  ``setup_s`` is the time from process start to the window's opening:
imports, weights, compiling (or loading from the cache) and the warm-up
call on a burst of its own.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
import traceback
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

from . import check, spec, traffic, weights, wire

HERE = spec.HERE


def use_compile_cache() -> None:
    """JAX's persistent cache in the checkout (``launch/compile_cache.py``:
    ``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``),
    for every program however quickly it compiles, so that a second run of a
    cell compiles nothing."""
    import jax

    from repro.launch.compile_cache import use_compile_cache as _use

    _use()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclass
class Run:
    """Everything a metric reader may read."""

    cell: spec.Cell
    peak: Optional[Dict]
    setup_s: float
    t0: float = 0.0
    t1: float = 0.0
    calls: list = field(default_factory=list)  # [(Burst, CallResult)]
    failed: int = 0
    attempted: int = 0
    compiles_in_window: int = 0
    trace: object = None  # trace_reduce.Trace of the window, --trace 1 only
    host: Dict[str, float] = field(default_factory=dict)
    streams: bool = False

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    @property
    def family(self):
        """The cell's family module: its counts take ``sizes``."""
        return self.cell.family

    @property
    def sizes(self):
        return self.cell.sizes

    def sequences(self):
        """(prompt length, generated tokens) of every sequence answered in
        the window, from the wires as the benchmark parses them."""
        for burst, res in self.calls:
            for prompts, w in zip(burst.prompts, res.responses):
                try:
                    _, outs = wire.decode_response(w)
                except ValueError:
                    continue
                for p, o in zip(prompts, outs):
                    yield len(p), len(o)


def _metric_reader(name: str) -> Callable:
    path = HERE / "metrics" / f"{name}.py"
    sp = importlib.util.spec_from_file_location(f"_bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod.read


class _CompileCounter:
    """Counts XLA backend compiles, to show that none falls in the window."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, *_a, **_k) -> None:
        if event == self.EVENT:
            self.n += 1


def _devices(cell: spec.Cell, require_chip: bool):
    import jax

    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {devs[0].platform}")
    if len(devs) < cell.chips:
        raise NoChip(f"{cell.name} needs {cell.chips} chips, JAX found {len(devs)}")
    return devs[: cell.chips]


def _time_per_item(fn: Callable[[], object], items: int, at_least_s: float = 0.25) -> float:
    """Seconds per item of ``fn``, repeated until the host clock spans at
    least ``at_least_s``."""
    fn()
    reps, t0 = 0, time.perf_counter()
    while True:
        fn()
        reps += 1
        dt = time.perf_counter() - t0
        if dt >= at_least_s:
            return dt / (reps * items)


def _host_layers(entry, run: Run) -> None:
    """DES and SER of the last burst, timed alone, outside the window."""
    burst, res = run.calls[-1]
    responses = [wire.decode_response(w) for w in res.responses]
    n = len(burst.wires)
    run.host["des_s_per_wire"] = _time_per_item(lambda: entry.des(burst.wires), n)
    run.host["ser_s_per_wire"] = _time_per_item(lambda: entry.ser(responses), n)


def window(entry, gen, seconds: float, run: Run, log=print) -> None:
    """Back-to-back calls on fresh bursts until the call running at
    ``seconds`` returns; a call that raises counts its wires as failed and
    ends the window."""
    import jax

    k = 0
    run.t0 = time.perf_counter()
    while True:
        burst = gen.burst(k)
        run.attempted += len(burst.wires)
        try:
            with jax.profiler.TraceAnnotation(f"bench.call {k}"):
                res = entry.call(burst.wires)
        except Exception:  # the program failed the call: count it, end the window
            run.t1 = time.perf_counter()
            run.failed += len(burst.wires)
            log(f"[bench] call {k} failed:\n{traceback.format_exc()}")
            return
        run.calls.append((burst, res))
        run.t1 = res.t1
        k += 1
        if run.t1 - run.t0 >= seconds:
            return


def measure(cell: spec.Cell, seed: int, seconds: float, trace: bool, t_start: float,
            require_chip: bool = True, cfg=None, log=print, keep_trace: Optional[str] = None,
            compile_cache: bool = True) -> Tuple[Run, check.Verdict, Dict]:
    import jax

    from . import peaks

    devs = _devices(cell, require_chip)
    if compile_cache:
        use_compile_cache()
    kind = devs[0].device_kind
    peak = peaks.peak(kind) if require_chip else None
    cfg = cfg or spec.program_config(cell.config)
    mix = cell.mix
    counter = _CompileCounter()

    params = weights.make_params(cfg, cell.config["initializer_range"], seed,
                                 cell.family.init_kind)
    log(f"[bench] weights made at {time.perf_counter() - t_start:.3f} s")
    entry_mod = spec.entry_module(mix["entry"])
    entry = entry_mod.Entry(params, cfg, {k: v for k, v in mix.items()})
    gen = traffic.Generator(mix, cfg.vocab, seed)
    warm = gen.burst(-1)
    try:
        entry.call(warm.wires)  # warm-up: every shape the window uses
        warmed = True
    except Exception:  # the entry cannot answer at all: no window
        log(f"[bench] warm-up call failed:\n{traceback.format_exc()}")
        warmed = False
    run = Run(cell, peak, setup_s=time.perf_counter() - t_start, streams=entry.streams)
    log(f"[bench] {cell.name}: set-up {run.setup_s:.3f} s")
    if not warmed:
        run.attempted = run.failed = len(warm.wires)
        trace = False

    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        jax.profiler.start_trace(tdir)
    compiles0 = counter.n
    if warmed:
        window(entry, gen, seconds, run, log)
    run.compiles_in_window = counter.n - compiles0
    if trace:
        jax.profiler.stop_trace()
    peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs)
    log(f"[bench] window {run.window_s:.3f} s, {len(run.calls)} calls, "
        f"{run.compiles_in_window} compiles inside it")

    if trace:
        from . import trace_reduce

        if run.calls:
            _host_layers(entry, run)
        run.trace = trace_reduce.load(trace_reduce.find_xplane(tdir))
        if keep_trace:
            shutil.copytree(tdir, keep_trace, dirs_exist_ok=True)
        shutil.rmtree(tdir, ignore_errors=True)

    # the check: the program's state goes first, then the reference runs
    served: List[check.Served] = []
    wires_bad = 0
    stream_bad = 0 if entry.streams else None
    for burst, res in run.calls:
        wires_bad += check.check_wires(burst, res.responses, mix["max_new"], cfg.vocab, served)
        if entry.streams:
            stream_bad += _stream_mismatch(burst, res)
    run.failed += wires_bad
    entry.close()
    del entry
    gc.collect()
    t_ref = time.perf_counter()
    seqs = check.sample(served, mix["check_sequences"], seed)
    gap = check.logit_gaps(cell, params, seqs) if seqs else None
    verdict = check.judge(cell.limits, wires_bad, stream_bad, gap, run.failed)
    log(f"[bench] reference check of {len(seqs)} sequences: "
        f"{time.perf_counter() - t_ref:.3f} s")
    device = {"platform": devs[0].platform, "kind": kind, "count": len(devs),
              "memory_peak_bytes": int(peak_bytes)}
    return run, verdict, device


def _stream_mismatch(burst, res) -> int:
    """Sequences whose tokens at the ingress differ from the final wire."""
    bad = 0
    for w, (prompts, resp) in enumerate(zip(burst.prompts, res.responses)):
        try:
            _, outs = wire.decode_response(resp)
        except ValueError:
            outs = []
        for j in range(len(prompts)):
            got = [t for _, t in res.arrivals.get((w, j), [])]
            bad += j >= len(outs) or got != list(outs[j])
    return bad


def read_metrics(run: Run, names: List[str]) -> Dict[str, Dict]:
    bench = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    units = {m["name"]: m["unit"] for m in bench["end_to_end"] + bench["per_layer"]}
    out = {}
    for name in names:
        value = _metric_reader(name)(run)
        if value is not None:
            out[name] = {"value": float(value), "unit": units[name]}
    return out


def breakdown(run: Run) -> Dict:
    t = run.trace
    if not t.chips:
        return {"device_ops": [], "idle_gaps": []}
    chip = t.chips[0]
    iv = t.busy_intervals(chip)
    lo, hi = (int(iv[0, 0]), int(iv[-1, 1])) if len(iv) else (0, 0)
    return {"device_ops": [[n, s] for n, s in t.top_ops(10)],
            "idle_gaps": [[n, s] for n, s in t.idle_gaps(chip, lo, hi, 10)]}


def result_line(run: Run, verdict: check.Verdict, device: Dict, trace: bool) -> Dict:
    names = run.cell.per_layer if trace else run.cell.e2e
    line = {"correct": verdict.correct, "attempted": run.attempted, "failed": run.failed,
            "metrics": read_metrics(run, names), "device": dict(device)}
    if trace and run.trace is not None:
        chips = [c for c in range(run.cell.chips) if c in run.trace.chips] or [0]
        line["device"]["busy_s"] = sum(run.trace.busy_s(c) for c in chips) / len(chips)
        line["device"]["window_s"] = run.window_s
        line["breakdown"] = breakdown(run)
    line["compiles_in_window"] = run.compiles_in_window
    line["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in verdict.numbers}
    return line


def parse(argv: List[str]) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="with --trace 1, also copy the profiler's trace to DIR")
    return ap.parse_args(argv)


def main(argv: List[str], t_start: float) -> int:
    args = parse(argv)
    log = lambda s: print(s, file=sys.stderr, flush=True)  # noqa: E731
    cell = spec.load_cell(args.workload)
    try:
        run, verdict, device = measure(cell, args.seed, args.seconds, bool(args.trace),
                                       t_start, log=log, keep_trace=args.keep_trace)
    except NoChip as exc:
        log(f"[bench] {exc}")
        return 3
    line = result_line(run, verdict, device, bool(args.trace))
    for name, value, limit in verdict.numbers:
        log(f"check {name} {value!r} limit {limit!r}")
    print(json.dumps(line), flush=True)
    return 0
