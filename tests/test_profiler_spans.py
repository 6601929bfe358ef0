"""The serve path's host spans (``repro.obs.trace.span``) in a
``jax.profiler`` trace, read back the way the chip benchmark reads them
(``benchmarks/chip/trace_reduce.py``): every span present under its plain
name, one ``batcher.dispatch`` per decode step, and the nesting the
benchmark's per-layer metrics rely on."""
import dataclasses
import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.chip import trace_reduce  # noqa: E402
from repro.obs.trace import SPAN_NAMES  # noqa: E402

#: the streaming loop's spans; the rest open on both paths
STREAM_SPANS = frozenset(n for n in SPAN_NAMES
                         if n == "serve.tick" or n.startswith(("stream.", "fabric.")))
ONE_CHIP_SPANS = SPAN_NAMES - STREAM_SPANS


@pytest.fixture(scope="module")
def serve_setup():
    from repro.configs import get_config, smoke_config
    from repro.launch.serve import encode_request
    from repro.models import init_params

    cfg = dataclasses.replace(smoke_config(get_config("yi-6b")), n_layers=1)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    wires = [encode_request(r, [list(map(int, rng.integers(2, cfg.vocab, int(n))))
                                for n in rng.integers(4, 12, 2)])
             for r in range(3)]
    return params, cfg, wires


@pytest.fixture
def batchers(monkeypatch):
    """Every ContinuousBatcher the serve path builds, for its step count."""
    from repro.launch import serve
    from repro.runtime.scheduler import ContinuousBatcher

    made = []

    class Recorded(ContinuousBatcher):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            made.append(self)

    monkeypatch.setattr(serve, "ContinuousBatcher", Recorded)
    return made


def _traced(tmp_path, fn):
    """Run ``fn`` once to compile, then again under the profiler; the trace."""
    fn()
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    return out, trace_reduce.load(trace_reduce.find_xplane(str(tmp_path)))


def _spans(trace, name):
    return [(s, e) for n, s, e in zip(trace.host.names, trace.host.start, trace.host.end)
            if n == name]


def _inside(inner, outer):
    """Every interval of ``inner`` lies inside some interval of ``outer``."""
    return all(any(s0 <= s and e <= e0 for s0, e0 in outer) for s, e in inner)


def test_one_chip_spans_in_profiler_trace(serve_setup, batchers, tmp_path):
    from repro.launch.serve import serve_requests

    params, cfg, wires = serve_setup

    def call():
        batchers.clear()
        return serve_requests(params, cfg, wires, max_new=3, pad_to=16, slots=4, admit_cap=2)

    out, trace = _traced(tmp_path, call)
    assert len(out) == len(wires)
    for name in sorted(ONE_CHIP_SPANS):
        assert _spans(trace, name), name
    (b,) = batchers
    assert len(_spans(trace, "batcher.dispatch")) == b.steps_run > 0
    assert len(_spans(trace, "batcher.finish")) == b.steps_run
    assert len(_spans(trace, "serve.des")) == len(_spans(trace, "serve.ser")) == 1
    for name in ("des.plan", "des.gather", "des.split"):
        assert _inside(_spans(trace, name), _spans(trace, "serve.des")), name
    # 5 sequences through 2-wide admits: three prefills, each read back
    # with its tick's decode step, in that tick's one sync
    assert len(_spans(trace, "batcher.admit")) == 3
    assert _inside(_spans(trace, "batcher.sync"), _spans(trace, "batcher.finish"))
    assert len(_spans(trace, "batcher.sync")) == b.steps_run


def test_streaming_spans_in_profiler_trace(serve_setup, batchers, tmp_path):
    from repro.launch.serve import default_serve_fabric, serve_requests_streaming

    params, cfg, wires = serve_setup
    fabric = default_serve_fabric(n_shards=3)  # 4 virtual devices

    def call():
        batchers.clear()
        return serve_requests_streaming(params, cfg, wires, max_new=3, pad_to=16, slots=4,
                                        fabric=fabric)

    out, trace = _traced(tmp_path, call)
    assert len(out) == len(wires)
    for name in sorted(SPAN_NAMES):
        assert _spans(trace, name), name
    ticks = _spans(trace, "serve.tick")
    assert len(_spans(trace, "batcher.dispatch")) == sum(b.steps_run for b in batchers) > 0
    # each dispatch names the device its shard computes on, one per shard
    raw = jax.profiler.ProfileData.from_file(trace_reduce.find_xplane(str(tmp_path)))
    named = {dict(e.stats)["device"] for p in raw.planes for line in p.lines
             for e in line.events if e.name == "batcher.dispatch"}
    assert named == {b.device.id for b in batchers}
    assert len(named) == len(batchers) > 1
    # every compute tick flushes the lanes once and dispatches one exchange
    flushes = [f for f in _spans(trace, "stream.flush") if _inside([f], ticks)]
    assert len(flushes) == len(ticks)
    assert len([x for x in _spans(trace, "fabric.exchange") if _inside([x], ticks)]) == len(ticks)
    assert _inside(_spans(trace, "batcher.dispatch"), ticks)
    # each tick after the first reaps the previous tick's exchange (RX half)
    completes = _spans(trace, "fabric.complete")
    assert sum(any(s <= c0 and c1 <= e for c0, c1 in completes) for s, e in ticks) \
        >= len(ticks) - 1
    for name in ("des.plan", "des.gather", "des.split"):
        assert _inside(_spans(trace, name), _spans(trace, "serve.des")), name
