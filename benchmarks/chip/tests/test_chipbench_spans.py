"""The readers of the program's spans (``metrics/_spans.py`` and the five
metrics on it) on hand-made traces: the serving chip, idle time inside spans,
and what each metric makes of them, with exact expected values."""
from types import SimpleNamespace

import numpy as np
import pytest

from _tiny import tiny_cell

from benchmarks.chip import harness, trace_reduce
from benchmarks.chip.metrics import _spans

US = 1000  # the hand-made times are in microseconds, the trace's in ns


def _events(*rows):
    ev = trace_reduce.Events()
    for name, s, e in rows:
        ev.add(name, s * US, (e - s) * US)
    return ev


def _trace(ticks=True, program_spans=True):
    """Chip 0 is the busier chip, chip 1 runs more ``serve_step``: the
    serving chip is chip 1, idle on it from 110 to 130 us."""
    ops = {0: _events(("fusion.1", 0, 10), ("fusion.2", 20, 30), ("fusion.3", 50, 60)),
           1: _events(("fusion.1", 100, 110), ("fusion.2", 130, 140))}
    modules = {0: _events(("jit_serve_step(1)", 0, 10), ("jit_local(2)", 20, 60)),
               1: _events(("jit_serve_step(1)", 100, 140))}
    host = [("bench.call 0", 90, 150), ("$scheduler.py:287 step_finish", 125, 130)]
    if program_spans:
        host += [("batcher.dispatch", 100, 112), ("batcher.finish", 115, 125),
                 ("batcher.sync", 116, 118), ("serve.des", 126, 128),
                 ("serve.ser", 135, 138)]
    if ticks:
        host += [("serve.tick", 100, 131), ("serve.tick", 131, 140)]
    return trace_reduce.Trace(ops, modules, _events(*host))


def _run(trace, wires=2):
    cell, _ = tiny_cell("yi6b.stream4")
    run = harness.Run(cell, None, setup_s=1.0)
    run.trace = trace
    run.calls = [(SimpleNamespace(wires=[b""] * wires), None)]
    return run


def _read(name, run):
    return harness._metric_reader(name)(run)


def test_idle_inside_intervals():
    idle = np.array([[10, 20], [30, 50]])
    s, e = np.array([0, 15, 25, 35, 60, 5]), np.array([12, 40, 28, 60, 70, 5])
    np.testing.assert_array_equal(_spans.inside(idle, s, e), [2, 15, 0, 15, 0, 0])
    np.testing.assert_array_equal(_spans.below(idle, [0, 10, 20, 45, 99]), [0, 0, 10, 25, 30])
    assert _spans.inside(np.zeros((0, 2), np.int64), s, e).tolist() == [0] * 6
    # overlapping spans count the idle they cover once
    assert _spans.idle_under(idle, np.array([[0, 40], [15, 45]])) == 25


def test_serving_chip_is_the_one_with_most_decode_time():
    t = _trace()
    assert t.busy_s(0) > t.busy_s(1)
    assert _spans.serving_chip(t) == 1
    np.testing.assert_array_equal(_spans.idle_intervals(t, 1), [[110 * US, 130 * US]])
    np.testing.assert_array_equal(_spans.idle_intervals(t, 0),
                                  [[10 * US, 20 * US], [30 * US, 50 * US]])
    no_decode = trace_reduce.Trace(t.ops, {0: _events(("jit_local(2)", 0, 5))}, t.host)
    assert _spans.serving_chip(no_decode) is None


def test_span_metrics_exact():
    run = _run(_trace())
    assert _read("des_span_us_per_wire", run) == pytest.approx(1.0, rel=1e-12)
    assert _read("ser_span_us_per_wire", run) == pytest.approx(1.5, rel=1e-12)
    # idle under dispatch (110-112) and finish (115-125; sync inside it
    # counts once), over one decode step
    assert _read("batcher_idle_ms_per_step", run) == pytest.approx(0.012, rel=1e-12)
    # ticks hold 20 us and 0 us of idle
    assert _read("tick_idle_ms", run) == pytest.approx(0.010, rel=1e-12)
    # the ticks cover the chip's whole idle interval
    assert _read("idle_unattributed_share", run) == 0.0


def test_unattributed_share_leaves_out_harness_and_python_frames():
    # without ticks, 14 of the 20 idle us lie under program spans; the
    # bench.call span and the Python frame over the rest do not count
    run = _run(_trace(ticks=False))
    assert _read("idle_unattributed_share", run) == pytest.approx(30.0, rel=1e-12)
    assert _read("tick_idle_ms", run) is None


@pytest.mark.parametrize("name", ["des_span_us_per_wire", "ser_span_us_per_wire",
                                  "batcher_idle_ms_per_step", "tick_idle_ms",
                                  "idle_unattributed_share"])
def test_span_metrics_absent_without_spans(name):
    # a program that opens no spans, no trace, or no window: no number
    assert _read(name, _run(_trace(ticks=False, program_spans=False))) is None
    assert _read(name, _run(None)) is None
    if name.endswith("per_wire"):
        assert _read(name, _run(_trace(), wires=0)) is None
