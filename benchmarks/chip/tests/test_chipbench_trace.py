"""The reduction from a profiler trace to per-layer numbers, on a small trace
recorded on a TPU v5e (``record_trace.py``: ``prefill_step`` run twice, then
``serve_step`` five times, each waited for, with host sleeps between), and
on hand-made intervals."""
import os

import numpy as np
import pytest

from _tiny import ROOT

from benchmarks.chip import trace_reduce

SMALL = os.path.join(ROOT, "benchmarks", "chip", "testdata", "small_trace.xplane.pb")


@pytest.fixture(scope="module")
def small():
    return trace_reduce.load(SMALL)


def test_union_of_intervals():
    s = np.array([0, 5, 2, 20, 21, 30])
    e = np.array([3, 10, 4, 25, 22, 30])
    np.testing.assert_array_equal(trace_reduce.union(s, e),
                                  [[0, 4], [5, 10], [20, 25], [30, 30]])
    assert trace_reduce.union(np.array([], np.int64), np.array([], np.int64)).shape == (0, 2)


def test_programs_found_by_name(small):
    assert small.chips == [0]
    t_pre, n_pre = small.program("prefill_step")
    t_dec, n_dec = small.program("serve_step")
    assert (n_pre, n_dec) == (2, 5)
    assert t_pre > 0 and t_dec > 0
    assert small.program("step") == (0.0, 0)  # names match whole, not in part


def test_busy_and_idle(small):
    iv = small.busy_intervals(0)
    assert (np.diff(iv.reshape(-1)) >= 0).all()  # merged and sorted
    busy = small.busy_s(0)
    span = (iv[-1, 1] - iv[0, 0]) / 1e9
    t_pre, _ = small.program("prefill_step")
    t_dec, _ = small.program("serve_step")
    # the ops lie inside their programs' executions
    assert 0 < busy <= t_pre + t_dec + 1e-9
    assert busy < span  # the host sleeps leave the chip idle
    gaps = small.idle_gaps(0, int(iv[0, 0]), int(iv[-1, 1]), n=10)
    secs = [s for _, s in gaps]
    assert secs == sorted(secs, reverse=True)
    # the longest gaps are the 20 ms and 10 ms sleeps between waited calls
    assert 0.009 < secs[0] < 0.2
    assert sum(secs) == pytest.approx(span - busy, rel=0.05) or len(gaps) == 10


def test_top_ops(small):
    top = small.top_ops(5)
    assert top and all(s > 0 for _, s in top)
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)
