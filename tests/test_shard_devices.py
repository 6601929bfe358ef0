"""Streaming shards on their own devices, and the batcher's one sync a tick.

``serve_requests_streaming`` serves shard ``s`` on the device of fabric rank
``s``: its batcher's weights (a copy made once per params and device), slot
cache and steps live there, so the shards' steps run at once.
``ContinuousBatcher.step_begin`` reads nothing back to the host; the
admitted sequences' first tokens are read in ``step_finish`` with the decode
step's, and the tick's emissions come out as they did when the admit read
its first tokens at once.
"""
import dataclasses
import gc
import weakref
from collections import deque

import jax
import numpy as np
import pytest
from jax._src.array import ArrayImpl

from repro.launch import serve
from repro.launch.serve import (
    default_serve_fabric, encode_request, serve_requests,
    serve_requests_streaming,
)
from repro.obs.metrics import MetricsRegistry
from repro.runtime import scheduler
from repro.runtime.scheduler import ContinuousBatcher, SchedulerConfig

KW = dict(max_new=3, pad_to=16, slots=4, admit_cap=2)


def _tiny():
    from repro.configs import get_config, smoke_config
    from repro.models import init_params

    cfg = dataclasses.replace(smoke_config(get_config("yi-6b")), n_layers=1)
    return init_params(cfg, jax.random.PRNGKey(0)), cfg


@pytest.fixture(scope="module")
def setup():
    params, cfg = _tiny()
    rng = np.random.default_rng(5)
    wires = [encode_request(r, [list(map(int, rng.integers(2, cfg.vocab, int(n))))
                                for n in rng.integers(4, 12, 2)])
             for r in range(6)]
    return params, cfg, wires


@pytest.fixture(scope="module")
def fabric():
    return default_serve_fabric(n_shards=3)  # 4 of conftest's 8 host devices


@pytest.fixture
def batchers(monkeypatch):
    """Every ContinuousBatcher the serve path builds."""
    made = []

    class Recorded(ContinuousBatcher):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            made.append(self)

    monkeypatch.setattr(serve, "ContinuousBatcher", Recorded)
    return made


def _on(tree, dev) -> bool:
    return all(x.devices() == {dev} for x in jax.tree.leaves(tree))


def test_streaming_shards_serve_on_their_rank_devices(setup, fabric, batchers):
    params, cfg, wires = setup
    want = serve_requests(params, cfg, wires, **KW)
    batchers.clear()
    m = MetricsRegistry()
    assert serve_requests_streaming(params, cfg, wires, fabric=fabric, metrics=m,
                                    **KW) == want

    flat = m.flat()
    ranks = [fabric.router.rank_device(r) for r in range(4)]
    assert len(set(ranks)) == 4
    ids = [int(flat[f"serve.shard.device{{shard={s}}}"]) for s in (1, 2, 3)]
    assert ids == [ranks[s].id for s in (1, 2, 3)]
    assert len(set(ids)) == 3 and ranks[0].id not in ids
    assert sorted(b.device.id for b in batchers) == sorted(ids)
    for b in batchers:
        assert _on(b.cache, b.device) and _on(b.params, b.device)
        assert _on((b.cur_tok, b._extra_inputs), b.device)


def test_rank_device_follows_the_mesh_order():
    """A rank's device is read from the mesh, whatever order it holds."""
    from jax.sharding import Mesh

    from repro.fabric import Fabric

    devs = jax.devices()
    order = [devs[i] for i in (0, 2, 3, 1, 4, 6, 7, 5)]
    fab = Fabric(mesh=Mesh(np.array(order).reshape(4, 2), ("x", "y")))
    assert [fab.router.rank_device(r) for r in range(8)] == order
    # ranks run over the axes in the fabric's order, not the mesh's
    router = type(fab.router)(fab.router.mesh, axis_names=("y", "x"))
    want = np.array(order).reshape(4, 2).T.reshape(-1)
    assert [router.rank_device(r) for r in range(8)] == list(want)


def test_weight_copies_made_once_and_freed_with_params(setup, fabric, batchers):
    _, cfg, wires = setup
    params, _ = _tiny()
    serve_requests_streaming(params, cfg, wires, fabric=fabric, **KW)
    first = {b.device: b.params for b in batchers}
    batchers.clear()
    serve_requests_streaming(params, cfg, wires, fabric=fabric, **KW)
    assert len(first) == len(batchers) == 3
    for b in batchers:
        assert b.params is first[b.device]  # the same arrays: no new transfer
        for x, y in zip(jax.tree.leaves(b.params), jax.tree.leaves(params)):
            assert x is not y and np.array_equal(x, y)
    jax.block_until_ready(list(first.values()))  # no transfer holds a copy
    copies = [weakref.ref(x) for tree in first.values() for x in jax.tree.leaves(tree)]
    keys = [(id(jax.tree.leaves(params)[0]), d) for d in first]
    assert all(k in scheduler._PLACED for k in keys)
    del params, first, b, x, y
    batchers.clear()
    gc.collect()
    assert all(k not in scheduler._PLACED for k in keys)
    assert all(r() is None for r in copies)


def _expected_ticks(ids, slots, admit, max_new):
    """(seq_id, position) of each tick's emissions, by the batcher's rules:
    admitted sequences' first tokens in admission order, then one decode
    token for every slot still live, in slot order."""
    active, pending, n = [None] * slots, deque(ids), {}
    ticks = []
    while pending or any(a is not None for a in active):
        em = []
        free = [i for i, a in enumerate(active) if a is None]
        for j in range(min(len(free), admit, len(pending))):
            sid = pending.popleft()
            em.append((sid, 0))
            n[sid] = 1
            if max_new > 1:
                active[free[j]] = sid
        for i, sid in enumerate(active):
            if sid is not None:
                em.append((sid, n[sid]))
                n[sid] += 1
                if n[sid] == max_new:
                    active[i] = None
        ticks.append(em)
    return ticks


@pytest.mark.parametrize("max_new,logprobs", [(1, False), (1, True), (3, False), (3, True)])
def test_batcher_emissions_after_deferred_admit_read(setup, monkeypatch, max_new, logprobs):
    params, cfg, _ = setup
    rng = np.random.default_rng(7)
    prompts = [list(map(int, rng.integers(2, cfg.vocab, int(n))))
               for n in rng.integers(4, 12, 5)]
    sched = SchedulerConfig(slots=2, prompt_cap=16, max_new=max_new, admit_cap=2)
    m = MetricsRegistry()
    b = ContinuousBatcher(params, cfg, sched, metrics=m, logprobs=logprobs)
    for i, p in enumerate(prompts):
        b.submit(i, p)

    reads = [0]
    value = ArrayImpl._value
    monkeypatch.setattr(ArrayImpl, "_value", property(
        lambda x: (reads.__setitem__(0, reads[0] + 1), value.fget(x))[1]))
    ticks, lps = [], {}
    while b.pending or b.n_active:
        reads[0] = 0
        b.step_begin()
        assert reads[0] == 0  # nothing is read back before step_finish
        ticks.append(b.step_finish())
        assert reads[0] > 0
        if logprobs:
            assert set(b.tick_logprobs) == {(s, p) for s, p, _ in ticks[-1]}
            lps.update(b.tick_logprobs)
    monkeypatch.undo()

    want = _expected_ticks(range(len(prompts)), 2, 2, max_new)
    assert [[(s, p) for s, p, _ in t] for t in ticks] == want
    assert all(len(b.done[i]) == max_new for i in range(len(prompts)))
    for t in ticks:
        for s, p, tok in t:
            assert b.done[s][p] == tok
    assert b.steps_run == sum(len(t) > sum(p == 0 for _, p in t) for t in want)
    flat = m.flat()
    assert flat["batcher.admitted"] == flat["batcher.evicted"] == len(prompts)
    assert flat.get("batcher.steps", 0) == b.steps_run
    if logprobs:
        assert all(np.isfinite(v) and v <= 0 for v in lps.values())
        plain = ContinuousBatcher(params, cfg, sched)
        for i, p in enumerate(prompts):
            plain.submit(i, p)
        assert plain.run() == b.done  # logprobs leave the tokens as they are
