"""Observability plane: metrics registry, on-device fabric counters,
static-vs-observed load drift, trace export, and telemetry-off identity.

The counter invariants here are the PR's acceptance criteria:

* counters are exact, not sampled — delivered frames reported by the
  scan-carry counter block equal ``Fabric.frames_routed`` exactly;
* for deterministic workloads the OBSERVED per-(link, direction) load
  matrix equals ``analysis.comm.demand_link_loads``'s static prediction
  bit-for-bit, so any divergence (``Fabric.load_drift()``) is a real
  routing bug or fault — asserted both ways with a seeded ``tx_hook``
  misroute;
* the fused single-jit tick and the three-program path accumulate
  bit-identical counter blocks (the counters are order-independent event
  counts, so engine choice and queue layout cannot skew them);
* attaching a registry/trace to the streaming serve loop changes ZERO
  response bytes.

Runs on the 8 simulated host devices from ``conftest.py``.
"""
import dataclasses
import json
import pathlib
import sys

import jax
import numpy as np
import pytest

from repro.fabric import Fabric, FabricConfig
from repro.obs import (
    ClassWindows,
    MetricsRegistry,
    SPAN_NAMES,
    TraceRecorder,
    format_key,
    span,
    validate_snapshot,
    validate_trace,
    window_stats,
)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_registry_basics_and_flat_keys():
    m = MetricsRegistry()
    m.counter("f.sent", axis=0).add(3)
    m.counter("f.sent", axis=0).add(2)  # get-or-create: same instance
    m.counter("f.sent", axis=1).add(7)
    m.gauge("q.depth").set(4)
    m.histogram("lat", base=1.0).observe(5.0)
    m.series("ttft").append(0.25)
    flat = m.flat()
    assert flat[format_key("f.sent", {"axis": 0})] == 5
    assert flat["f.sent{axis=1}"] == 7
    assert flat["q.depth"] == 4
    assert flat["lat"]["count"] == 1
    assert flat["ttft"] == [0.25]


def test_registry_kind_conflict_and_negative_counter_raise():
    m = MetricsRegistry()
    m.counter("x").add(1)
    with pytest.raises(ValueError):
        m.gauge("x")  # a name is pinned to one metric type
    with pytest.raises(ValueError):
        m.counter("x").add(-1)  # counters are monotonic


def test_histogram_log2_buckets_exact():
    from repro.obs import Histogram

    h = Histogram(base=1.0, n_buckets=8)
    for v, bucket in ((0.5, 0), (1.0, 0), (1.5, 1), (2.0, 1), (3.0, 2),
                      (1000.0, 7)):  # overflow clamps to the last bucket
        before = list(h.buckets)
        h.observe(v)
        assert h.buckets[bucket] == before[bucket] + 1, (v, bucket)
    assert h.count == sum(h.buckets) == 6
    assert h.min == 0.5 and h.max == 1000.0
    assert h.bounds()[0] == 1.0 and h.bounds()[2] == 4.0


def test_snapshot_round_trips_and_readers_ignore_unknown_keys():
    m = MetricsRegistry()
    m.counter("a", k=1).add(2)
    m.histogram("h").observe(3.0)
    snap = json.loads(m.to_json())
    assert validate_snapshot(snap) == []
    # forward-compat: a newer writer may add keys; validators/readers must
    # ignore what they don't know rather than reject the document
    snap["future_field"] = {"x": 1}
    snap["metrics"][0]["future_key"] = "y"
    assert validate_snapshot(snap) == []
    # ...but real schema violations are caught
    bad = json.loads(m.to_json())
    bad["metrics"][1]["buckets"][0] += 1  # count != sum(buckets)
    assert validate_snapshot(bad)


# ---------------------------------------------------------------------------
# satellite: ONE shared arrive-window implementation
# ---------------------------------------------------------------------------


def test_arrive_window_is_one_shared_implementation():
    """``stream.plane.arrive_stats`` IS ``obs.metrics.window_stats`` (the
    module-level alias), and ``ClassWindows`` — what
    ``Fabric.class_arrive_stats`` serves — produces byte-identical dicts
    for the same samples.  The two ends of the backpressure loop can never
    disagree on what "p95" means."""
    from repro.stream import plane

    assert plane.arrive_stats is window_stats
    samples = {0: [3, 5, 2, 9, 4, 1, 1, 12], 1: [7, 7, 8]}
    cw = ClassWindows(maxlen=256)
    for cls, vals in samples.items():
        for v in vals:
            cw.record(cls, v)
    assert cw.stats() == {c: window_stats(v) for c, v in samples.items()}


def test_fabric_and_reader_arrive_stats_identical():
    """End to end: single-token chunks (one chunk per message) make the
    fabric's per-message window and the reader's per-chunk window see the
    same arrive steps — the per-class stats must match exactly."""
    from repro.stream import StreamReader, encode_token_chunk

    fab = Fabric(n_ranks=4, config=FabricConfig(
        frame_phits=16, credits=4, qos_weights=(2, 1)))
    boxes = [fab.mailbox(r) for r in range(4)]
    reader = StreamReader()
    for step in range(3):
        for src in (1, 2, 3):
            wire = encode_token_chunk(src, step, [100 + step], eos=(step == 2))
            boxes[src].send(0, wire, list_level=1 + (src % 2))
        fab.exchange()
        reader.feed(boxes[0].recv())
    fab_stats = fab.class_arrive_stats(0)
    reader_stats = reader.class_arrive_stats()
    # fabric keys by level % n_classes; fold the reader's streams the same way
    per = {}
    for st in reader.streams.values():
        per.setdefault(st.level % fab.n_classes, []).extend(st.arrive_steps)
    assert fab_stats == {c: window_stats(v) for c, v in sorted(per.items())}
    assert reader_stats  # and the reader's own per-level view is populated


# ---------------------------------------------------------------------------
# on-device counters: exactness + static-vs-observed drift
# ---------------------------------------------------------------------------


def _all_to_all(fab, n=None, nbytes=17):
    n = n or fab.n_ranks
    boxes = [fab.mailbox(r) for r in range(n)]
    for s in range(n):
        for d in range(n):
            if s != d:
                boxes[s].send(d, bytes([s, d]) * nbytes)
    fab.exchange()
    return boxes


def test_counters_exact_delivered_and_observed_equals_static():
    """Delivered counter == ``frames_routed`` exactly; the observed
    per-(ring, direction) load matrix equals the static
    ``analysis.comm.demand_link_loads`` prediction frame-for-frame, so
    ``load_drift()`` is empty."""
    fab = Fabric(n_ranks=8, config=FabricConfig(
        frame_phits=2, credits=2, qos_weights=(3, 1)))
    _all_to_all(fab)
    ctr = fab.counters_total()
    from repro.obs.counters import global_index

    delivered = int(ctr[:, global_index(1, "delivered")].sum())
    assert delivered == fab.frames_routed > 0
    assert int(ctr[:, global_index(1, "crc_fail")].sum()) == 0
    observed = fab.observed_link_loads()
    expected = fab.expected_link_loads()
    assert observed == expected
    assert fab.load_drift() == {}


@pytest.mark.parametrize("routing", ["dimension", "shortest"])
def test_observed_loads_match_static_on_2d_mesh(routing):
    """Both routing disciplines: static demand == observed, per axis, per
    ring, per direction, on a (4, 2) mesh."""
    mesh = jax.make_mesh((4, 2), ("fx", "fy"))
    fab = Fabric(mesh=mesh, config=FabricConfig(
        frame_phits=2, credits=2, routing=routing))
    _all_to_all(fab, n=8)
    assert fab.load_drift() == {}
    # and the matrices are non-trivial on both axes
    obs_x, obs_y = fab.observed_link_loads()
    assert sum(obs_x.values()) > 0 and sum(obs_y.values()) > 0


@pytest.mark.parametrize("routing", ["dimension", "shortest"])
def test_counters_bit_identical_fused_vs_three_program(routing):
    """The fused one-jit tick and the three-program fallback accumulate the
    SAME counter block bit-for-bit: counters are order-independent event
    counts, so engine choice cannot skew observability."""
    rng = np.random.default_rng(7)
    sends = []
    for s in range(8):
        for _ in range(2):
            d = int(rng.integers(0, 8))
            if d == s:
                continue
            w = rng.integers(0, 256, int(rng.integers(1, 60)),
                             dtype=np.uint8).tobytes()
            sends.append((s, d, w, int(rng.integers(1, 4))))
    cfg = dict(frame_phits=2, credits=2, routing=routing,
               qos_weights=(2, 1))
    totals = []
    for fused in (True, False):
        fab = Fabric(n_ranks=8, config=FabricConfig(fused=fused, **cfg))
        boxes = [fab.mailbox(r) for r in range(8)]
        for s, d, w, lvl in sends:
            boxes[s].send(d, w, list_level=lvl)
        fab.exchange()
        for r in range(8):
            boxes[r].recv()
        totals.append(fab.counters_total())
        assert fab.load_drift() == {}
    assert np.array_equal(totals[0], totals[1])


def test_seeded_misroute_shows_up_as_load_drift():
    """Fault injection: a ``tx_hook`` that rewrites one frame's dst byte
    back to its src (a misroute the static analysis cannot know about)
    must surface as a nonzero static-vs-observed divergence."""
    from repro.fabric.frames import HDR_ROUTE

    def run(hook):
        fab = Fabric(n_ranks=8, config=FabricConfig(frame_phits=2, credits=2))
        fab.tx_hook = hook
        _all_to_all(fab)
        return fab

    identity = run(lambda tx, v: tx)
    assert identity.load_drift() == {}  # hook path itself drifts nothing

    def misroute(tx, tx_valid):
        tx = np.array(tx)
        w = int(tx[1, 0, HDR_ROUTE])
        src = (w >> 24) & 0x7F
        tx[1, 0, HDR_ROUTE] = (w & ~0xFF0000) | (src << 16)
        return tx

    drift = run(misroute).load_drift()
    assert drift  # the misroute is visible as expected != observed
    assert all(exp != obs for exp, obs in drift.values())


def test_recompile_counter_machine_readable_and_flat_after_warmup():
    """Satellite: tick recompiles surface as a labeled counter.  The same
    traffic shape re-exchanged must not grow it (steady-state serving
    never recompiles silently); a new shape bucket adds exactly one."""
    fab = Fabric(n_ranks=8, config=FabricConfig(frame_phits=2, credits=2))

    def recompiles():
        return sum(
            v for k, v in fab.metrics.flat().items()
            if k.startswith("fabric.tick.recompiles")
        )

    for tick in range(3):
        for s in range(4):
            fab.mailbox(s).send((s + 2) % 8, bytes([tick + 1, s]) * 16)
        fab.exchange()
        assert recompiles() == 1, f"tick {tick}"
    fab.mailbox(0).send(1, bytes(4096))  # much longer wire: new bucket
    fab.exchange()
    assert recompiles() == 2


# ---------------------------------------------------------------------------
# trace export
# ---------------------------------------------------------------------------


def test_trace_recorder_emits_valid_chrome_trace(tmp_path):
    tr = TraceRecorder()
    tr.name_track(0, "fabric", tid=1, thread="ticks")
    with span("serve.tick", tr, frames=4):
        tr.instant("chunk.arrive", pid=1, args={"stream": 2})
    tr.counter("inflight", {"frames": 3.0})
    obj = tr.to_json()
    assert validate_trace(obj) == []
    assert obj["displayTimeUnit"] == "ms"
    phases = {e["ph"] for e in obj["traceEvents"]}
    assert {"X", "i", "C", "M"} <= phases
    ev = next(e for e in obj["traceEvents"] if e["ph"] == "X")
    assert ev["dur"] >= 0 and ev["args"]["frames"] == 4
    out = tmp_path / "t.json"
    tr.save(out)
    assert validate_trace(json.loads(out.read_text())) == []
    # bare-list form (what some tools emit) validates too
    assert validate_trace(obj["traceEvents"]) == []
    assert validate_trace({"nope": 1})  # and garbage is rejected


def test_span_writes_recorder_slice_and_profiler_event(tmp_path):
    """One with-block, timed once: the recorder's slice (name, category from
    the first dotted part, args including those added inside the block) and
    the profiler trace's host event under the same plain name."""
    root = str(pathlib.Path(__file__).parents[1])
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.chip import trace_reduce

    tr = TraceRecorder()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with span("serve.tick", tr, tick=3) as sp:
            with span("stream.flush"):
                pass
            sp.args["tokens_arrived"] = 5
    finally:
        jax.profiler.stop_trace()
    (ev,) = tr.events
    assert (ev["name"], ev["ph"], ev["cat"]) == ("serve.tick", "X", "serve")
    assert ev["args"] == {"tick": 3, "tokens_arrived": 5} and ev["dur"] >= 0
    assert validate_trace(tr.to_json()) == []
    host = trace_reduce.load(trace_reduce.find_xplane(str(tmp_path))).host
    names = list(host.names)
    tick, flush = names.index("serve.tick"), names.index("stream.flush")
    assert host.start[tick] <= host.start[flush] and host.end[flush] <= host.end[tick]
    # no recorder, no profiler: a plain with-block, exceptions pass through
    with pytest.raises(KeyError):
        with span("serve.des", wires=2):
            raise KeyError("x")
    # a name outside SPAN_NAMES would be invisible to the trace's readers
    assert {"serve.tick", "stream.flush", "serve.des"} <= SPAN_NAMES
    with pytest.raises(ValueError, match="SPAN_NAMES"):
        span("serve.unlisted")


def test_obs_cli_validates_artifacts(tmp_path):
    from repro.obs.__main__ import main as obs_main

    m = MetricsRegistry()
    m.counter("c").add(1)
    mfile = tmp_path / "m.json"
    mfile.write_text(m.to_json())
    tr = TraceRecorder()
    with span("serve.tick", tr):
        pass
    tfile = tmp_path / "t.json"
    tr.save(tfile)
    assert obs_main([str(mfile), "--validate"]) == 0
    assert obs_main([str(tfile), "--validate"]) == 0
    bad = tmp_path / "bad.json"
    bad.write_text('{"what": 1}')
    assert obs_main([str(bad), "--validate"]) != 0


# ---------------------------------------------------------------------------
# serving-plane telemetry: byte-identity + required series
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def serve_setup():
    from repro.configs import get_config, smoke_config
    from repro.launch.serve import encode_request
    from repro.models import init_params

    cfg = dataclasses.replace(smoke_config(get_config("yi-6b")), n_layers=2)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    wires = []
    for r in range(3):
        prompts = [
            list(map(int, rng.integers(2, cfg.vocab, int(rng.integers(8, 16)))))
            for _ in range(int(rng.integers(1, 3)))
        ]
        wires.append(encode_request(r, prompts))
    return params, cfg, wires


def test_streaming_serve_telemetry_is_byte_invisible(serve_setup):
    """Attaching a full registry + trace recorder to the streamed serve
    loop changes ZERO response bytes, and the snapshot contains the
    acceptance series: TTFT, tokens/s, backpressure p95, fabric frames."""
    from repro.launch.serve import serve_requests_streaming

    params, cfg, wires = serve_setup
    kw = dict(max_new=4, pad_to=8, slots=4, n_shards=2)
    plain = serve_requests_streaming(params, cfg, wires, **kw)
    metrics, trace = MetricsRegistry(), TraceRecorder()
    observed = serve_requests_streaming(
        params, cfg, wires, metrics=metrics, trace=trace, **kw)
    assert observed == plain  # telemetry must never touch tokens
    snap = metrics.snapshot()
    assert validate_snapshot(snap) == []
    names = {m["name"] for m in snap["metrics"]}
    for required in ("serve.ttft_s", "serve.ttft_s.series",
                     "serve.tokens_per_s", "serve.backpressure.p95",
                     "serve.tick.tokens", "serve.tokens",
                     "batcher.admitted", "batcher.occupancy",
                     "batcher.steps", "stream.reader.chunks",
                     "stream.reader.tokens", "fabric.frames.delivered",
                     "fabric.ticks", "serve.shard.device"):
        assert required in names, required
    flat = metrics.flat()
    assert flat["serve.tokens"] > 0
    assert flat["serve.ttft_s.series"]  # at least one first token recorded
    assert validate_trace(trace.to_json()) == []
    ev_names = {e["name"] for e in trace.events}
    assert "serve.tick" in ev_names and "stream.chunk" in ev_names


# ---------------------------------------------------------------------------
# satellites: histogram quantiles, snapshot diff, SLO gates, CLI
# ---------------------------------------------------------------------------


def test_histogram_quantile_pinned_against_exact_samples():
    """Interpolated log2-bucket quantiles vs exact-sample references:
    within one bucket width of the true value, exact at the extremes."""
    import math

    from repro.obs import Histogram

    h = Histogram(base=1.0, n_buckets=16)
    samples = [float(v) for v in range(1, 11)]  # 1..10
    for v in samples:
        h.observe(v)
    assert h.quantile(0.0) == 1.0  # clamps to observed min
    assert h.quantile(1.0) == 10.0  # ...and max
    assert h.quantile(0.5) == 5.0  # pinned: ceil-rank 5 lands mid-bucket
    for q in (0.25, 0.75, 0.9, 0.95):
        exact = samples[min(len(samples) - 1,
                            max(0, math.ceil(q * len(samples)) - 1))]
        got = h.quantile(q)
        lo, hi = 2 ** (math.floor(math.log2(exact))), \
            2 ** (math.ceil(math.log2(exact)) or 1)
        assert lo / 2 <= got <= hi * 2, (q, exact, got)
    assert Histogram().quantile(0.5) is None  # empty -> None
    with pytest.raises(ValueError):
        h.quantile(1.5)


def test_quantile_from_buckets_interpolation_and_overflow():
    from repro.obs import quantile_from_buckets

    # one bucket (1, 2], 4 samples: rank interpolates within the bucket
    assert quantile_from_buckets(1.0, [0, 4], 4, 1.2, 2.0, 0.5) == \
        pytest.approx(1.5)
    # overflow bucket is capped at the observed max, not 2^n
    v = quantile_from_buckets(1.0, [0, 0, 10], 10, 3.0, 6.0, 0.99)
    assert v is not None and v <= 6.0


def test_diff_snapshots_structure_and_render():
    from repro.obs import diff_snapshots, render_diff

    a = MetricsRegistry()
    a.counter("x").add(2)
    a.counter("gone").add(1)
    b = MetricsRegistry()
    b.counter("x").add(3)
    b.gauge("new.g").set(7)
    d = diff_snapshots(a.snapshot(), b.snapshot())
    assert list(d["added"]) == ["new.g"] and list(d["removed"]) == ["gone"]
    assert d["changed"]["x"]["delta"] == 1
    assert d["changed"]["x"]["ratio"] == pytest.approx(1.5)
    text = render_diff(d)
    assert "+ new.g" in text and "- gone" in text and "~ x" in text
    same = diff_snapshots(a.snapshot(), a.snapshot())
    assert not (same["added"] or same["removed"] or same["changed"])
    assert "snapshots agree" in render_diff(same)


def test_evaluate_slo_pass_fail_burn_and_missing_signal():
    from repro.obs import evaluate_slo

    m = MetricsRegistry()
    for v in (0.1, 0.2, 0.3, 0.4):
        m.series("serve.ttft_s.series").append(v)
    m.gauge("serve.tokens_per_s").set(50.0)
    m.gauge("fabric.load_drift.entries").set(0)
    snap = m.snapshot()
    rep = evaluate_slo("ttft_p95_s=0.5,tokens_per_s_min=10,drift_free",
                       snapshot=snap)
    assert rep.ok and not rep.violations()
    by = {r.name: r for r in rep.results}
    assert by["ttft_p95_s"].observed == pytest.approx(0.4)  # ceil-rank p95
    assert by["ttft_p95_s"].burn_rate == pytest.approx(0.8)
    assert by["tokens_per_s_min"].burn_rate == pytest.approx(0.2)
    # violation: burn > 1 and ok=False; missing signal FAILS, never passes
    rep2 = evaluate_slo({"ttft_p95_s": 0.2, "max:absent.metric": 1},
                        snapshot=snap)
    assert not rep2.ok
    by2 = {r.name: r for r in rep2.results}
    assert by2["ttft_p95_s"].burn_rate == pytest.approx(2.0)
    assert by2["max:absent.metric"].observed is None
    assert "VIOLATED" in rep2.render_text()
    # generic flat-key bounds work on plain values dicts (bench metrics)
    rep3 = evaluate_slo("min:fabric.smoke_frames_per_s=10",
                        values={"fabric.smoke_frames_per_s": 100.0})
    assert rep3.ok
    # unknown objectives fail loudly with a hint
    assert not evaluate_slo({"not_a_thing": 1}, snapshot=snap).ok


def test_parse_slo_forms(tmp_path):
    from repro.obs import parse_slo

    assert parse_slo("a=1.5,drift_free") == {"a": 1.5, "drift_free": True}
    assert parse_slo({"k": 2}) == {"k": 2}
    p = tmp_path / "slo.json"
    p.write_text('{"ttft_p95_s": 0.25}')
    assert parse_slo(str(p)) == {"ttft_p95_s": 0.25}
    with pytest.raises(ValueError):
        parse_slo("  ")


def test_obs_cli_diff_slo_attribution_history(tmp_path, capsys):
    from repro.obs.__main__ import main as obs_main

    a = MetricsRegistry()
    a.counter("x").add(1)
    afile = tmp_path / "a.json"
    afile.write_text(a.to_json())
    b = MetricsRegistry()
    b.counter("x").add(5)
    bfile = tmp_path / "b.json"
    bfile.write_text(b.to_json())
    assert obs_main(["diff", str(afile), str(bfile)]) == 0
    assert obs_main(["diff", str(afile), str(bfile),
                     "--fail-on-change"]) == 1
    assert obs_main(["diff", str(afile), str(afile),
                     "--fail-on-change"]) == 0
    # slo: exit 0 on pass, 1 on violation
    assert obs_main(["slo", "max:x=10", "--metrics", str(afile)]) == 0
    assert obs_main(["slo", "max:x=0.5", "--metrics", str(bfile)]) == 1
    # attribution: render a spans export
    from repro.obs import SpanTracker

    sp = SpanTracker()
    sp.set_tick(0)
    rid = sp.start("request", cls=1)
    sp.event(rid, "serve.ingress")
    sp.add_component(rid, "fabric.transit", 3)
    sp.set_tick(2)
    sp.event(rid, "serve.first_token")
    sp.finish(rid)
    sfile = tmp_path / "spans.json"
    sfile.write_text(json.dumps(sp.export()))
    assert obs_main(["attribution", str(sfile)]) == 0
    out = capsys.readouterr().out
    assert "ttft_ticks" in out and "request attribution" in out
    # history: tabulate bench_history.jsonl rows
    hfile = tmp_path / "hist.jsonl"
    hfile.write_text(
        json.dumps({"git_sha": "abc123def456", "timestamp": "t0",
                    "metrics": {"fabric": {"smoke_frames_per_s": 1000.0}}})
        + "\n"
        + json.dumps({"git_sha": "def456abc789", "timestamp": "t1",
                      "metrics": {"fabric": {"smoke_frames_per_s": 1100.0}}})
        + "\n")
    assert obs_main(["history", str(hfile)]) == 0
    out = capsys.readouterr().out
    assert "2 run(s)" in out and "fabric.smoke_frames_per_s" in out
    assert obs_main(["history", str(tmp_path / "missing.jsonl")]) == 2
    # the legacy single-file form is untouched by subcommand dispatch
    assert obs_main([str(afile), "--validate"]) == 0


def test_batcher_metrics_admit_evict_occupancy(serve_setup):
    from repro.runtime.scheduler import ContinuousBatcher, SchedulerConfig

    params, cfg, _ = serve_setup
    m = MetricsRegistry()
    b = ContinuousBatcher(
        params, cfg, SchedulerConfig(slots=2, prompt_cap=8, max_new=2),
        metrics=m)
    for i in range(3):
        b.submit(i, list(range(2, 8)))
    out = b.run()
    assert len(out) == 3
    flat = m.flat()
    assert flat["batcher.admitted"] == 3
    assert flat["batcher.evicted"] == 3
    assert flat["batcher.steps"] == b.steps_run
    # gauges reflect the LAST dispatched tick: the straggler ran alone
    # with nothing left queued
    assert flat["batcher.occupancy"] == 1
    assert flat["batcher.queue_depth"] == 0
