"""Serving driver: the batched HGum message plane + continuous batching.

Requests arrive as HGum-serialized wires (``request_schema`` — a List of
prompts with unknown lengths, the paper's List case).  Request paths:
``serve_requests`` (local batched plane), ``serve_requests_sharded``
(whole-response wires over the routed fabric), ``serve_requests_streaming``
(token chunks stream back every decode tick, async fabric/compute overlap,
per-tenant QoS levels), and the seed ``serve_request`` baseline.  The first
two are documented below:

* **Batched plane (default)** — ``serve_requests`` takes MANY request wires
  at once.  One *batched structure pass* (``core.vectorized.batch_plans``)
  walks the schema a single time with per-message cursor columns and yields
  a ``BatchedDecodePlan`` with a leading message axis; one gather per leaf
  path (``decode_batch``) then decodes every payload of every message.  The
  reconstructed prompts feed ``runtime.scheduler.ContinuousBatcher`` — a
  fixed-slot KV cache with per-step admit/evict and *cached* jitted
  prefill/decode steps — and all responses are serialized back through the
  HW->SW SerFSM in bulk (one schema ROM shared across the batch, counts
  after elements so the host parses from the end — paper §IV-B).
* **Sequential path (seed baseline)** — ``serve_request`` answers one wire
  at a time with a fresh ROM walk, a streaming-FSM DES, and per-request
  ``jax.jit``.  Kept verbatim so ``benchmarks/bench_serve.py`` measures the
  batched plane against it.

Scheduler knobs (see ``runtime.scheduler.SchedulerConfig``):

* ``slots``      — concurrent sequences / KV-cache rows (decode batch width)
* ``prompt_cap`` — static prompt pad length (``--pad-to``)
* ``max_new``    — greedy tokens per sequence
* ``admit_cap``  — prefill width per scheduler tick (default: ``slots``)

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --smoke \
      --n-requests 8 --n-prompts 4 --max-new 16 --slots 8
"""
from __future__ import annotations

import argparse
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config, smoke_config
from ..core import (
    DesFSM,
    SerFSM,
    batch_plans,
    build_rom,
    decode_batch,
    des_hw_to_sw,
    lanes_to_int,
    msg_to_des_tokens,
    ser_sw_to_hw,
    stack_wires,
    strip_for_ser,
    tokens_to_msg,
)
from ..data.schemas import request_schema, response_schema
from ..models import init_params
from ..runtime.scheduler import ContinuousBatcher, SchedulerConfig
from .compile_cache import use_compile_cache
from .steps import make_prefill_step, make_serve_step


def encode_request(req_id: int, prompts: List[List[int]]) -> bytes:
    schema = request_schema()
    msg = {"req_id": req_id, "prompts": [{"tokens": p} for p in prompts]}
    return ser_sw_to_hw(schema, msg)


def decode_request(wire: bytes) -> Tuple[int, List[List[int]]]:
    """Hardware-side DES of ONE request (streaming FSM engine — seed path)."""
    schema = request_schema()
    rom = build_rom(schema)
    res = DesFSM(rom, "sw2hw").run(wire)
    msg = tokens_to_msg(schema, res.tokens)
    return msg["req_id"], [p["tokens"] for p in msg["prompts"]]


#: the request leaves the serve plane consumes; skipping the outer
#: 'prompts' count leaf drops one gather from the request hot path
REQUEST_PATHS = ["req_id", "prompts.elem.tokens", "prompts.elem.tokens.elem"]


def decode_request_batch(wires: List[bytes]) -> List[Tuple[int, List[List[int]]]]:
    """Batched DES of N request wires: one schema walk + one gather per leaf."""
    bplan = batch_plans(request_schema(), wires, record_paths=REQUEST_PATHS)
    vals = decode_batch(jnp.asarray(stack_wires(wires)), bplan)
    return requests_from_lanes(vals, bplan)


def requests_from_lanes(vals, bplan) -> List[Tuple[int, List[List[int]]]]:
    """(req_id, prompts) per message from the decoded :data:`REQUEST_PATHS`
    lanes (jnp ``decode_batch`` or the Pallas ``decode_batch_kernel``).

    The per-prompt lengths are read from the decoded *count fields* of the
    inner token lists (container paths decode like u32 leaves), so splitting
    the flat token column back into prompts needs no second walk.
    """
    rid_lanes = np.asarray(vals["req_id"])  # (N, 1, 2)
    len_lanes = np.asarray(vals["prompts.elem.tokens"])  # (N, capP, 1)
    tok_lanes = np.asarray(vals["prompts.elem.tokens.elem"])  # (N, capT, 1)
    out = []
    for m in range(bplan.n_messages):
        rid = int(lanes_to_int(rid_lanes[m], 8)[0])
        n_prompts = int(bplan.counts["prompts.elem.tokens"][m])
        n_toks = int(bplan.counts["prompts.elem.tokens.elem"][m])
        lens = len_lanes[m, :n_prompts, 0].astype(np.int64)
        toks = tok_lanes[m, :n_toks, 0]
        splits = np.split(toks, np.cumsum(lens)[:-1]) if n_prompts else []
        out.append((rid, [list(map(int, p)) for p in splits]))
    return out


def encode_response(req_id: int, outputs: List[List[int]]) -> bytes:
    """Hardware-side SER (HW->SW: counts after elements)."""
    return encode_response_batch([(req_id, outputs)])[0]


def encode_response_batch(
    responses: List[Tuple[int, List[List[int]]]]
) -> List[bytes]:
    """Bulk HW->SW SER: one schema ROM shared by every response wire."""
    schema = response_schema()
    rom = build_rom(schema)
    wires = []
    for req_id, outputs in responses:
        msg = {"req_id": req_id, "outputs": [{"tokens": o} for o in outputs]}
        toks = strip_for_ser(msg_to_des_tokens(schema, msg))
        wires.append(SerFSM(rom, "hw2sw").run(toks).wire)
    return wires


def decode_response(wire: bytes) -> Tuple[int, List[List[int]]]:
    schema = response_schema()
    msg = des_hw_to_sw(schema, wire)
    return msg["req_id"], [o["tokens"] for o in msg["outputs"]]


# ---------------------------------------------------------------------------
# Sequential path — the seed's one-wire-at-a-time loop (benchmark baseline)
# ---------------------------------------------------------------------------


def serve_request(
    params, cfg, wire: bytes, max_new: int = 16, pad_to: int = 64
) -> bytes:
    """Answer ONE request wire (seed baseline: per-request ROM walk + jit)."""
    req_id, prompts = decode_request(wire)
    if not prompts:  # zero-prompt request: nothing to generate
        return encode_response(req_id, [])
    B = len(prompts)
    max_len = max(len(p) for p in prompts)
    S = min(pad_to, max(8, max_len))
    toks = np.zeros((B, S), np.int32)
    for i, p in enumerate(prompts):
        toks[i, : min(len(p), S)] = p[:S]
    batch = {"tokens": jnp.asarray(toks)}
    if cfg.family == "vlm":
        batch["vision"] = jnp.zeros((B, cfg.vision_tokens, cfg.vision_dim), jnp.float32)
    if cfg.family == "encdec":
        batch["audio"] = jnp.zeros((B, cfg.enc_seq, cfg.d_model), jnp.float32)

    prefill_step = jax.jit(make_prefill_step(cfg, cache_len=S + max_new))
    serve_step = jax.jit(make_serve_step(cfg), donate_argnums=(1,))
    next_tok, cache = prefill_step(params, batch)
    out_tokens = [next_tok]
    tok = next_tok
    for _ in range(max_new - 1):
        tok, cache = serve_step(params, cache, tok)
        out_tokens.append(tok)
    gen = np.concatenate([np.asarray(t) for t in out_tokens], axis=1)  # (B, max_new)
    outputs = [list(map(int, gen[i])) for i in range(B)]
    return encode_response(req_id, outputs)


# ---------------------------------------------------------------------------
# Batched plane — many wires in, many wires out
# ---------------------------------------------------------------------------


def serve_requests(
    params,
    cfg,
    wires: List[bytes],
    max_new: int = 16,
    pad_to: int = 64,
    slots: int = 8,
    admit_cap: Optional[int] = None,
) -> List[bytes]:
    """Answer N request wires through the batched message plane.

    Batched structure pass -> one gather per leaf -> continuous-batching
    generate -> bulk SER.  Responses come back in request order; a request
    with zero prompts yields an empty-outputs response wire.

    Padding semantics: every prompt is padded/truncated to the static
    ``pad_to`` (fixed KV slots need one shape), whereas the seed's
    ``serve_request`` picks ``min(pad_to, max(8, longest prompt))`` per
    request — so the two paths emit identical tokens exactly when prompts
    are >= ``pad_to`` long (both truncate to ``pad_to``).
    """
    reqs = decode_request_batch(wires)
    sched = SchedulerConfig(
        slots=slots, prompt_cap=pad_to, max_new=max_new, admit_cap=admit_cap
    )
    batcher = ContinuousBatcher(params, cfg, sched)
    for m, (_, prompts) in enumerate(reqs):
        for i, p in enumerate(prompts):
            batcher.submit((m, i), p)
    outs = batcher.run()
    responses = [
        (rid, [outs[(m, i)] for i in range(len(prompts))])
        for m, (rid, prompts) in enumerate(reqs)
    ]
    return encode_response_batch(responses)


# ---------------------------------------------------------------------------
# Sharded plane — requests routed over the message fabric to per-shard
# batchers (ISSUE 2); composes the batched plane with repro.fabric
# ---------------------------------------------------------------------------


def place_requests(
    router,
    n_requests: int,
    shards: List[int],
    capacity: int,
    weights: Optional[List[int]] = None,
    exclude=(),
) -> List[int]:
    """Topology-aware ingress placement (ROADMAP item): requests go to the
    nearest shard with free capacity instead of round-robin.

    Shards are ordered by round-trip fabric distance from the ingress
    (``Router.route_hops(0, s) + route_hops(s, 0)`` — request path plus
    response/stream return path, measured under the router's configured
    routing mode so placement stays consistent with the paths frames
    actually take: ``min_hops`` under shortest-path routing, +1-ring
    ``hops`` under dimension-order); each request takes the nearest shard
    whose load is still under ``capacity``, spilling to the next nearest
    when full.  When every shard is full, the least-loaded (nearest first)
    takes the overflow.  ``weights`` measures each request's load — pass
    per-request sequence counts with ``capacity`` = KV slots so "free"
    means free *decode slots* (the streaming ingress does; default: one
    unit per request).  Under +1-ring routing a 1D round trip is the same
    length from every shard; under shortest-path routing the round trip is
    ``2 * min_hops``, so near ranks genuinely cost less and placement
    prefers them.  Placement cannot change tokens — rows decode
    independently — only how far each request's wires travel.

    ``exclude`` removes shards from consideration entirely — the serve
    plane passes its *suspect* set (ranks that stopped ACKing) so
    neither fresh placement nor a retry ever lands on a rank believed
    dead.  Excluding every shard raises rather than silently placing on
    a suspect.
    """
    live = [s for s in shards if s not in exclude]
    if not live:
        raise ValueError(
            f"no healthy shard to place on: all of {sorted(shards)} are "
            f"excluded (suspect)"
        )
    order = sorted(
        live,
        key=lambda s: (router.route_hops(0, s) + router.route_hops(s, 0), s),
    )
    w = weights if weights is not None else [1] * n_requests
    load = {s: 0 for s in order}
    placement = []
    for i in range(n_requests):
        free = [s for s in order if load[s] < capacity]
        s = free[0] if free else min(order, key=lambda t: load[t])
        placement.append(s)
        load[s] += max(1, w[i])
    return placement


def _analyze_serve(fabric, n_requests: int, context: str) -> None:
    """The ``analyze=True`` serve hook: statically prove the serving
    schemas, the fabric config + topology, and the stream-id budget safe
    before any request crosses a link — raising on ERROR findings with the
    rule's fix hint.  Also arms the fabric's per-tick demand analysis."""
    from ..analysis import analyze_schema, assert_clean, finding
    from ..analysis.fabric_passes import analyze_fabric
    from ..data.schemas import request_schema, response_schema
    from ..stream.chunks import STREAM_ID_BITS

    fs = analyze_schema(request_schema(), location=f"{context}.request")
    fs += analyze_schema(response_schema(), location=f"{context}.response")
    fs += analyze_fabric(fabric, location=f"{context}.fabric")
    if n_requests >= (1 << STREAM_ID_BITS):
        fs.append(finding(
            "stream-id-width", context,
            f"{n_requests} requests overflow the u{STREAM_ID_BITS} "
            f"request lane of the (request | prompt) stream-id packing",
        ))
    assert_clean(fs, context)
    fabric.analyze = True  # per-tick demand checks from here on


def default_serve_fabric(
    n_shards: Optional[int] = None, routing: str = "shortest",
    defect_after: int = 0, analyze: bool = False, arq: bool = True,
    faults=None,
):
    """The fabric ``serve_requests_sharded`` builds when none is passed:
    rank 0 ingress plus up to 7 serving shards on the available devices,
    shortest-path routed with the fused single-jit tick (pass
    ``routing="dimension"`` for the legacy +1-ring discipline).
    ``defect_after=k`` enables congestion-aware direction defection: a
    frame whose preferred ring direction has been credit-starved for k
    consecutive router steps escapes to the other direction.

    ``arq=True`` (the serving default) turns on reliable delivery: every
    request/response/chunk message is retransmit-buffered and recovered
    on NACK or timeout, so seeded chaos (``faults`` — a
    ``fabric.faults.FaultPlan``, e.g. from ``parse_chaos``) degrades
    latency instead of correctness.  ``arq=False`` is the escape hatch
    back to flag-only delivery.
    Returns None when fewer than 2 ranks fit (no shard to route to)."""
    from ..fabric import Fabric, FabricConfig

    n_devices = len(jax.devices())
    n_ranks = (n_shards + 1) if n_shards else min(n_devices, 8)
    if n_ranks > n_devices:
        raise ValueError(
            f"n_shards={n_shards} needs {n_ranks} devices (shards + ingress) "
            f"but only {n_devices} are visible — set XLA_FLAGS="
            f"--xla_force_host_platform_device_count or lower n_shards"
        )
    if n_ranks < 2:
        return None
    fab = Fabric(
        n_ranks=n_ranks,
        config=FabricConfig(frame_phits=16, routing=routing,
                            defect_after=defect_after, arq=arq),
        analyze=analyze,
    )
    fab.faults = faults
    return fab


def _require_shards(fabric, caller: str) -> None:
    """The routed planes need an ingress plus at least one shard."""
    ranks = 0 if fabric is None else fabric.n_ranks
    if ranks < 2:
        raise ValueError(
            f"{caller} needs a fabric of >= 2 ranks (ingress + shards), got "
            f"{ranks} ({len(jax.devices())} device(s) visible); serve on one "
            f"device with serve_requests"
        )


def serve_requests_sharded(
    params,
    cfg,
    wires: List[bytes],
    max_new: int = 16,
    pad_to: int = 64,
    slots: int = 8,
    admit_cap: Optional[int] = None,
    n_shards: Optional[int] = None,
    fabric=None,
    placement: Optional[List[int]] = None,
    routing: str = "shortest",
    defect_after: int = 0,
    analyze: bool = False,
    metrics=None,
    trace=None,
    suspect_after: Optional[int] = 24,
    deadline_ticks: Optional[int] = None,
) -> List[bytes]:
    """Answer N request wires across fabric-connected serving shards.

    Rank 0 is the *ingress*: it routes each request wire over the message
    fabric (``repro.fabric``) to one of the serving shards (ranks 1..R-1,
    nearest free shard first — ``place_requests``; pass ``placement`` to
    pin requests to shards), every shard answers its share through the batched plane
    (``serve_requests`` — batched DES, ContinuousBatcher, bulk SER), and the
    response wires ride the fabric back to the ingress, which restores
    request order.  Requests and responses cross the links as routed framed
    Lists with CRC32 per frame; responses from shard ``s`` take the
    multi-hop return path (``R - s`` ring hops).

    Token-identical to ``serve_requests`` on the same wires: both pad every
    prompt to the static ``pad_to``, and rows decode independently, so shard
    placement cannot change the greedy outputs.

    Failure awareness (requires an ARQ fabric, the ``default_serve_fabric``
    default): the loop keeps ticking until every request is answered or
    ``deadline_ticks`` fabric ticks elapse (default 256 with ARQ; exactly
    the legacy 2-exchange schedule without it), and a shard the ingress has
    not heard from — no data, no ACKs — for more than ``suspect_after``
    ticks while it still owes responses is marked *suspect*: its
    outstanding requests are re-placed once onto healthy shards
    (``place_requests(..., exclude=suspects)``).  Rows decode
    independently and greedily, so a retried request re-decodes to the
    same bytes and the answer stays byte-identical; a request whose retry
    also dies raises.  ``suspect_after=None`` disables the detector.

    Raises ``ValueError`` when the fabric has fewer than 2 ranks (no shard
    to route to); one device serves through ``serve_requests``.
    """
    if fabric is None:
        fabric = default_serve_fabric(n_shards, routing=routing,
                                      defect_after=defect_after)
    _require_shards(fabric, "serve_requests_sharded")
    if metrics is not None:
        fabric.metrics = metrics
    if trace is not None:
        fabric.trace = trace
    if analyze:
        _analyze_serve(fabric, len(wires), "serve_requests_sharded")
    shards = list(range(1, fabric.n_ranks))
    ingress = fabric.mailbox(0)
    if placement is None:
        placement = place_requests(
            fabric.router, len(wires), shards, capacity=max(1, slots)
        )

    # ingress -> shards: route the raw request wires.  queue[s] is the
    # FIFO of global request indices shard s owes responses for — every
    # (src, dst) stream delivers in order (ARQ enforces it under faults),
    # so the k-th response arriving from s answers queue[s][k]
    queue: Dict[int, List[int]] = {s: [] for s in shards}
    for i, w in enumerate(wires):
        queue[placement[i]].append(i)
        ingress.send(placement[i], w)

    arq = bool(fabric.config.arq)
    watch = arq and suspect_after is not None
    max_ticks = (deadline_ticks or 256) if arq else 3
    t0_tick = fabric.ticks if arq else 0
    answered: Dict[int, bytes] = {}
    cursor = {s: 0 for s in shards}
    suspects: set = set()
    retried: set = set()
    wait_since: Dict[int, int] = {}  # shard -> tick its current debt began
    for _ in range(max_ticks):
        fabric.exchange()
        # each shard answers newly arrived request wires through the
        # batched plane and sends the response wires back
        for s in shards:
            box = fabric.mailbox(s)
            arrived = box.recv()
            if s in suspects or not arrived:
                continue
            bad = [d.src for d in arrived if not d.ok]
            if bad:
                raise RuntimeError(
                    f"shard {s}: corrupt request frames from {bad}")
            resp = serve_requests(
                params, cfg, [d.wire for d in arrived], max_new=max_new,
                pad_to=pad_to, slots=slots, admit_cap=admit_cap,
            )
            for rw in resp:
                box.send(0, rw)
        # ingress: responses arrive per-shard in FIFO order; undo the
        # placement.  setdefault: when a slow shard was wrongly suspected,
        # the FIRST answer (original or retry) wins — both are identical
        for d in ingress.recv():
            if not d.ok:
                raise RuntimeError(
                    f"ingress: corrupt response frames from {d.src}")
            i = queue[d.src][cursor[d.src]]
            cursor[d.src] += 1
            answered.setdefault(i, d.wire)
        if len(answered) == len(wires):
            break
        if not watch:
            continue
        for s in shards:
            if s in suspects:
                continue
            outstanding = [i for i in queue[s][cursor[s]:]
                           if i not in answered]
            if not outstanding:
                wait_since.pop(s, None)
                continue  # a shard that owes nothing goes quiet, fine
            # the horizon starts when the shard last spoke OR when its
            # current debt began, whichever is later — a shard that sat
            # idle before being handed a retry is not late
            since = wait_since.setdefault(s, fabric.ticks)
            heard = fabric.ticks_since_heard(0, s)
            waited = (fabric.ticks - t0_tick) if heard is None else heard
            waited = min(waited, fabric.ticks - since)
            if waited <= suspect_after:
                continue
            # rank s stopped ACKing with responses outstanding: mark it
            # suspect and retry its in-flight requests elsewhere, once
            suspects.add(s)
            # the fabric registry is always on (and IS `metrics` when one
            # was passed), so recovery stays observable either way
            fabric.metrics.counter("serve.suspects").add(1)
            twice = [i for i in outstanding if i in retried]
            if twice:
                raise RuntimeError(
                    f"sharded serve: request(s) {twice} failed on shard "
                    f"{s} after a retry — no healthy shard answered")
            repl = place_requests(
                fabric.router, len(outstanding), shards,
                capacity=max(1, slots), exclude=suspects)
            for i, s2 in zip(outstanding, repl):
                retried.add(i)
                queue[s2].append(i)
                ingress.send(s2, wires[i])
                fabric.metrics.counter("serve.retries").add(1)
    if len(answered) < len(wires):
        missing = sorted(i for i in range(len(wires)) if i not in answered)
        raise RuntimeError(
            f"sharded serve: {len(missing)} request(s) unanswered after "
            f"{max_ticks} fabric ticks (missing {missing[:8]})")
    out = [answered[i] for i in range(len(wires))]
    if metrics is not None:
        metrics.gauge("fabric.load_drift.entries").set(
            len(fabric.load_drift())
        )
    return out


# ---------------------------------------------------------------------------
# Streaming plane — tokens leave each shard the tick they decode (ISSUE 3);
# composes the batched compute plane with repro.stream over repro.fabric
# ---------------------------------------------------------------------------

#: ListLevel reserved for the typed logprob side-stream when
#: ``serve_requests_streaming(logprobs=True)`` — the ingress partitions
#: deliveries between the token reader and the logprob reader by this tag,
#: so tenant QoS levels must stay below it (254 itself stays clear of the
#: fabric's ``FabricConfig.arq_level`` control class, 255)
LOGPROB_STREAM_LEVEL = 254


def serve_requests_streaming(
    params,
    cfg,
    wires: List[bytes],
    max_new: int = 16,
    pad_to: int = 64,
    slots: int = 8,
    admit_cap: Optional[int] = None,
    n_shards: Optional[int] = None,
    fabric=None,
    placement: Optional[List[int]] = None,
    qos_levels: Optional[List[int]] = None,
    overlap: bool = True,
    on_token=None,
    on_event=None,
    routing: str = "shortest",
    defect_after: int = 0,
    backpressure_p95: Optional[float] = None,
    backpressure_chunks: int = 1,
    backpressure_hold: int = 3,
    analyze: bool = False,
    metrics=None,
    trace=None,
    spans=None,
    suspect_after: Optional[int] = 24,
    deadline_ticks: Optional[int] = None,
    logprobs: bool = False,
    on_logprob=None,
) -> List[bytes]:
    """Answer N request wires with token-level streamed responses.

    Same placement and compute as ``serve_requests_sharded`` — rank-0
    ingress, nearest-free-shard placement, one ContinuousBatcher per shard
    — but the response path streams: every decode tick, each shard writes
    the step's tokens into per-sequence ``StreamWriter``s and one
    ``ChunkLane`` burst per (shard, tenant) rides the fabric back, so the
    ingress sees each token one fabric tick after it decodes instead of
    after the whole generation.  ``on_token(req_idx, prompt_idx, step,
    token)`` fires as tokens arrive (time-to-first-token = first admit tick
    + one exchange).

    With ``overlap=True`` (default) the fabric and compute pipelines run
    double-buffered: each tick dispatches the batched decode
    (``ContinuousBatcher.step_begin``), reaps the PREVIOUS tick's routed
    chunks while the decode executes (``Fabric.poll``), syncs the decode
    (``step_finish``), and dispatches the new bursts without waiting
    (``Fabric.exchange_async``) — multi-hop latency hides behind decode
    steps.  ``overlap=False`` runs the same ticks synchronously (chunks
    arrive one tick earlier; tokens identical either way).

    ``qos_levels`` tags each request's stream chunks with a ListLevel (the
    tenant's QoS class when the fabric is built with
    ``FabricConfig.qos_weights``); default: level 1 for everyone.
    ``on_event(StreamEvent)`` fires per arriving chunk with the raw stream
    event (including ``arrive_step``, the router scan step its carrying
    message arrived at — benchmarks use it to measure time-to-token
    jitter); ``routing`` picks the fabric's routing mode when no ``fabric``
    is passed, and ``defect_after=k`` additionally lets a credit-starved
    frame defect to the opposite ring direction after k starved router
    steps (congestion-aware routing).

    ``backpressure_p95`` closes the latency feedback loop: every tick the
    ingress reader's per-QoS-class arrive-step percentiles
    (``StreamReader.class_arrive_stats``, sliding window) feed back into
    each shard's ``ChunkLane``; a lane whose class p95 exceeds the
    threshold clamps its flush rate — it *trickles* ``backpressure_chunks``
    chunks per tick (default 1) and holds the rest — so its WRR credit
    quota spills to the healthy tenants and a stalled tenant stops
    inflating everyone else's queues.  ``backpressure_chunks=0`` holds
    entirely instead of trickling, with ``backpressure_hold`` bounding the
    consecutive fully-held flushes so a stream can never stall forever.
    Held chunks ride later bursts in order; the streamed tokens and the
    final wires are identical with backpressure on or off.

    Returns the final response wires, byte-identical to ``serve_requests``
    on the same inputs (the streamed tokens are re-serialized through the
    same bulk SER).  Raises ``ValueError`` when the fabric has fewer than 2
    ranks; one device serves through ``serve_requests``.

    ``metrics`` (an ``obs.metrics.MetricsRegistry``) turns on serve-level
    telemetry — per-stream TTFT (``serve.ttft_s``), per-tick token rate
    (``serve.tick.tokens`` + the final ``serve.tokens_per_s`` gauge), and
    the per-class backpressure feedback values (``serve.backpressure.p95``)
    — and is shared with the fabric, the batchers, the lanes, and the
    reader, so one ``snapshot()`` covers the whole stack.  ``trace`` (an
    ``obs.trace.TraceRecorder``) records the tick/chunk/recompile
    timeline.  ``spans`` (an ``obs.spans.SpanTracker``; auto-created when
    a ``trace`` is given) mints one request id per request wire at
    ingress and follows it through mailbox deliveries, batcher
    admit/evict, lane first-flush and first-token — the end-to-end causal
    arc the attribution report breaks down.  All three are
    observation-only: tokens and final wires are byte-identical with or
    without them (property-tested).

    Failure awareness (requires an ARQ fabric, the ``default_serve_fabric``
    default): a shard the ingress has not heard from — no chunks, no ACKs
    — for more than ``suspect_after`` fabric ticks while it still owes
    live streams is marked *suspect*.  Its batcher and lanes are dropped,
    its unfinished streams abandoned, and every request that had not
    fully streamed there is re-sent once to a healthy shard
    (``place_requests(..., exclude=suspects)``), where it re-decodes from
    scratch and re-streams under fresh stream ids; greedy decode makes
    the retried tokens — and therefore the final wires — byte-identical
    to an undisturbed run.  Each retry leg is visible as a
    ``serve.retry`` span event plus ``serve.retries``/``serve.suspects``
    counters.  A request whose retry shard also dies raises.  When no
    compute remains, the loop keeps draining in-flight chunks for up to
    ``deadline_ticks`` fabric ticks (default 256 with ARQ; the legacy 3
    without) before declaring the missing streams lost.
    ``suspect_after=None`` disables the detector.

    ``logprobs=True`` attaches the *second typed stream*: per-token
    logprobs as the schema-declared ``Stream<Struct{tok, logprob}>``
    (``stream.chunks.LOGPROB_STREAM_SCHEMA_JSON``), generated by
    ``core.stream_plans`` with no hand-written codec.  Each shard runs
    one extra ``ChunkLane`` on the reserved :data:`LOGPROB_STREAM_LEVEL`
    ListLevel carrying ``(token, float32-bit-pattern)`` elements, and the
    ingress demultiplexes it through a second plan-parametric
    ``StreamReader``.  ``on_logprob(req_idx, prompt_idx, step, token,
    logprob)`` fires per element.  The greedy pick is computed exactly as
    without logprobs, so tokens — and the returned wires — are
    byte-identical with or without the extra stream attached (CI gates
    on this).
    """
    from ..stream import ChunkLane, StreamReader, logprob_stream_plan

    if fabric is None:
        fabric = default_serve_fabric(n_shards, routing=routing,
                                      defect_after=defect_after)
    _require_shards(fabric, "serve_requests_streaming")
    if metrics is not None:
        fabric.metrics = metrics  # one registry across the whole stack
    if trace is not None:
        fabric.trace = trace
        if spans is None:
            from ..obs import SpanTracker

            spans = SpanTracker(trace)
    if spans is not None:
        fabric.spans = spans  # deliveries correlate back to request ids
        spans.set_tick(0)
    if analyze:
        _analyze_serve(fabric, len(wires), "serve_requests_streaming")
    shards = list(range(1, fabric.n_ranks))
    ingress = fabric.mailbox(0)
    reqs = decode_request_batch(wires)  # ingress keeps rids + prompt counts
    if placement is None:
        # the ingress already decoded the burst, so placement can weigh each
        # request by its sequence count: "free" = free KV slots, not
        # request headroom
        placement = place_requests(
            fabric.router, len(wires), shards, capacity=max(1, slots),
            weights=[len(p) for _, p in reqs],
        )
    levels = list(qos_levels) if qos_levels is not None else [1] * len(wires)
    if logprobs and any(lvl >= LOGPROB_STREAM_LEVEL for lvl in levels):
        raise ValueError(
            f"qos_levels must stay below the reserved logprob stream "
            f"level {LOGPROB_STREAM_LEVEL} when logprobs=True"
        )

    # ingress -> shards: mint one span per request at tick 0 and route the
    # raw request wires, each tagged with its request id so every fabric
    # delivery it causes correlates back to the span
    rid_of: List[Optional[int]] = [None] * len(wires)
    for i, w in enumerate(wires):
        if spans is not None:
            rid_of[i] = spans.start("request", req=i, cls=levels[i],
                                    shard=placement[i])
            spans.event(rid_of[i], "serve.ingress", shard=placement[i])
        ingress.send(placement[i], w, list_level=levels[i],
                     request_id=rid_of[i])
    fabric.exchange()

    # shard setup: per-shard batcher + per-sequence stream writers.  The
    # k-th delivery at shard s is the k-th entry of globals_of[s]
    # (per-source FIFO; ARQ keeps it true under faults), which maps
    # shard-local stream ids back to global requests — retried requests
    # are appended to globals_of at re-send time, preserving the map.
    arq = bool(fabric.config.arq)
    watch = arq and suspect_after is not None
    t0_tick = fabric.ticks if arq else 0
    globals_of = {s: [i for i, p in enumerate(placement) if p == s]
                  for s in shards}
    sched = SchedulerConfig(
        slots=slots, prompt_cap=pad_to, max_new=max_new, admit_cap=admit_cap
    )
    batchers: Dict[int, ContinuousBatcher] = {}
    lanes: Dict[Tuple[int, int], ChunkLane] = {}
    writers: Dict[Tuple[int, int, int], object] = {}
    expected = []  # (src shard, stream_id) keys the reader must close
    # corrupt deliveries on an ARQ fabric mean the link already gave up
    # retransmitting (skip) — drop them and let the suspect machinery
    # re-place the request instead of poisoning the stream
    reader = StreamReader(metrics=metrics, spans=spans,
                          on_corrupt="retry" if arq else "flag")
    # second typed stream: the schema-declared logprob plan gets its own
    # reader (streams are keyed (src, stream_id) per reader; the reserved
    # ListLevel partitions deliveries between the two planes).  Span
    # accounting stays on the token reader — one open-stream count per
    # request, not two.
    lp_reader = (
        StreamReader(metrics=metrics, plan=logprob_stream_plan(),
                     on_corrupt="retry" if arq else "flag")
        if logprobs else None
    )
    lp_writers: Dict[Tuple[int, int, int], object] = {}
    open_streams: Dict[int, int] = {}  # rid -> streams not yet at EOS
    admitted = {s: 0 for s in shards}  # request wires admitted at s
    suspects: set = set()
    retried: set = set()
    abandoned: set = set()  # (src, stream_id) keys of dead streams

    def _admit(s: int) -> None:
        # admit newly arrived request wires at shard s into its (possibly
        # new) batcher — runs at setup and once per tick, so a retried
        # request re-routed to s mid-serve joins its continuous batch
        # exactly like an initial one
        box = fabric.mailbox(s)
        arrived = box.recv()
        if not arrived:
            return
        bad = [d.src for d in arrived if not d.ok]
        if bad:
            raise RuntimeError(f"shard {s}: corrupt request frames from {bad}")
        local_reqs = decode_request_batch([d.wire for d in arrived])
        batcher = batchers.get(s)
        if batcher is None:
            batcher = ContinuousBatcher(params, cfg, sched, metrics=metrics,
                                        spans=spans, logprobs=logprobs)
            batchers[s] = batcher
            if metrics is not None:
                # the device holding this shard's slot cache, where its
                # prefill and decode steps run
                (dev,) = jax.tree.leaves(batcher.cache)[0].devices()
                metrics.gauge("serve.shard.device", shard=s).set(dev.id)
        for d, (_, prompts) in zip(arrived, local_reqs):
            k = admitted[s]
            admitted[s] += 1
            lvl = levels[globals_of[s][k]]
            lane = lanes.setdefault(
                (s, lvl),
                ChunkLane(box, 0, list_level=lvl,
                          p95_threshold=backpressure_p95,
                          clamp_chunks=backpressure_chunks,
                          max_hold=backpressure_hold,
                          metrics=metrics),
            )
            lane.spans = spans
            if logprobs:
                lp_lane = lanes.setdefault(
                    (s, LOGPROB_STREAM_LEVEL),
                    ChunkLane(box, 0, list_level=LOGPROB_STREAM_LEVEL,
                              plan=logprob_stream_plan(), metrics=metrics),
                )
            rid = d.request_id if spans is not None else None
            for j, p in enumerate(prompts):
                batcher.submit((k, j), p)
                sid = (k << 16) | j
                writers[(s, k, j)] = lane.writer(sid)
                if logprobs:
                    lp_writers[(s, k, j)] = lp_lane.writer(sid)
                expected.append((s, sid))
                if rid is not None:
                    batcher.span_of[(k, j)] = rid
                    lane.span_ids[sid] = rid
                    reader.span_ids[(s, sid)] = rid
                    open_streams[rid] = open_streams.get(rid, 0) + 1

    for s in shards:
        _admit(s)

    def _live_expected():
        return [key for key in expected if key not in abandoned]

    def _stream_done(key) -> bool:
        st = reader.streams.get(key)
        return st is not None and st.eos

    def _mark_suspect(s: int) -> None:
        # rank s stopped ACKing: drop its compute and lanes, abandon its
        # unfinished streams, and re-send every request that had not fully
        # streamed there to a healthy shard — once; a second failure is an
        # outage, not a flaky link.  Requests that already reached EOS on
        # s keep their streams (and tokens) untouched.
        suspects.add(s)
        batchers.pop(s, None)
        for key in [k for k in lanes if k[0] == s]:
            del lanes[key]
        for key in [k for k in writers if k[0] == s]:
            del writers[key]
        for key in [k for k in lp_writers if k[0] == s]:
            del lp_writers[key]
        # the fabric registry is always on (and IS `metrics` when one was
        # passed), so recovery stays observable either way
        fabric.metrics.counter("serve.suspects").add(1)
        inflight = []
        for k, i in enumerate(globals_of[s]):
            keys = [(s, (k << 16) | j) for j in range(len(reqs[i][1]))]
            if k < admitted[s] and all(_stream_done(key) for key in keys):
                continue
            for key in keys:
                abandoned.add(key)
                rid = reader.span_ids.get(key)
                if rid is not None and not _stream_done(key):
                    open_streams[rid] = open_streams.get(rid, 1) - 1
            if i in retried:
                raise RuntimeError(
                    f"streaming serve: request {i} failed on shard {s} "
                    f"after a retry — no healthy shard answered it")
            inflight.append(i)
        if not inflight:
            return
        repl = place_requests(
            fabric.router, len(inflight), shards, capacity=max(1, slots),
            weights=[len(reqs[i][1]) for i in inflight], exclude=suspects)
        for i, s2 in zip(inflight, repl):
            retried.add(i)
            globals_of[s2].append(i)
            if spans is not None and rid_of[i] is not None:
                spans.event(rid_of[i], "serve.retry", from_shard=s,
                            to_shard=s2)
            ingress.send(s2, wires[i], list_level=levels[i],
                         request_id=rid_of[i])
            fabric.metrics.counter("serve.retries").add(1)

    wait_since: Dict[int, int] = {}  # shard -> tick its current debt began

    def _check_suspects() -> None:
        for s in shards:
            if s in suspects:
                continue
            # only a shard that still owes something can be suspect — a
            # shard that finished its share goes legitimately quiet
            waiting = (
                admitted[s] < len(globals_of[s])
                or any(key[0] == s and key not in abandoned
                       and not _stream_done(key) for key in expected))
            if not waiting:
                wait_since.pop(s, None)
                continue
            # the horizon starts when the shard last spoke OR when its
            # current debt began, whichever is later — a shard that sat
            # legitimately idle before being handed a retry is not late
            since = wait_since.setdefault(s, fabric.ticks)
            heard = fabric.ticks_since_heard(0, s)
            waited = (fabric.ticks - t0_tick) if heard is None else heard
            waited = min(waited, fabric.ticks - since)
            if waited > suspect_after:
                _mark_suspect(s)

    # the streamed tick pipeline
    t_serve0 = time.perf_counter()
    seen_first: set = set()  # stream keys that produced their first token
    tok_count = [0, 0]  # [total tokens arrived, tokens this tick]

    def _pump() -> None:
        got = ingress.recv()
        if lp_reader is not None:
            # the reserved ListLevel partitions the two typed streams
            lp_got = [d for d in got if d.list_level == LOGPROB_STREAM_LEVEL]
            got = [d for d in got if d.list_level != LOGPROB_STREAM_LEVEL]
            for ev in lp_reader.feed(lp_got):
                key = (ev.src, ev.stream_id)
                if key in abandoned:
                    continue  # stale side-stream of a retried request
                if not ev.ok:
                    raise RuntimeError(
                        f"ingress: corrupt logprob stream chunks from "
                        f"shard {ev.src}"
                    )
                if on_logprob is not None:
                    k, j = ev.stream_id >> 16, ev.stream_id & 0xFFFF
                    m = globals_of[ev.src][k]
                    for t, (tok, bits) in enumerate(ev.tokens):
                        lpv = float(np.uint32(bits).view(np.float32))
                        on_logprob(m, j, ev.step + t, int(tok), lpv)
        for ev in reader.feed(got):
            key = (ev.src, ev.stream_id)
            if key in abandoned:
                continue  # stale chunks from a suspect shard's old stream
            if not ev.ok:
                raise RuntimeError(
                    f"ingress: corrupt stream chunks from shard {ev.src}"
                )
            tok_count[0] += len(ev.tokens)
            tok_count[1] += len(ev.tokens)
            if ev.tokens and key not in seen_first:
                seen_first.add(key)
                ttft = time.perf_counter() - t_serve0
                if metrics is not None:
                    metrics.histogram("serve.ttft_s", base=0.001).observe(ttft)
                    metrics.series("serve.ttft_s.series").append(ttft)
                if spans is not None and key in reader.span_ids:
                    spans.event(reader.span_ids[key], "serve.first_token",
                                ttft_s=ttft)
            if ev.eos and spans is not None and key in reader.span_ids:
                rid = reader.span_ids[key]
                open_streams[rid] = open_streams.get(rid, 1) - 1
                if open_streams[rid] <= 0:
                    spans.finish(rid)
            if trace is not None:
                trace.instant(
                    "stream.chunk", cat="stream", pid=ev.src,
                    args={"stream": ev.stream_id, "step": ev.step,
                          "tokens": len(ev.tokens),
                          "arrive_step": ev.arrive_step},
                )
            if on_event is not None:
                on_event(ev)
            if on_token is not None:
                k, j = ev.stream_id >> 16, ev.stream_id & 0xFFFF
                m = globals_of[ev.src][k]
                for t, tok in enumerate(ev.tokens):
                    on_token(m, j, ev.step + t, tok)
        per_class = (
            reader.class_arrive_stats(window=64)
            if (backpressure_p95 is not None or metrics is not None)
            else {}
        )
        if metrics is not None:
            # the live backpressure feedback values, recorded whether or
            # not a threshold acts on them — the observability of the loop
            # must not depend on the loop being closed
            for cls, st in per_class.items():
                metrics.series("serve.backpressure.p95",
                               cls=cls).append(st["p95"])
        if backpressure_p95 is not None:
            # close the loop: the reader's per-class p95 arrive latency
            # clamps (or releases) each lane's flush rate for next tick;
            # the sliding window lets a clamped tenant recover once its
            # congested tail has drained
            for lane in lanes.values():
                st = per_class.get(lane.list_level)
                lane.feedback(st["p95"] if st else None)

    tick = 0
    idle = 0
    drain_cap = (deadline_ticks or 256) if arq else 3
    force_flushed = False
    while True:
        active = any(b.pending or b.n_active for b in batchers.values())
        awaiting = any(admitted[s] < len(globals_of[s])
                       for s in shards if s not in suspects)
        if (not active and not awaiting
                and reader.all_eos(_live_expected())
                and (lp_reader is None
                     or lp_reader.all_eos(_live_expected()))):
            break
        tick += 1
        if spans is not None:
            spans.set_tick(tick)  # ingress was tick 0; the loop is 1..N
        if active:
            idle = 0
            force_flushed = False
            t_tick0 = trace.now_us() if trace is not None else 0.0
            tok_count[1] = 0
            for b in batchers.values():
                b.step_begin()  # dispatch compute; device runs in background
            if overlap:
                fabric.poll()  # reap last tick's chunks while decode runs
                _pump()
            for s, b in list(batchers.items()):
                for (k, j), pos, tok in b.step_finish():
                    eos = pos == max_new - 1
                    writers[(s, k, j)].write((tok,), eos=eos)
                    if logprobs:
                        # the logprob element is (tok, float32 bit
                        # pattern) — the schema's Struct{tok, logprob}
                        bits = int(np.float32(
                            b.tick_logprobs[((k, j), pos)]
                        ).view(np.uint32))
                        lp_writers[(s, k, j)].write(((tok, bits),), eos=eos)
            for lane in lanes.values():
                lane.flush()  # ONE burst per (shard, tenant) this tick
            if overlap:
                fabric.exchange_async()  # dispatch routing; overlap next tick
            else:
                fabric.exchange()
                _pump()
            if metrics is not None:
                metrics.series("serve.tick.tokens").append(tok_count[1])
            if trace is not None:
                trace.complete("serve.tick", t_tick0,
                               trace.now_us() - t_tick0, cat="serve",
                               args={"tokens_arrived": tok_count[1]})
        else:
            # nothing left to compute: force out any bursts a clamped
            # lane still holds, then keep the fabric ticking so in-flight
            # chunks, ARQ recovery traffic, and retried request wires land
            if not force_flushed:
                for lane in lanes.values():
                    lane.flush(force=True)
                force_flushed = True
            idle += 1
            if idle > drain_cap:
                raise RuntimeError(
                    "streaming serve: streams did not reach EOS")
            fabric.exchange()
            _pump()
        if watch:
            _check_suspects()
            for s in shards:
                if s not in suspects:
                    _admit(s)
    if metrics is not None:
        dt = max(time.perf_counter() - t_serve0, 1e-9)
        metrics.gauge("serve.tokens_per_s").set(tok_count[0] / dt)
        metrics.counter("serve.tokens").add(tok_count[0])
        metrics.gauge("fabric.load_drift.entries").set(
            len(fabric.load_drift())
        )

    # final wires from the streamed tokens — same bulk SER as the batched
    # plane, so the result is byte-identical to serve_requests
    outs: Dict[Tuple[int, int], List[int]] = {}
    for (src, sid), st in reader.streams.items():
        if (src, sid) in abandoned:
            continue  # a retried request's dead first attempt
        m = globals_of[src][sid >> 16]
        outs[(m, sid & 0xFFFF)] = st.tokens
    responses = [
        (rid, [outs[(m, j)] for j in range(len(prompts))])
        for m, (rid, prompts) in enumerate(reqs)
    ]
    return encode_response_batch(responses)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--n-requests", type=int, default=4)
    ap.add_argument("--n-prompts", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--pad-to", type=int, default=64)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--sequential", action="store_true",
                    help="use the seed one-wire-at-a-time path")
    ap.add_argument("--sharded", action="store_true",
                    help="route requests over the message fabric to "
                         "per-shard batchers (ranks 1..N serve, rank 0 ingress)")
    ap.add_argument("--streaming", action="store_true",
                    help="sharded serve with token-level streamed responses "
                         "(chunks ride the fabric back every decode tick)")
    ap.add_argument("--no-overlap", action="store_true",
                    help="disable the async fabric/compute overlap pipeline "
                         "for --streaming")
    ap.add_argument("--logprobs", action="store_true",
                    help="for --streaming: attach the typed logprob "
                         "side-stream (Stream<Struct{tok, logprob}> from "
                         "schema JSON); tokens are byte-identical either "
                         "way")
    ap.add_argument("--n-shards", type=int, default=None,
                    help="serving shards for --sharded/--streaming "
                         "(default: devices-1)")
    ap.add_argument("--routing", choices=("shortest", "dimension"),
                    default="shortest",
                    help="fabric routing mode for --sharded/--streaming: "
                         "per-frame shortest ring direction (default) or "
                         "the legacy +1-only dimension order")
    ap.add_argument("--defect-after", type=int, default=0,
                    help="congestion-aware routing: let a frame defect to "
                         "the opposite ring direction after its preferred "
                         "link has been credit-starved for this many "
                         "consecutive router steps (0 = static shortest)")
    ap.add_argument("--chaos", default=None, metavar="SPEC",
                    help="seeded deterministic fault injection on the serve "
                         "fabric: 'drop=0.02,corrupt=0.01,...' (see "
                         "repro.fabric.faults.parse_chaos); deterministic "
                         "in --seed")
    ap.add_argument("--no-arq", action="store_true",
                    help="disable ARQ reliable delivery on the serve fabric "
                         "(corruption is flagged, never recovered)")
    ap.add_argument("--suspect-after", type=int, default=24,
                    help="mark a shard suspect — and retry its in-flight "
                         "requests on a healthy shard — after this many "
                         "fabric ticks without hearing from it (needs ARQ; "
                         "0 disables)")
    ap.add_argument("--deadline-ticks", type=int, default=None,
                    help="max fabric ticks to wait on in-flight deliveries "
                         "before the serve gives up (default 256 with ARQ, "
                         "3 without)")
    ap.add_argument("--backpressure-p95", type=float, default=None,
                    help="for --streaming: clamp a tenant lane's flush "
                         "rate while its QoS class's p95 arrive latency "
                         "(router steps) exceeds this threshold")
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="write the run's metrics snapshot (repro.obs "
                         "registry + environment meta) as JSON")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome-trace JSON timeline of ticks, "
                         "chunk arrivals and recompiles (load in "
                         "chrome://tracing or ui.perfetto.dev)")
    ap.add_argument("--attribution-json", default=None, metavar="PATH",
                    help="for --streaming: write the per-request span "
                         "export (latency attribution + degradation) as "
                         "JSON; render with `python -m repro.obs "
                         "attribution PATH`")
    ap.add_argument("--slo", default=None, metavar="SPEC",
                    help="evaluate SLO targets against the run's metrics "
                         "('k=v,k=v' inline or a JSON file; see "
                         "repro.obs.slo) and exit 1 on any violation")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    metrics = trace = spans = None
    if args.metrics_json or args.trace_out or args.slo or args.attribution_json:
        from ..obs import MetricsRegistry, SpanTracker, TraceRecorder

        metrics = MetricsRegistry()
        if args.trace_out:
            trace = TraceRecorder()
        if args.attribution_json or args.trace_out:
            spans = SpanTracker(trace)

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    params = init_params(cfg, jax.random.PRNGKey(args.seed))

    serve_fabric = None
    if args.sharded or args.streaming:
        from ..fabric import parse_chaos

        faults = parse_chaos(args.chaos, args.seed) if args.chaos else None
        serve_fabric = default_serve_fabric(
            args.n_shards, routing=args.routing,
            defect_after=args.defect_after, arq=not args.no_arq,
            faults=faults)
    suspect_after = args.suspect_after if args.suspect_after > 0 else None

    rng = np.random.default_rng(args.seed)
    wires = []
    for r in range(args.n_requests):
        prompts = [
            list(map(int, rng.integers(2, cfg.vocab, rng.integers(4, 24))))
            for _ in range(args.n_prompts)
        ]
        wires.append(encode_request(r, prompts))
    total_b = sum(len(w) for w in wires)
    print(f"[serve] {len(wires)} request wires, {total_b} bytes total")
    t0 = time.time()
    first_tok_t = []
    lp_events = []
    if args.sequential:
        resp_wires = [
            serve_request(params, cfg, w, max_new=args.max_new,
                          pad_to=args.pad_to)
            for w in wires
        ]
    elif args.streaming:
        resp_wires = serve_requests_streaming(
            params, cfg, wires, max_new=args.max_new, pad_to=args.pad_to,
            slots=args.slots, n_shards=args.n_shards, fabric=serve_fabric,
            overlap=not args.no_overlap, routing=args.routing,
            defect_after=args.defect_after,
            backpressure_p95=args.backpressure_p95,
            metrics=metrics,
            trace=trace,
            spans=spans,
            suspect_after=suspect_after,
            deadline_ticks=args.deadline_ticks,
            logprobs=args.logprobs,
            on_logprob=(
                (lambda m, j, step, tok, lp: lp_events.append((tok, lp)))
                if args.logprobs else None
            ),
            on_token=lambda m, j, step, tok: first_tok_t.append(time.time())
            if not first_tok_t else None,
        )
    elif args.sharded:
        resp_wires = serve_requests_sharded(
            params, cfg, wires, max_new=args.max_new, pad_to=args.pad_to,
            slots=args.slots, n_shards=args.n_shards, fabric=serve_fabric,
            routing=args.routing, defect_after=args.defect_after,
            metrics=metrics, trace=trace,
            suspect_after=suspect_after,
            deadline_ticks=args.deadline_ticks,
        )
    else:
        resp_wires = serve_requests(
            params, cfg, wires, max_new=args.max_new, pad_to=args.pad_to,
            slots=args.slots,
        )
    dt = time.time() - t0
    n_tok = 0
    for rw in resp_wires:
        rid, outs = decode_response(rw)
        n_tok += sum(len(o) for o in outs)
    mode = ("sequential" if args.sequential
            else f"streaming(slots={args.slots})" if args.streaming
            else f"sharded(slots={args.slots})" if args.sharded
            else f"batched(slots={args.slots})")
    print(f"[serve] {mode}: {len(wires)} requests, {n_tok} tokens in {dt:.2f}s "
          f"({len(wires)/dt:.2f} req/s, {n_tok/dt:.1f} tok/s)")
    if first_tok_t:
        print(f"[serve] time-to-first-token {first_tok_t[0] - t0:.3f}s "
              f"(vs {dt:.2f}s total)")
    if lp_events:
        tok, lp = lp_events[0]
        print(f"[serve] logprob side-stream: {len(lp_events)} events "
              f"(first tok={tok}, lp={lp:.4f})")
    if args.metrics_json and metrics is not None:
        import json as _json

        from ..obs.report import environment_meta

        snap = metrics.snapshot()
        snap["meta"] = environment_meta()
        with open(args.metrics_json, "w") as f:
            _json.dump(snap, f, indent=1)
            f.write("\n")
        print(f"[serve] metrics snapshot -> {args.metrics_json} "
              f"({len(snap['metrics'])} metrics)")
    if args.trace_out and trace is not None:
        trace.save(args.trace_out)
        print(f"[serve] trace timeline -> {args.trace_out} "
              f"({len(trace.events)} events)")
    if args.attribution_json and spans is not None:
        import json as _json

        export = spans.export()
        with open(args.attribution_json, "w") as f:
            _json.dump(export, f, indent=1)
            f.write("\n")
        print(f"[serve] attribution export -> {args.attribution_json} "
              f"({len(export['requests'])} request span(s))")
    rid, outs = decode_response(resp_wires[0])
    for i, o in enumerate(outs[:2]):
        print(f"  req {rid} out[{i}][:8] = {o[:8]}")
    if args.slo and metrics is not None:
        from ..obs import evaluate_slo

        rep = evaluate_slo(args.slo, snapshot=metrics.snapshot())
        print(rep.render_text())
        if not rep.ok:
            raise SystemExit(1)


if __name__ == "__main__":
    main()
