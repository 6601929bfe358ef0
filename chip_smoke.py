"""Chip smoke test: serve yi-6b at its published widths on a TPU.

One process drives the system's main path once and checks what comes out:

  python chip_smoke.py               # one chip, phases (a)-(e)
  python chip_smoke.py --four-chips  # four chips: streamed serving over
                                     # the routed fabric vs the batched plane

One chip:
  (a) device check — the first device must be a TPU; anything else exits
      non-zero before any work;
  (b) compiled DES kernel — the request wires decode through
      ``decode_batch_kernel(..., interpret=False)``, equal leaf for leaf to
      the jnp ``decode_batch`` and to the host ``DesFSM``; the compiled
      program must hold one ``tpu_custom_call`` per leaf;
  (c) serve — ``serve_requests`` (batched DES -> ContinuousBatcher -> bulk
      SER) answers the burst; every response decodes to its request id with
      ``MAX_NEW`` tokens per prompt, all in ``[0, vocab)``;
  (d) compiled chunk SER kernel — the generated tokens, as token-chunk
      bursts through ``encode_fragment_burst`` (the Pallas
      ``encode_chunks_batch``), are byte-identical to the host codec;
  (e) reference — the sequential seed path ``serve_request`` answers the
      same wires; token agreement is printed, and does not decide ``ok``.

Four chips: ``serve_requests_streaming`` with 3 shards behind a rank-0
ingress (ARQ on, the serve default) and ``serve_requests`` on the same
burst; the final wires must be byte-identical.  It prints the device each
fabric rank sits on and the device each shard's KV cache and steps use.

Weights are random from ``--seed``; so are the requests.  The last line of
standard output is ``{"ok": true, "device": {...}}``; any failed check
raises and exits non-zero without it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import (
    batch_plans,
    decode_batch,
    decode_fragments,
    encode_fragment,
    encode_fragment_burst,
    stack_wires,
)
from repro.core.stream_plans import Fragment
from repro.data.schemas import request_schema
from repro.kernels import (
    decode_batch_kernel,
    encode_chunks_batch,
    wires_to_u32,
)
from repro.launch.compile_cache import use_compile_cache
from repro.launch.serve import (
    REQUEST_PATHS,
    decode_request,
    decode_response,
    default_serve_fabric,
    encode_request,
    requests_from_lanes,
    serve_request,
    serve_requests,
    serve_requests_streaming,
)
from repro.models import init_params
from repro.obs import MetricsRegistry
from repro.stream.chunks import token_stream_plan

ARCH = "yi-6b"
N_REQUESTS = 8
N_PROMPTS = 4
PAD_TO = 64  # prompts are >= PAD_TO tokens, so both serve paths pad alike
MAX_NEW = 16
SLOTS = 8  # 32 sequences through 8 slots: four admit waves


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAIL: {what}")


def make_requests(seed: int, vocab: int):
    """N_REQUESTS request wires of N_PROMPTS prompts, from the seed."""
    rng = np.random.default_rng(seed)
    reqs = []
    for _ in range(N_REQUESTS):
        rid = int(rng.integers(1, 1 << 62))  # exercises both u32 lanes
        prompts = [
            rng.integers(2, vocab, int(rng.integers(PAD_TO, PAD_TO + 32))).tolist()
            for _ in range(N_PROMPTS)
        ]
        reqs.append((rid, prompts))
    return reqs, [encode_request(rid, p) for rid, p in reqs]


def custom_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def phase_des(dev: str, reqs, wires) -> None:
    """(b) the Pallas DES kernel, compiled, vs the jnp plane and the FSM."""
    bplan = batch_plans(request_schema(), wires, record_paths=REQUEST_PATHS)
    u32, row_bytes = wires_to_u32(wires)
    des = jax.jit(lambda w: decode_batch_kernel(
        w, row_bytes, bplan, paths=REQUEST_PATHS, interpret=False))
    t0 = time.perf_counter()
    compiled = des.lower(u32).compile()
    t_compile = time.perf_counter() - t0
    n_calls = custom_calls(compiled)
    print(f"[{dev}] DES kernel: {n_calls} tpu_custom_call for "
          f"{len(REQUEST_PATHS)} leaves, compile {t_compile:.6f} s")
    check(n_calls >= len(REQUEST_PATHS), "DES kernel did not compile to Mosaic")
    t0 = time.perf_counter()
    got = jax.block_until_ready(compiled(u32))
    print(f"[{dev}] DES kernel run: {time.perf_counter() - t0:.6f} s "
          f"({sum(len(w) for w in wires)} wire bytes)")
    want = decode_batch(jnp.asarray(stack_wires(wires)), bplan, paths=REQUEST_PATHS)
    for p in REQUEST_PATHS:
        check(np.array_equal(np.asarray(got[p]), np.asarray(want[p])),
              f"DES kernel differs from decode_batch on leaf {p}")
    fsm = [decode_request(w) for w in wires]
    check(fsm == reqs, "host DesFSM does not return the encoded requests")
    check(requests_from_lanes(got, bplan) == fsm,
          "DES kernel requests differ from the host DesFSM")
    print(f"[{dev}] DES kernel == decode_batch == DesFSM on {len(wires)} wires")


def check_responses(resp_wires, reqs, vocab: int):
    """(c) every response answers its request: id, count, token range."""
    outs = []
    for (rid, prompts), w in zip(reqs, resp_wires):
        got_rid, outputs = decode_response(w)
        check(got_rid == rid, f"response id {got_rid} != request id {rid}")
        check(len(outputs) == len(prompts), f"request {rid}: wrong output count")
        for o in outputs:
            check(len(o) == MAX_NEW, f"request {rid}: {len(o)} tokens, not {MAX_NEW}")
            check(all(0 <= t < vocab for t in o), f"request {rid}: token out of range")
        outs.append(outputs)
    return outs


def phase_chunk_ser(dev: str, outs) -> None:
    """(d) token-chunk bursts via the compiled chunk SER kernel vs host."""
    plan = token_stream_plan()
    seqs = [((m << 16) | j, toks) for m, outputs in enumerate(outs)
            for j, toks in enumerate(outputs)]
    # one burst per decode step (one token per stream, as the streaming
    # plane emits them) and one burst of whole sequences
    bursts = [
        [Fragment(sid, t, (toks[t],), eos=t == MAX_NEW - 1) for sid, toks in seqs]
        for t in range(MAX_NEW)
    ]
    bursts.append([Fragment(sid, 0, tuple(toks), eos=True) for sid, toks in seqs])
    n_bytes = 0
    for frags in bursts:
        dev_wire = encode_fragment_burst(plan, frags)
        host_wire = b"".join(
            encode_fragment(plan, f.stream_id, f.step, f.tokens, f.eos) for f in frags)
        check(dev_wire == host_wire, "chunk SER kernel differs from the host codec")
        back, ok = decode_fragments(plan, dev_wire)
        check(ok and back == frags, "chunk burst does not parse back")
        n_bytes += len(dev_wire)
    # the kernel program at the whole-sequence burst's shapes
    bp = 1 << (len(seqs) - 1).bit_length()
    cap = 1 << (MAX_NEW - 1).bit_length()
    compiled = encode_chunks_batch.lower(
        jax.ShapeDtypeStruct((bp, 3), jnp.uint32),
        jax.ShapeDtypeStruct((bp, cap), jnp.uint32),
        jax.ShapeDtypeStruct((bp,), jnp.uint32),
    ).compile()
    check(custom_calls(compiled) >= 1, "chunk SER kernel did not compile to Mosaic")
    print(f"[{dev}] chunk SER kernel == host codec on {len(bursts)} bursts "
          f"({n_bytes} bytes), tpu_custom_call present")


def phase_reference(dev: str, params, cfg, wires, outs) -> None:
    """(e) the sequential seed path on the same wires (report only)."""
    t0 = time.perf_counter()
    seq = [decode_response(serve_request(params, cfg, w, max_new=MAX_NEW, pad_to=PAD_TO))[1]
           for w in wires]
    dt = time.perf_counter() - t0
    n_tok = same_tok = n_seq = same_seq = 0
    for a_req, b_req in zip(outs, seq):
        for a, b in zip(a_req, b_req):
            n_seq += 1
            same_seq += a == b
            n_tok += len(a)
            same_tok += sum(x == y for x, y in zip(a, b))
    print(f"[{dev}] sequential serve_request: {dt:.6f} s for {len(wires)} wires "
          f"(one jit per request, compile included)")
    print(f"[{dev}] greedy tokens vs sequential path: {same_tok}/{n_tok} tokens, "
          f"{same_seq}/{n_seq} sequences identical")


def peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def serve_timed(dev: str, label: str, fn):
    t0 = time.perf_counter()
    out = fn()
    dt = time.perf_counter() - t0
    print(f"[{dev}] {label}: {dt:.6f} s")
    return out, dt


def run_one_chip(dev: str, params, cfg, reqs, wires) -> None:
    phase_des(dev, reqs, wires)

    def serve():
        return serve_requests(params, cfg, wires, max_new=MAX_NEW,
                              pad_to=PAD_TO, slots=SLOTS)

    resp, t_cold = serve_timed(dev, "serve_requests cold (compile + run)", serve)
    resp_warm, t_warm = serve_timed(dev, "serve_requests warm", serve)
    n_tok = N_REQUESTS * N_PROMPTS * MAX_NEW
    print(f"[{dev}] serve compile overhead (cold - warm): {t_cold - t_warm:.6f} s; "
          f"warm {n_tok / t_warm:.6f} tok/s over {N_REQUESTS} requests")
    check(resp_warm == resp, "two serves of the same burst differ")
    outs = check_responses(resp, reqs, cfg.vocab)
    print(f"[{dev}] {len(resp)} responses: ids, counts and token range ok")
    phase_chunk_ser(dev, outs)
    phase_reference(dev, params, cfg, wires, outs)


def run_four_chips(dev: str, params, cfg, reqs, wires) -> None:
    batched, _ = serve_timed(dev, "serve_requests (batched plane)", lambda: serve_requests(
        params, cfg, wires, max_new=MAX_NEW, pad_to=PAD_TO, slots=SLOTS))
    check_responses(batched, reqs, cfg.vocab)
    fabric = default_serve_fabric(n_shards=3)  # ARQ on: the serve default
    check(fabric is not None and fabric.config.arq, "no 4-rank ARQ fabric")
    ranks = list(fabric.router.mesh.devices.flat)
    print(f"[{dev}] fabric ranks -> devices: "
          + ", ".join(f"rank {r}: {d}" for r, d in enumerate(ranks)))
    metrics = MetricsRegistry()
    streamed, _ = serve_timed(
        dev, "serve_requests_streaming (3 shards, compile + run)",
        lambda: serve_requests_streaming(
            params, cfg, wires, max_new=MAX_NEW, pad_to=PAD_TO, slots=SLOTS,
            fabric=fabric, metrics=metrics))
    flat = metrics.flat()
    by_id = {d.id: d for d in jax.devices()}
    for s in range(1, len(ranks)):
        key = f"serve.shard.device{{shard={s}}}"
        where = by_id[int(flat[key])] if key in flat else "no requests placed"
        print(f"[{dev}] shard {s}: fabric rank on {ranks[s]}, KV cache and "
              f"prefill/decode steps on {where}")
    check(streamed == batched, "streamed wires differ from the batched plane")
    print(f"[{dev}] streamed wires byte-identical to serve_requests "
          f"({len(streamed)} responses)")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 3-shard streamed plane vs the batched "
                         "plane, across four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    # (a) the device: a TPU, or nothing runs
    devices = jax.devices()
    d0 = devices[0]
    dev = f"{d0.platform}:{d0.device_kind} x{len(devices)}"
    print(f"[device] platform={d0.platform} kind={d0.device_kind} count={len(devices)}")
    check(d0.platform == "tpu", f"no TPU: JAX's first device is {d0.platform}")
    if args.four_chips:
        check(len(devices) >= 4, f"--four-chips needs 4 devices, found {len(devices)}")
    cache = use_compile_cache()
    warm = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    print(f"[{dev}] compile cache: {cache} ({warm} entries at start)")

    cfg = get_config(ARCH)
    print(f"[{dev}] {ARCH}: {cfg.n_layers} layers, d {cfg.d_model}, "
          f"{cfg.n_heads}/{cfg.n_kv} heads, d_ff {cfg.d_ff}, vocab {cfg.vocab}, "
          f"{cfg.dtype}")
    t0 = time.perf_counter()
    params = jax.block_until_ready(init_params(cfg, jax.random.PRNGKey(args.seed)))
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    print(f"[{dev}] init_params: {n_bytes} bytes in "
          f"{time.perf_counter() - t0:.6f} s")
    reqs, wires = make_requests(args.seed, cfg.vocab)
    print(f"[{dev}] {len(wires)} request wires, {sum(len(w) for w in wires)} bytes, "
          f"{N_REQUESTS * N_PROMPTS} prompts of >= {PAD_TO} tokens")

    if args.four_chips:
        run_four_chips(dev, params, cfg, reqs, wires)
    else:
        run_one_chip(dev, params, cfg, reqs, wires)
    print(f"[{dev}] peak_bytes_in_use (device 0): {peak_bytes()}")
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind, "count": len(devices)}}))


if __name__ == "__main__":
    main()
