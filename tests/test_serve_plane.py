"""Batched message plane: batched structure pass, batched decode, scheduler.

The batched plan/decode must be *bit-exact* against N independent scalar
``plan_from_wire`` + ``decode_message`` calls (the jnp oracle), including
ragged prompt counts, an empty-list request, and empty inner lists; and the
continuous-batching serve loop must reproduce the seed sequential path's
tokens exactly when both pad prompts to the same length.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    batch_plans, build_plan, decode_batch, decode_message, plan_from_wire,
    stack_wires, wire_to_u8,
)
from repro.data.schemas import request_schema
from repro.kernels.ops import decode_batch_kernel, wires_to_u32
from repro.launch.serve import (
    decode_request, decode_request_batch, decode_response, encode_request,
    serve_request, serve_requests, serve_requests_sharded,
    serve_requests_streaming,
)


def _random_request_wires(rng, n=6):
    """Ragged batch: includes a zero-prompt request and an empty token list."""
    wires, truth = [], []
    n_prompts = [0, 1, 3, 5, 2, 4]
    for m in range(n):
        prompts = [
            list(map(int, rng.integers(0, 2**31, rng.integers(0, 9))))
            for _ in range(n_prompts[m % len(n_prompts)])
        ]
        truth.append((100 + m, prompts))
        wires.append(encode_request(100 + m, prompts))
    return wires, truth


def test_batch_plans_matches_individual(rng):
    schema = request_schema()
    wires, _ = _random_request_wires(rng)
    bp = batch_plans(schema, wires)
    caps = {p: bp.cap(p) for p in bp.offsets}
    for i, w in enumerate(wires):
        sp = plan_from_wire(schema, w, caps=caps)
        assert sp.wire_len == int(bp.wire_lens[i]) == len(w)
        for p in sp.offsets:
            n = sp.counts[p]
            assert n == int(bp.counts[p][i])
            np.testing.assert_array_equal(sp.offsets[p][:n], bp.offsets[p][i, :n])
        # plan_for slices back to an equivalent scalar plan
        one = bp.plan_for(i)
        assert one.counts == sp.counts


def test_decode_batch_matches_scalar_oracle(rng):
    schema = request_schema()
    wires, _ = _random_request_wires(rng)
    bp = batch_plans(schema, wires)
    caps = {p: bp.cap(p) for p in bp.offsets}
    vals = decode_batch(jnp.asarray(stack_wires(wires)), bp)
    for i, w in enumerate(wires):
        ref = decode_message(wire_to_u8(w), plan_from_wire(schema, w, caps=caps))
        for p, v in vals.items():
            n = int(bp.counts[p][i])
            np.testing.assert_array_equal(np.asarray(v[i, :n]), np.asarray(ref[p][:n]))


def test_decode_batch_kernel_matches_oracle(rng):
    schema = request_schema()
    wires, _ = _random_request_wires(rng)
    bp = batch_plans(schema, wires)
    oracle = decode_batch(
        jnp.asarray(stack_wires(wires, pad_to=-(-max(len(w) for w in wires) // 4) * 4)),
        bp,
    )
    u32, row_bytes = wires_to_u32(wires)
    got = decode_batch_kernel(u32, row_bytes, bp)
    for p in oracle:
        for i in range(len(wires)):
            n = int(bp.counts[p][i])
            np.testing.assert_array_equal(
                np.asarray(got[p][i, :n]), np.asarray(oracle[p][i, :n])
            )


def test_decode_request_batch_roundtrip(rng):
    wires, truth = _random_request_wires(rng)
    assert decode_request_batch(wires) == truth
    # and agrees with the streaming-FSM scalar DES
    for w, t in zip(wires, truth):
        assert decode_request(w) == t


def test_plan_overflow_raises(rng):
    """Both structure passes must refuse an undersized cap (not truncate)."""
    schema = request_schema()
    msg = {"req_id": 1, "prompts": [{"tokens": [1, 2, 3, 4, 5]}]}
    wire = encode_request(1, [[1, 2, 3, 4, 5]])
    caps = {"prompts.elem.tokens.elem": 2}
    with pytest.raises(ValueError, match="exceed"):
        build_plan(schema, msg, caps=caps)
    with pytest.raises(ValueError, match="exceed"):
        plan_from_wire(schema, wire, caps=caps)
    with pytest.raises(ValueError, match="exceed"):
        batch_plans(schema, [wire], caps=caps)


def test_batch_plans_rejects_corrupt_count(rng):
    """A corrupted count field must fail that batch loudly (ValueError),
    not index numpy out of bounds or silently mis-decode."""
    schema = request_schema()
    good = encode_request(1, [[1, 2, 3]])
    bad = bytearray(encode_request(2, [[4, 5, 6]]))
    bad[8] = 0xFF  # prompts count (after the 8-byte req_id) -> 255 prompts
    with pytest.raises(ValueError, match="truncated or corrupt"):
        batch_plans(schema, [good, bytes(bad)])


@pytest.fixture(scope="module")
def tiny_serve():
    from repro.configs import get_config, smoke_config
    from repro.models import init_params

    cfg = dataclasses.replace(smoke_config(get_config("yi-6b")), n_layers=1)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return params, cfg


def test_scheduler_matches_sequential(tiny_serve, rng):
    """More sequences than slots -> admit/evict churn; outputs must equal
    the seed's per-request loop (same prompt pad length on both sides)."""
    params, cfg = tiny_serve
    pad_to = 8  # prompts >= 8 so the seed path also pads to exactly 8
    wires = [
        encode_request(r, [
            list(map(int, rng.integers(2, cfg.vocab, 8 + int(rng.integers(0, 4)))))
            for _ in range(2)
        ])
        for r in range(3)
    ]
    seq = [serve_request(params, cfg, w, max_new=4, pad_to=pad_to) for w in wires]
    bat = serve_requests(params, cfg, wires, max_new=4, pad_to=pad_to, slots=2)
    assert [decode_response(w) for w in bat] == [decode_response(w) for w in seq]


def test_serve_empty_request(tiny_serve):
    """A request with zero prompts flows through the whole plane — and
    through the sequential baseline."""
    params, cfg = tiny_serve
    wires = [encode_request(9, []), encode_request(10, [[5, 6, 7, 8]])]
    resp = serve_requests(params, cfg, wires, max_new=2, pad_to=8, slots=2)
    rid, outs = decode_response(resp[0])
    assert (rid, outs) == (9, [])
    rid, outs = decode_response(resp[1])
    assert rid == 10 and len(outs) == 1 and len(outs[0]) == 2
    assert decode_response(serve_request(params, cfg, wires[0])) == (9, [])


@pytest.mark.parametrize("arch", ["phi-3-vision-4.2b", "whisper-tiny"])
def test_scheduler_other_families(arch, rng):
    """The slot cache must match prefill's geometry for families whose KV
    grows beyond prompt_cap + max_new (vlm vision prefix, encdec enc_kv)."""
    from repro.configs import get_config, smoke_config
    from repro.models import init_params

    cfg = dataclasses.replace(smoke_config(get_config(arch)), n_layers=1)
    params = init_params(cfg, jax.random.PRNGKey(0))
    wires = [encode_request(0, [list(map(int, rng.integers(2, cfg.vocab, 8)))])]
    resp = serve_requests(params, cfg, wires, max_new=3, pad_to=8, slots=2)
    rid, outs = decode_response(resp[0])
    assert rid == 0 and len(outs) == 1 and len(outs[0]) == 3


@pytest.mark.parametrize("serve", [serve_requests_sharded, serve_requests_streaming])
def test_routed_planes_refuse_one_rank(serve):
    """Below 2 ranks there is no shard to route to: the routed planes raise
    instead of quietly serving the batched plane under their own name."""
    from repro.fabric import Fabric

    wires = [encode_request(1, [[5, 6, 7]])]
    with pytest.raises(ValueError, match=">= 2 ranks"):
        serve(None, None, wires, fabric=Fabric(n_ranks=1))
