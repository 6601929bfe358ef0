"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels import (
    decode_gather, decode_message_kernel, decode_run, encode_run, wire_to_u32,
)
from repro.kernels import ref
from repro.kernels.ops import runs_from_plan


@pytest.mark.parametrize("nbytes", [1, 2, 3, 4, 7, 8, 12, 16])
@pytest.mark.parametrize("base,stride_kind", [
    (0, "tight"), (4, "tight"), (5, "padded"), (13, "word"), (0, "word"),
])
def test_unpack_run_vs_oracle(rng, nbytes, base, stride_kind):
    stride = {
        "tight": nbytes, "padded": nbytes + 1, "word": ((nbytes + 3) // 4) * 4
    }[stride_kind]
    stride = max(stride, nbytes)
    for count in (1, 5, 300):
        wirelen = base + stride * count + 16
        w32 = wire_to_u32(rng.integers(0, 256, wirelen, dtype=np.uint8).tobytes())
        got = decode_run(w32, base, stride, count, nbytes)
        want = ref.unpack_run_ref(w32, base, stride, count, nbytes)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("nbytes", [1, 3, 4, 8, 16])
@pytest.mark.parametrize("n", [1, 17, 256, 513])
def test_unpack_gather_vs_oracle(rng, nbytes, n):
    offs = np.sort(rng.choice(8000, size=n, replace=False)).astype(np.int32)
    w32 = wire_to_u32(rng.integers(0, 256, 8192 + 32, dtype=np.uint8).tobytes())
    got = decode_gather(w32, jnp.asarray(offs), nbytes)
    want = ref.unpack_gather_ref(w32, jnp.asarray(offs), nbytes)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("nbytes", [1, 4, 8, 13, 16])
@pytest.mark.parametrize("n", [1, 256, 517])
def test_pack_run_vs_oracle(rng, nbytes, n):
    nlanes = (nbytes + 3) // 4
    for stride in (nlanes * 4, nlanes * 4 + 4, 32):
        toks = jnp.asarray(rng.integers(0, 2**32, (n, nlanes), dtype=np.uint32))
        got = encode_run(toks, stride, nbytes)
        want = ref.pack_run_ref(toks, stride, nbytes)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_pack_unpack_roundtrip(rng):
    toks = jnp.asarray(rng.integers(0, 2**32, (300, 4), dtype=np.uint32))
    wire = encode_run(toks, 16, 16)
    back = decode_run(wire, 0, 16, 300, 16)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(toks))


def test_decode_message_kernel_end_to_end(rng):
    from repro.core import (Schema, build_plan, lanes_to_int, random_message,
                            ser_sw_to_hw)
    schema = Schema.from_json({
        "Msg": [["hdr", ["Bytes", 8]],
                 ["a", ["List", ["Array", ["Struct", "T"]]]],
                 ["tail", ["Bytes", 2]]],
        "T": [["x", ["Bytes", 4]], ["y", ["Bytes", 8]]],
    })
    for i in range(10):
        msg = random_message(schema, np.random.default_rng(i), max_elems=6)
        wire = ser_sw_to_hw(schema, msg)
        plan = build_plan(schema, msg)
        dec = decode_message_kernel(wire_to_u32(wire), plan)
        xs = [e["x"] for arr in msg["a"] for e in arr]
        ys = [e["y"] for arr in msg["a"] for e in arr]
        got_x = lanes_to_int(np.asarray(dec["a.elem.elem.x"]), 4)[: len(xs)]
        got_y = lanes_to_int(np.asarray(dec["a.elem.elem.y"]), 8)[: len(ys)]
        assert list(got_x) == xs and list(got_y) == ys
        assert lanes_to_int(np.asarray(dec["hdr"]), 8)[0] == msg["hdr"]


def test_runs_from_plan_detects_uniform(rng):
    from repro.core import Schema, build_plan, random_message
    schema = Schema.from_json({"M": [["a", ["Array", ["Bytes", 16]]]]})
    msg = {"a": [1, 2, 3, 4]}
    plan = build_plan(schema, msg)
    assert runs_from_plan(plan, "a.elem") == (4, 16)
