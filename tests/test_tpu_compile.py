"""The serve path's Pallas kernels compile for a TPU v5e chip.

No chip is needed: the TPU compiler runs against a described v5e topology
and Mosaic refuses here what it would refuse on the chip (block tiling,
layouts, VMEM).  Sizes are serving sizes: a few thousand wire words and
1-2k rows.  Every test asserts that the compiled program holds the kernel
(``tpu_custom_call``) rather than an interpreted or XLA fallback.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.fabric.frames import HDR_WORDS
from repro.kernels import frame_pack, phit_unpack

os.environ.setdefault("TPU_LOG_DIR", "disabled")

WIRE_WORDS = 4096


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("nbytes,base,stride,count", [
    (4, 0, 4, 2048),  # aligned 4-byte tokens: the prompt-token run
    (8, 8, 8, 1024),  # aligned 8-byte leaf (req_id width)
    (8, 16, 1200, 8),  # aligned, one 8-byte leaf per 1200-byte wire row
    (4, 5, 4, 1536),  # unaligned base
    (8, 13, 9, 1024),  # unaligned base and stride
    (16, 0, 17, 1024),  # unaligned stride, 4-lane tokens
])
def test_unpack_run_compiles(one_chip, nbytes, base, stride, count):
    words = max(WIRE_WORDS, (base + stride * count) // 4 + 8)
    txt = _compile_text(
        lambda w: phit_unpack.unpack_run(w, base, stride, count, nbytes, interpret=False),
        one_chip, ((words,), jnp.uint32),
    )
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("nbytes,n", [(4, 1536), (8, 1024), (3, 2048)])
def test_unpack_gather_compiles(one_chip, nbytes, n):
    txt = _compile_text(
        lambda w, o: phit_unpack.unpack_gather(w, o, nbytes, interpret=False),
        one_chip, ((WIRE_WORDS,), jnp.uint32), ((n,), jnp.int32),
    )
    assert "tpu_custom_call" in txt


def test_pack_chunks_batch_compiles(one_chip):
    # one token-chunk row per live sequence per tick
    txt = _compile_text(
        lambda m, t, c: frame_pack.pack_chunks_batch(m, t, c, interpret=False),
        one_chip, ((1024, 3), jnp.uint32), ((1024, 16), jnp.uint32),
        ((1024, 1), jnp.uint32),
    )
    assert "tpu_custom_call" in txt


def test_pack_frames_batch_compiles(one_chip):
    # 64 streams of 16 frames of 16 phits
    txt = _compile_text(
        lambda h, p: frame_pack.pack_frames_batch(h, p, interpret=False),
        one_chip, ((64, 16, HDR_WORDS), jnp.uint32), ((64, 16, 64), jnp.uint32),
    )
    assert "tpu_custom_call" in txt


def test_unpack_frames_batch_compiles(one_chip):
    txt = _compile_text(
        lambda f: frame_pack.unpack_frames_batch(f, interpret=False),
        one_chip, ((1024, HDR_WORDS + 64), jnp.uint32),
    )
    assert "tpu_custom_call" in txt


def _serve_step_entry(cfg, sharding, batch, cache_len):
    """The compiled decode step's entry computation, as the batcher jits it
    (cache donated), for a described v5e chip."""
    from repro.launch.steps import make_serve_step
    from repro.models import init_cache, init_params

    def spec(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree)

    params = spec(jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0))))
    cache = spec(jax.eval_shape(lambda: init_cache(cfg, batch, cache_len)))
    tokens = jax.ShapeDtypeStruct((batch, 1), jnp.int32, sharding=sharding)
    step = jax.jit(make_serve_step(cfg), donate_argnums=(1,))
    txt = step.lower(params, cache, tokens).compile().as_text()
    entry = txt[txt.index("\nENTRY"):]
    return entry[:entry.index("\n}")].splitlines()


_INSTR = re.compile(r"\s*(?:ROOT )?(%\S+) = (\(.*?\)|\S+) ([\w-]+)\((.*)")


def _layouts(type_str, shape):
    return set(re.findall(re.escape(shape) + r"\{([\d,]+)", type_str))


def _ops_out(lines, opcodes, shape):
    """Instructions of ``opcodes`` whose output type holds ``shape``."""
    hits = []
    for line in lines:
        m = _INSTR.match(line)
        if m and m.group(3) in opcodes and _layouts(m.group(2), shape):
            hits.append(line)
    return hits


def _relayouts(lines, shape):
    """Copies that change the layout of an array of ``shape``.  A copy that
    keeps the layout only moves the array between memory spaces."""
    types, hits = {}, []
    for line in lines:
        m = _INSTR.match(line)
        if not m:
            continue
        name, ty, op, rest = m.groups()
        types[name] = ty
        if op in ("copy", "copy-start"):
            src = re.match(r"%[\w.-]+", rest)
            seen = _layouts(ty, shape) | _layouts(types.get(src and src.group(0), ""), shape)
            if len(seen) > 1:
                hits.append(line)
    return hits


def test_stablelm_decode_writes_cache_in_place(one_chip):
    # head dim 80: the cache is stored T-minor; the decode step's row write
    # must not relayout the whole cache around it
    from repro.configs import get_config

    cfg = dataclasses.replace(
        get_config("stablelm-3b"), n_layers=2, norm="layernorm", norm_eps=1e-5,
        tie_embeddings=False)
    lines = _serve_step_entry(cfg, one_chip, batch=16, cache_len=1152)
    full = "bf16[16,1152,32,80]"
    assert _relayouts(lines, full) == []
    assert _ops_out(lines, {"dynamic-update-slice"}, full)


def test_yi_decode_keeps_batched_scatter(one_chip):
    # head dim 128: the cache is stored D-minor and the scatter is native
    from repro.configs import get_config

    cfg = dataclasses.replace(get_config("yi-6b"), n_layers=2)
    lines = _serve_step_entry(cfg, one_chip, batch=32, cache_len=384)
    full = "bf16[32,384,4,128]"
    scatters = [l for l in _ops_out(lines, {"fusion"}, full) if "/scatter" in l]
    assert len(scatters) == 2 * cfg.n_layers  # one per K and per V
    assert _relayouts(lines, full) == []
    assert _ops_out(lines, {"dynamic-update-slice"}, full) == []
