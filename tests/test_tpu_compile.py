"""The serve path's Pallas kernels compile for a TPU v5e chip.

No chip is needed: the TPU compiler runs against a described v5e topology
and Mosaic refuses here what it would refuse on the chip (block tiling,
layouts, VMEM).  Sizes are serving sizes: a few thousand wire words and
1-2k rows.  Every test asserts that the compiled program holds the kernel
(``tpu_custom_call``) rather than an interpreted or XLA fallback.
"""
import os

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
