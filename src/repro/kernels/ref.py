"""Pure-jnp oracles for the HGum kernels (tests assert allclose against these)."""
from __future__ import annotations

import jax.numpy as jnp

from ..core.vectorized import decode_leaf


def wire_u32_to_u8(wire_u32: jnp.ndarray) -> jnp.ndarray:
    """uint32 lanes -> little-endian uint8 stream."""
    shifts = jnp.array([0, 8, 16, 24], jnp.uint32)
    b = (wire_u32[:, None] >> shifts[None, :]) & jnp.uint32(0xFF)
    return b.reshape(-1).astype(jnp.uint8)


def unpack_run_ref(
    wire_u32: jnp.ndarray, base: int, stride: int, count: int, nbytes: int
) -> jnp.ndarray:
    """Oracle for phit_unpack.unpack_run (via core.vectorized.decode_leaf)."""
    wire_u8 = wire_u32_to_u8(wire_u32)
    offsets = base + stride * jnp.arange(count, dtype=jnp.int32)
    return decode_leaf(wire_u8, offsets, nbytes)


def unpack_gather_ref(
    wire_u32: jnp.ndarray, offsets: jnp.ndarray, nbytes: int
) -> jnp.ndarray:
    """Oracle for phit_unpack.unpack_gather."""
    return decode_leaf(wire_u32_to_u8(wire_u32), offsets, nbytes)


def pack_run_ref(tokens: jnp.ndarray, stride: int, nbytes: int) -> jnp.ndarray:
    """Oracle for frame_pack.pack_run: scatter lanes at pitch `stride`."""
    n, nlanes = tokens.shape
    masks = []
    for j in range(nlanes):
        rem = nbytes - 4 * j
        masks.append(
            0xFFFFFFFF if rem >= 4 else ((1 << (8 * max(rem, 0))) - 1)
        )
    toks = tokens & jnp.asarray(masks, jnp.uint32)[None, :]
    stride_w = stride // 4
    buf = jnp.zeros((n, stride_w), jnp.uint32)
    buf = buf.at[:, :nlanes].set(toks)
    return buf.reshape(n * stride_w)
