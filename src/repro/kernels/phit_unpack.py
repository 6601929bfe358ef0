"""Pallas TPU kernel: HGum DES payload pass (phit stream -> token lanes).

The FPGA DES emits one <=16B token per cycle from the phit stream; the TPU
analogue emits a *tile* of tokens per grid step (DESIGN.md §3).  Two kernels:

* ``unpack_run``     — uniform-width run: instance i sits at byte
  ``base + i*stride``.  This is the bulk path (the paper's Fig. 14 schema —
  long Array/List of fixed-size elements — is exactly one run).  The aligned
  case (base, stride multiples of 4) views the run as a ``(count, stride/4)``
  word matrix and keeps the first lanes of each row; any other base/stride
  goes through the gather kernel with the run's offsets.
* ``unpack_gather``  — arbitrary per-instance byte offsets (ragged
  containers).  The wire sits in VMEM as ``(rows, 128)`` u32 tiles and the
  offsets in SMEM; per instance, one aligned 16-row window load, a sublane
  and a lane rotate bring its words to lane 0, and a shift-combine fixes
  the byte phase.  Eight instances fill one ``(8, 128)`` output tile.

Wire layout: uint32 little-endian lanes (``ops.wire_to_u32`` pads the tail).
Outputs are (N, nlanes) uint32 lanes, identical to ``ref.decode_leaf_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK = 256  # instances per grid step
LANES = 128  # u32 words per wire row (the TPU lane width)
_SUB = 8  # sublanes per u32 tile: instances per output tile
_WIN = 2 * _SUB  # wire rows per gather window (an instance spans <= 2 rows)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _lane_mask(nbytes: int, width: int) -> jnp.ndarray:
    """(1, width) u32 masks zeroing bytes beyond `nbytes` (lanes past the
    token are 0).

    Computed from a 2-D iota (not a literal array) so it can be
    materialized inside a Pallas kernel body without becoming a captured
    constant.
    """
    j = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    rem = nbytes - 4 * j
    partial = (jnp.uint32(1) << (8 * jnp.clip(rem, 0, 3)).astype(jnp.uint32)) - 1
    return jnp.where(
        rem >= 4, jnp.uint32(0xFFFFFFFF), jnp.where(rem <= 0, jnp.uint32(0), partial)
    )


def _block_rows(count: int) -> int:
    return min(BLOCK, _round_up(max(count, 1), _SUB))


# ---------------------------------------------------------------------------
# uniform-run unpack
# ---------------------------------------------------------------------------


def _run_kernel_aligned(rows_ref, out_ref, *, nbytes: int):
    """base%4 == 0 and stride%4 == 0: row i of the word matrix is token i."""
    nlanes = out_ref.shape[1]
    out_ref[...] = rows_ref[:, :nlanes] & _lane_mask(nbytes, nlanes)


def unpack_run(
    wire_u32: jnp.ndarray,  # (W,) uint32 (padded; see ops.wire_to_u32)
    base: int,
    stride: int,
    count: int,  # static capacity (rows); mask invalid rows downstream
    nbytes: int,
    *,
    interpret: bool,
) -> jnp.ndarray:
    """Unpack `count` fixed-width fields at base + i*stride.  Static shapes."""
    if not isinstance(base, int):
        raise TypeError("unpack_run: base must be a static python int")
    nlanes = (nbytes + 3) // 4
    if not (base % 4 == 0 and stride % 4 == 0 and nbytes >= 1):
        # rows past the wire read zeros, as in the aligned case
        need = (base + count * stride) // 4 + nlanes + 1
        if wire_u32.shape[0] < need:
            wire_u32 = jnp.pad(wire_u32, (0, need - wire_u32.shape[0]))
        offsets = base + stride * jnp.arange(count, dtype=jnp.int32)
        return unpack_gather(wire_u32, offsets, nbytes, interpret=interpret)

    blk = _block_rows(count)
    cap = _round_up(count, blk)
    stride_w = stride // 4
    base_w = base // 4
    need = base_w + cap * stride_w
    if wire_u32.shape[0] < need:
        wire_u32 = jnp.pad(wire_u32, (0, need - wire_u32.shape[0]))
    rows = jax.lax.dynamic_slice(wire_u32, (base_w,), (cap * stride_w,))
    out = pl.pallas_call(
        functools.partial(_run_kernel_aligned, nbytes=nbytes),
        grid=(cap // blk,),
        in_specs=[pl.BlockSpec((blk, stride_w), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((blk, nlanes), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((cap, nlanes), jnp.uint32),
        interpret=interpret,
        name="hgum_unpack_run",
    )(rows.reshape(cap, stride_w))
    return out[:count]


# ---------------------------------------------------------------------------
# gather unpack (ragged offsets)
# ---------------------------------------------------------------------------


def _gather_kernel(off_ref, wire_ref, out_ref, *, nbytes: int):
    n_rows = wire_ref.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    tile_row = jax.lax.broadcasted_iota(jnp.int32, (_SUB, LANES), 0)
    mask = _lane_mask(nbytes, LANES)

    def group(g, carry):
        first = pl.multiple_of(g * _SUB, _SUB)

        def one(k, acc):
            off = off_ref[0, 0, first + k]
            w = off // 4
            r = off % 4
            row = w // LANES
            col = w % LANES
            # aligned 16-row window holding the token's <= 2 wire rows
            start = jnp.minimum((row // _SUB) * _SUB, n_rows - _WIN)
            win = wire_ref[pl.ds(pl.multiple_of(start, _SUB), _WIN), :]
            # sublane rotate: the token's row to 0, the next row to 1
            win = pltpu.roll(win, (_WIN - (row - start)) % _WIN, 0)
            # lane rotate: word w to lane 0 (words past the row end come
            # from the next row)
            shift = (LANES - col) % LANES
            a = pltpu.roll(win[0:1, :], shift, 1)
            b = pltpu.roll(win[1:2, :], shift, 1)
            words = jnp.where(lane + col < LANES, a, b)  # words[j] = wire[w+j]
            nxt = pltpu.roll(words, LANES - 1, 1)  # nxt[j] = wire[w+j+1]
            rs = (8 * r).astype(jnp.uint32)
            lo = words >> rs
            hi = jnp.where(r == 0, jnp.uint32(0), nxt << ((32 - rs) % 32))
            return jnp.where(tile_row == k, (lo | hi) & mask, acc)

        acc = jax.lax.fori_loop(0, _SUB, one, jnp.zeros((_SUB, LANES), jnp.uint32))
        out_ref[pl.ds(first, _SUB), :] = acc
        return carry

    jax.lax.fori_loop(0, out_ref.shape[0] // _SUB, group, 0)


def unpack_gather(
    wire_u32: jnp.ndarray,
    offsets: jnp.ndarray,  # (cap,) int32 byte offsets
    nbytes: int,
    *,
    interpret: bool,
) -> jnp.ndarray:
    nlanes = (nbytes + 3) // 4
    if nlanes + 1 > LANES:
        raise ValueError(f"unpack_gather: {nbytes}-byte tokens exceed one wire row")
    n = offsets.shape[0]
    blk = _block_rows(n)
    cap = _round_up(n, blk)
    offsets = jnp.pad(offsets.astype(jnp.int32), (0, cap - n))
    # (rows, 128) tiles plus one spare tile, so every in-range token's
    # window [start, start + 16) stays inside the wire
    n_rows = _round_up(-(-wire_u32.shape[0] // LANES), _SUB) + _SUB
    wire = jnp.pad(wire_u32, (0, n_rows * LANES - wire_u32.shape[0]))
    out = pl.pallas_call(
        functools.partial(_gather_kernel, nbytes=nbytes),
        grid=(cap // blk,),
        in_specs=[
            pl.BlockSpec((1, 1, blk), lambda i: (i, 0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((n_rows, LANES), lambda i: (0, 0)),  # wire resident
        ],
        out_specs=pl.BlockSpec((blk, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((cap, LANES), jnp.uint32),
        interpret=interpret,
        name="hgum_unpack_gather",
    )(offsets.reshape(cap // blk, 1, blk), wire.reshape(n_rows, LANES))
    return out[:n, :nlanes]
